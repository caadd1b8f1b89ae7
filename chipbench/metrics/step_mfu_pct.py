"""The whole step's share of the chips' bf16 peak: the configuration's
forward and backward operations per token (chipbench/models) times the
traced run's tokens per second, over chips times the published peak."""


def read(f):
    return (100.0 * f.flops_per_token * f.tokens / f.window_s
            / (f.chips * f.peaks["bf16_flops"]))
