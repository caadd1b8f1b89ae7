"""Device milliseconds per step of the block-top-k decode-sum kernel's
calls, summed per chip and averaged over chips; nothing where none ran."""


def read(f):
    if f.trace is None or not f.trace.kernel_s.get("unpack"):
        return None
    return 1e3 * f.trace.kernel_s["unpack"] / f.steps
