"""The attention kernels' share of their roofline: the least time one
step's causal attention could take on this chip (the larger of bytes over
HBM bandwidth and operations over the bf16 peak, chipbench/kernels/attn.py;
operations decide, and the forward's recomputation is not counted), over
the splash kernels' measured device time.  Nothing where they did not
run."""

from chipbench.kernels import attn


def read(f):
    if f.trace is None or not f.trace.kernel_s.get("attn"):
        return None
    nbytes, ops = attn.work(f.config, f.traffic, f.chips)
    least = max(nbytes / f.peaks["hbm_bytes_per_s"], ops / f.peaks["bf16_flops"])
    return 100.0 * least / (f.trace.kernel_s["attn"] / f.steps)
