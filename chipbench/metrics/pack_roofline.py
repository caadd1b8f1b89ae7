"""The pack kernel's share of its roofline: the least time its calls of one
step could take on this chip (the larger of bytes over HBM bandwidth and
operations over the bf16 peak, chipbench/kernels/pack.py; bytes decide),
over their measured device time.  Nothing where the kernel did not run."""

from chipbench.kernels import pack


def read(f):
    if f.trace is None or not f.trace.kernel_s.get("pack"):
        return None
    name, arg = f.traffic["compressor"].split(":")
    if name != "block_topk":
        return None
    block, k = (int(x) for x in arg.split(","))
    nbytes, ops = pack.work(f.leaf_sizes, block, k)
    least = max(nbytes / f.peaks["hbm_bytes_per_s"], ops / f.peaks["bf16_flops"])
    return 100.0 * least / (f.trace.kernel_s["pack"] / f.steps)
