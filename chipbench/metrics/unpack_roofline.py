"""The decode-sum kernel's share of its roofline: the least time its calls
of one step could take on this chip (the larger of bytes over HBM bandwidth
and operations over the bf16 peak, chipbench/kernels/unpack.py; bytes
decide), over their measured device time.  Nothing where the kernel did not
run."""

from chipbench.kernels import unpack


def read(f):
    if f.trace is None or not f.trace.kernel_s.get("unpack"):
        return None
    name, arg = f.traffic["compressor"].split(":")
    if name != "block_topk":
        return None
    block, k = (int(x) for x in arg.split(","))
    nbytes, ops = unpack.work(f.leaf_sizes, block, k, f.traffic["workers"])
    least = max(nbytes / f.peaks["hbm_bytes_per_s"], ops / f.peaks["bf16_flops"])
    return 100.0 * least / (f.trace.kernel_s["unpack"] / f.steps)
