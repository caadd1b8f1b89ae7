"""The grouped-product kernels' share of their roofline: the least time one
step's calls could take on this chip at the held experts' expected load
(the larger of bytes over HBM bandwidth and operations over the bf16 peak,
chipbench/kernels/gmm.py; operations decide), over their measured device
time.  Nothing where the kernels did not run."""

from chipbench.kernels import gmm


def read(f):
    if f.trace is None or not f.trace.kernel_s.get("gmm"):
        return None
    tokens = f.traffic["global_batch"] * f.traffic["seq"] // f.chips
    nbytes, ops = gmm.work(f.config, tokens)
    least = max(nbytes / f.peaks["hbm_bytes_per_s"], ops / f.peaks["bf16_flops"])
    return 100.0 * least / (f.trace.kernel_s["gmm"] / f.steps)
