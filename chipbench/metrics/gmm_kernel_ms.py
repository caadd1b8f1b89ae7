"""Device milliseconds per step of the MoE layer's grouped-product kernels
(gmm and tgmm, forward and backward), summed per chip and averaged over
chips; nothing where none ran."""


def read(f):
    if f.trace is None or not f.trace.kernel_s.get("gmm"):
        return None
    return 1e3 * f.trace.kernel_s["gmm"] / f.steps
