"""Device milliseconds per step of the splash attention kernels (forward,
dq and dkv, the forward's recomputation included), summed per chip and
averaged over chips; nothing where none ran, as where the step attends
through materialised scores."""


def read(f):
    if f.trace is None or not f.trace.kernel_s.get("attn"):
        return None
    return 1e3 * f.trace.kernel_s["attn"] / f.steps
