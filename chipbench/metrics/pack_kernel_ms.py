"""Device milliseconds per step of the block-top-k pack kernel's calls,
summed per chip and averaged over chips; nothing where none ran."""


def read(f):
    if f.trace is None or not f.trace.kernel_s.get("pack"):
        return None
    return 1e3 * f.trace.kernel_s["pack"] / f.steps
