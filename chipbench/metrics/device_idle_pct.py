"""Share of the traced window in which no operation ran on the device,
averaged over chips."""


def read(f):
    if f.trace is None:
        return None
    return 100.0 * (1.0 - f.trace.busy_s / f.trace.window_s)
