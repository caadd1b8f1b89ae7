"""Seconds from process start to the first timed step: imports, building
the job, initialising the state on the device, compiling (or loading from
the cache) and the first three steps the correctness check reads."""


def read(f):
    return f.setup_s
