"""Trained tokens of every step in the window, all chips, over the host-clock
time from the window's start to the completion of its last step."""


def read(f):
    return f.tokens / f.window_s
