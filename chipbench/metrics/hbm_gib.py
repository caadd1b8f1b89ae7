"""Device memory at its peak on the fullest chip, as the device's allocator
reports it (``memory_stats()["peak_bytes_in_use"]``) once the window has
closed, before the reference runs."""


def read(f):
    return f.peak_bytes / 2 ** 30
