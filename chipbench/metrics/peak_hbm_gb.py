"""Peak device memory of the run, in GB (1e9 bytes):
``memory_stats()["peak_bytes_in_use"]`` read after the window."""


def read(w):
    return None if w.memory_peak_bytes is None else w.memory_peak_bytes / 1e9
