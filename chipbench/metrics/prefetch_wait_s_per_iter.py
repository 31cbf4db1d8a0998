"""Seconds per iteration the driver's consumer stalled waiting for the
prefetcher: the registry's ``phase_seconds/prefetch`` over the window."""


def read(w):
    s = w.phase_seconds.get("prefetch")
    return None if s is None else s / w.iterations
