"""Seconds per iteration the prefetch worker spent slicing the host
layout for the waves: the program's ``prefetch.slice`` spans (one per
wave, around the ``next()`` of the driver's wave generator on the
``prefetch-worker`` thread) in the window, over the window's iterations.
Nothing where the program records no such span."""


def read(w):
    d = [e.dur * 1e-6 for e in (w.spans or ()) if e.name == "prefetch.slice"]
    return sum(d) / w.iterations if d else None
