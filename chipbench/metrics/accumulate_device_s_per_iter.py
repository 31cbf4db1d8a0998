"""Device seconds per iteration of the accumulate-Theta update: the self
time of the operations of the program that ``core.als.accumulate_bin``
runs (module ``jit__accumulate_bin``) among the traced window's top
operations (``w.trace.top_ops``, the ten with the most self time), over
the window's iterations.  Nothing where no such program ran, as in a
program without it, or where the trace names no programs (a CPU trace)."""

#: the module prefix of the update's operations in ``top_ops``
PROGRAM = "jit__accumulate_bin/"


def read(w):
    if w.trace is None:
        return None
    s = sum(t for name, t in w.trace.top_ops if name.startswith(PROGRAM))
    return s / w.iterations if s > 0 else None
