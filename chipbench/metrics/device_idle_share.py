"""Share of the traced window in which no operation ran on the device, in
%: 1 - (union of the device's operation intervals) / window, from the
profiler trace (``chipbench.trace_reduce``)."""


def read(w):
    if w.trace is None:
        return None
    return 100.0 * w.trace.idle_share
