"""Seconds per iteration the driver spent committing checkpoints: the
registry's ``phase_seconds/checkpoint`` over the window (host snapshot plus
enqueue, and any wait on the previous commit).  Nothing where the mix
checkpoints nothing."""


def read(w):
    s = w.phase_seconds.get("checkpoint")
    return None if s is None else s / w.iterations
