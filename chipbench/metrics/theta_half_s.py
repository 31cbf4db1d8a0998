"""Mean seconds of the accumulate-Theta half: the driver's
``als.accumulate_theta_half`` spans in the window (each ends in the host
write-back of the solved Theta)."""


def read(w):
    d = [e.dur * 1e-6 for e in (w.spans or ())
         if e.name == "als.accumulate_theta_half"]
    return sum(d) / len(d) if d else None
