"""Mean seconds of the solve-X half: the driver's ``als.solve_x_half``
spans in the window (each ends in the host write-back of its last wave,
so the device work is inside it)."""


def read(w):
    d = [e.dur * 1e-6 for e in (w.spans or ())
         if e.name == "als.solve_x_half"]
    return sum(d) / len(d) if d else None
