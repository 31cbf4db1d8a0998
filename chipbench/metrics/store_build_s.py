"""Host seconds to build the program's layout and plan from the generated
COO: CSR/ELL (``repro.sparse.padded``), ``outofcore.RatingStore`` and the
``core.partition`` plan and schedule.  Synchronous numpy, host clock."""


def read(w):
    return w.store_build_s
