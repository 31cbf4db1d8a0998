"""Share of the HBM roofline that the accumulate-Theta update reaches, in
%: the least HBM traffic any implementation of the half must move, over
``accumulate_device_s_per_iter`` times the device's ``hbm_bytes_per_s``
(``peaks.json``, for ``jax.devices()[0].device_kind``).

The traffic is counted from the program's ``als.accumulate`` spans (one
per bin update, args ``bin``, ``rows``, ``nnz``, ``x_rows``, ``f``): 8
bytes a rating (its index and value), each bin's rows' A, B and c
written once (4 rows (f^2 + f + 1) bytes), and the batch's X rows read
once (4 x_rows f bytes, counted on each batch's first bin, not once a
bin).  A lower bound, so the share stays under 100 % whatever implements
the half.  Nothing where the program records no such span, or off the
chip (no peak)."""

from chipbench import harness


def moved_bytes(spans) -> int:
    """The least bytes the updates of ``spans`` move."""
    moved = 0
    for e in spans:
        a, f = e.args, e.args["f"]
        moved += 8 * a["nnz"] + 4 * a["rows"] * (f * f + f + 1)
        if a["bin"] == 0:
            moved += 4 * a["x_rows"] * f
    return moved


def read(w):
    spans = [e for e in (w.spans or ()) if e.name == "als.accumulate"]
    dev_s = harness.load_reader(harness.ROOT,
                                "accumulate_device_s_per_iter")(w)
    if not spans or dev_s is None or not w.peak_flops:
        return None
    import jax

    peak = harness.load_peaks(
        harness.ROOT, jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * moved_bytes(spans) / w.iterations / peak / dev_s
