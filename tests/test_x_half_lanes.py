"""The binned solve-X half through the accumulate half's lanes programs:
each wave's lanes bins, made once by the store, added into the wave's own
accumulators and solved once, against the per-bin row update
(``update_factor_binned``) it replaced."""
import shutil

import numpy as np
import pytest

from repro.core import als as als_mod
from repro.core.partition import plan_for, streaming_acc_bytes
from repro.kernels import ops as kops
from repro.obs import MetricsRegistry, Tracer
from repro.outofcore import (RatingStore, SimulatedFailure, build_schedule,
                             run_streaming_als)
from repro.sparse.padded import csr_from_coo, pad_csr_fast

M, N, LAM = 600, 400, 0.05
BLOCK = 128          # rows per block: the light user bins hold several


def _coo(f, seed=0):
    """Ratings of M users on N items: lognormal user degrees with an
    eighth of the users empty, values from a planted rank-f model."""
    rng = np.random.default_rng(seed)
    deg = np.minimum(np.round(np.exp(rng.normal(1.8, 1.0, M))), 80)
    deg[rng.choice(M, M // 8, replace=False)] = 0
    rows = np.repeat(np.arange(M), deg.astype(int))
    cols = np.concatenate([rng.choice(N, int(d), replace=False)
                           for d in deg])
    xs, ts = rng.normal(0, 0.3, (M, f)), rng.normal(0, 0.3, (N, f))
    vals = (np.sum(xs[rows] * ts[cols], axis=1)
            + rng.normal(0, 0.1, rows.size)).astype(np.float32)
    return rows, cols, vals


def _store(f, q, seed=0):
    rows, cols, vals = _coo(f, seed)
    r = pad_csr_fast(*csr_from_coo(rows, cols, vals, M), N)
    store = RatingStore(r, q=q, n_bins=4)
    plan = plan_for(M, N, r.nnz, f, p=1, q=q, n_data=1,
                    acc_bytes=streaming_acc_bytes(N, f),
                    bin_fills=store.bin_fill_pairs())
    sched = build_schedule(plan, M, N, n_data=1)
    assert len(sched.waves) == q
    return store, sched, rows


def _cfg(f, iters=1):
    return als_mod.AlsConfig(f=f, lam=LAM, iters=iters, mode="ref",
                             batch_rows=BLOCK)


@pytest.mark.parametrize("q", [1, 2])
@pytest.mark.parametrize("f", [10, 40], ids=["vector_unit", "fused_herm"])
def test_x_half_equals_the_per_bin_row_update(f, q):
    """The streamed X of one iteration equals the per-bin row update of
    the whole binned R from the same Theta, at both arithmetics of the
    lanes Hermitian (f = 10 on the vector unit, f = 40 through
    ``fused_herm``); users with no rating solve to exactly 0."""
    assert (f <= kops.VPU_HERM_MAX_F) == (f == 10)
    store, sched, rows = _store(f, q)
    cfg = _cfg(f)
    theta0 = als_mod.als_init(M, N, cfg).theta
    want = np.asarray(als_mod.update_factor_binned(
        theta0, store.r_binned, cfg))
    fac, _, _ = run_streaming_als(store, sched, cfg)
    np.testing.assert_allclose(fac.x[:M], want[:M], rtol=2e-4, atol=2e-5)
    empty = np.bincount(rows, minlength=M) == 0
    assert empty.any() and not np.any(fac.x[:M][empty])
    assert not np.any(fac.x[M:])


@pytest.mark.parametrize("q", [1, 2])
def test_x_accumulate_counters_cover_every_rating(q):
    """On a binned p = 1 run every streamed X rating goes through the
    lanes update, once a wave; the solve counters count the wave's rows
    and the lanes solve's width."""
    f = 10
    store, sched, _ = _store(f, q)
    reg = MetricsRegistry()
    run_streaming_als(store, sched, _cfg(f, iters=2), registry=reg)
    ratings = reg.counter("x_accumulate_ratings").value
    assert ratings == reg.counter("x_nnz_streamed").value == 2 * store.nnz
    lanes = [store.x_wave_lanes(w.row_start, w.row_stop, BLOCK)
             for w in sched.waves]
    assert reg.counter("x_accumulate_slots").value == 2 * sum(
        lb.slots for lbs in lanes for lb in lbs)
    widths = [store.x_wave_layout(w.row_start, w.row_stop, BLOCK).width
              for w in sched.waves]
    theta_w = store.theta_acc_layout(BLOCK).width
    assert reg.counter("solve_systems").value == 2 * (store.m_pad + N)
    assert reg.counter("solve_padded_systems").value == 2 * (
        sum(kops.solve_width(w, f) for w in widths)
        + kops.solve_width(theta_w, f))


def test_x_lanes_are_made_once_beside_the_theta_lanes():
    """The store makes each wave's X lanes bins once, under keys apart
    from the Theta half's, every bin a run of the wave's columns."""
    store, sched, _ = _store(10, 2)
    for w in sched.waves:
        cut = (w.row_start, w.row_stop, BLOCK)
        lbs = store.x_wave_lanes(*cut)
        assert lbs is store.x_wave_lanes(*cut)
        assert all(lb.cols is None for lb in lbs)
        layout = store.x_wave_layout(*cut)
        assert layout.width % 128 == 0
        assert sorted(layout.col_of) == sorted(set(layout.col_of))
        assert sum(lb.rows for lb in lbs) == w.row_stop - w.row_start
    assert store.theta_batch_lanes(0, BLOCK) is not store.x_wave_lanes(
        sched.waves[0].row_start, sched.waves[0].row_stop, BLOCK)


def test_x_bin_updates_are_spanned_by_half():
    """Every lanes bin update is one ``als.accumulate`` span that names
    its half; the X half's solve stays under ``als.wave_x``."""
    f = 10
    store, sched, _ = _store(f, 1)
    tracer = Tracer()
    run_streaming_als(store, sched, _cfg(f), tracer=tracer)
    spans = [e for e in tracer.spans() if e.name == "als.accumulate"]
    w = sched.waves[0]
    n_x = len(store.x_wave_lanes(w.row_start, w.row_stop, BLOCK))
    n_t = len(store.theta_batch_lanes(0, BLOCK))
    halves = [e.args["half"] for e in spans]
    assert halves.count("x") == n_x and halves.count("theta") == n_t
    for e in spans:
        assert {"bin", "rows", "slots", "nnz", "x_rows", "n", "f"} <= set(
            e.args)
    x_rows = {e.args["x_rows"] for e in spans if e.args["half"] == "x"}
    assert x_rows == {N}
    assert not [e for e in tracer.spans()
                if e.name == "als.solve_accumulated"
                and e.args["systems"] != store.theta_acc_layout(BLOCK).width]


@pytest.mark.parametrize("f", [10, 40], ids=["vector_unit", "fused_herm"])
def test_second_iteration_traces_nothing(f):
    """After the first iteration compiled every program, the second one
    traces nothing: no per-call lowering of a row-block map."""
    store, sched, _ = _store(f, 1)
    reg = MetricsRegistry()
    traces = []
    run_streaming_als(
        store, sched, _cfg(f, iters=2), registry=reg,
        callback=lambda it, rec: traces.append(
            reg.counter("jax/traces").value))
    assert len(traces) == 2 and traces[1] == traces[0]


@pytest.mark.parametrize("fail_after", [1, 5],
                         ids=["first_iteration", "second_iteration"])
def test_kill_mid_x_half_resumes_bit_exact(tmp_path, fail_after):
    """Killed after the first of two X waves (of the first or the second
    iteration), the run resumes to the uninterrupted run's factors bit
    for bit."""
    f = 10
    store, sched, _ = _store(f, 2)
    cfg = _cfg(f, iters=2)
    assert sched.waves_per_iteration == 4
    ref, _, _ = run_streaming_als(store, sched, cfg)
    ckpt = str(tmp_path / "ckpt")
    shutil.rmtree(ckpt, ignore_errors=True)
    with pytest.raises(SimulatedFailure):
        run_streaming_als(store, sched, cfg, ckpt_dir=ckpt,
                          fail_after_waves=fail_after)
    fac, _, tel = run_streaming_als(store, sched, cfg, ckpt_dir=ckpt)
    assert tel.resumed_from_step == fail_after
    np.testing.assert_array_equal(fac.x, ref.x)
    np.testing.assert_array_equal(fac.theta, ref.theta)


def _half_the_ratings(real):
    def fused_herm(theta, idx, val, cnt, lam, **kw):
        return real(theta, idx, val, cnt // 2, lam, **kw)
    return fused_herm


def _altered_answers(real):
    def batch_solve(A, B, **kw):
        return real(A, B, **kw).at[::8].multiply(1.01)
    return batch_solve


@pytest.mark.parametrize("entry,plant", [("fused_herm", _half_the_ratings),
                                         ("batch_solve", _altered_answers)],
                         ids=["fused_herm", "batch_solve"])
def test_replaced_entry_point_reaches_both_halves(monkeypatch, entry, plant):
    """A ``kernels.ops`` entry point replaced after the lanes programs
    were traced, as a planted fault replaces it, reaches the X and the
    Theta of the next run without clearing JAX's caches; restored, the
    programs traced before serve again, tracing nothing."""
    f = 40
    store, sched, _ = _store(f, 1)
    cfg = _cfg(f)
    clean, _, _ = run_streaming_als(store, sched, cfg)
    monkeypatch.setattr(kops, entry, plant(getattr(kops, entry)))
    bad, _, _ = run_streaming_als(store, sched, cfg)
    monkeypatch.undo()
    assert not np.allclose(bad.x, clean.x, rtol=1e-3, atol=1e-4)
    assert not np.allclose(bad.theta, clean.theta, rtol=1e-3, atol=1e-4)
    reg = MetricsRegistry()
    again, _, _ = run_streaming_als(store, sched, cfg, registry=reg)
    assert reg.counter("jax/traces").value == 0
    np.testing.assert_array_equal(again.x, clean.x)
    np.testing.assert_array_equal(again.theta, clean.theta)
