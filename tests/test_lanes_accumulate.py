"""The accumulate-Theta half with the systems on the lanes, on a problem
shaped like one chip's share of SparkALS: f = 10, several times more items
than users, a third of the items and some users with no rating, and row
blocks smaller than the largest degree bin."""
import pathlib
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.store import latest_step, restore_checkpoint
from repro.core import als as als_mod
from repro.core.partition import (lanes_bytes, plan_for, plan_partitions,
                                  streaming_acc_bytes)
from repro.kernels import ops as kops
from repro.outofcore import (RatingStore, SimulatedFailure, build_schedule,
                             run_streaming_als)
from repro.sparse.padded import csr_from_coo, pad_csr_fast

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from chipbench import reference  # noqa: E402
from chipbench.control import solve_rows_low  # noqa: E402

M, N, F, LAM = 160, 640, 10, 0.05
BLOCK = 128          # rows per block: the first item bin holds several


def _coo(seed=0):
    """Ratings of M users on N items: user degrees lognormal with a tenth
    of the users empty, items drawn by a power law from two thirds of the
    catalog, values from a planted rank-F model plus noise."""
    rng = np.random.default_rng(seed)
    deg = np.minimum(np.round(np.exp(rng.normal(2.2, 1.0, M))), 60)
    deg[rng.choice(M, M // 10, replace=False)] = 0
    live = rng.choice(N, 2 * N // 3, replace=False)
    p = np.arange(1, live.size + 1, dtype=np.float64) ** -0.5
    p /= p.sum()
    rows, cols = [], []
    for u, d in enumerate(deg.astype(int)):
        cols.append(rng.choice(live, d, replace=False, p=p))
        rows.append(np.full(d, u))
    rows, cols = np.concatenate(rows), np.concatenate(cols)
    xs = rng.normal(0, 0.3, (M, F))
    ts = rng.normal(0, 0.3, (N, F))
    vals = (np.sum(xs[rows] * ts[cols], axis=1)
            + rng.normal(0, 0.1, rows.size)).astype(np.float32)
    return rows, cols, vals


def _store(q, n_bins=4, seed=0):
    rows, cols, vals = _coo(seed)
    r = pad_csr_fast(*csr_from_coo(rows, cols, vals, M), N)
    store = RatingStore(r, q=q, n_bins=n_bins)
    plan = plan_for(M, N, r.nnz, F, p=1, q=q, n_data=1,
                    acc_bytes=streaming_acc_bytes(N, F),
                    bin_fills=store.bin_fill_pairs())
    return store, build_schedule(plan, M, N, n_data=1), (rows, cols, vals)


def _cfg(iters=1):
    return als_mod.AlsConfig(f=F, lam=LAM, iters=iters, mode="ref",
                             batch_rows=BLOCK)


def test_problem_is_sparkals_shaped():
    store, _, (rows, cols, _) = _store(q=1)
    items = np.bincount(cols, minlength=N)
    users = np.bincount(rows, minlength=M)
    assert 0.3 < np.mean(items == 0) < 0.4
    assert 0.05 < np.mean(users == 0) < 0.2
    rt = store.theta_batch_binned(0)
    assert max(b.m for b in rt.bins) > 2 * BLOCK


@pytest.mark.parametrize("q", [1, 2])
def test_streaming_matches_float64_reference(q):
    """Both factors of one streamed iteration against the float64 row
    solves: the X rows from the initial Theta, the Theta rows from the X
    the iteration produced, empty rows included (solved to 0).  The
    backward error limit is the benchmark's; the reference with its
    Hermitians at one precision step below fails it."""
    store, sched, (rows, cols, vals) = _store(q)
    assert len(sched.waves) == q
    theta0 = np.asarray(als_mod.als_init(M, N, _cfg()).theta)
    fac, _, _ = run_streaming_als(store, sched, _cfg())
    x, theta = fac.x[:M], fac.theta
    users = reference.Csr.from_coo(rows, cols, vals, M)
    items = reference.Csr.from_coo(cols, rows, vals, N)
    all_u, all_v = np.arange(M), np.arange(N)
    for fixed, csr, rr, got in ((theta0, users, all_u, x),
                                (x, items, all_v, theta)):
        a, b = reference.row_systems(fixed, csr, rr, LAM)
        assert np.max(reference.backward_errors(got, a, b)) < 1e-6
        low = solve_rows_low(fixed, csr, rr, LAM)
        assert np.max(reference.backward_errors(low, a, b)) > 1e-6
        ref = np.linalg.solve(a, b[..., None])[..., 0]
        assert np.max(reference.row_errors(got, ref)) < 1e-3
    empty = np.bincount(cols, minlength=N) == 0
    assert empty.any() and not np.any(theta[empty])


def _plant_altered_answers(monkeypatch):
    real = kops.batch_solve_lanes

    def batch_solve_lanes(At, Bt, **kw):
        return real(At, Bt, **kw).at[:, ::8].multiply(1.01)
    monkeypatch.setattr(kops, "batch_solve_lanes", batch_solve_lanes)


def _plant_half_the_ratings(monkeypatch):
    real = kops.herm_lanes

    def herm_lanes(theta, idx, val, cnt, lam, **kw):
        return real(theta, idx, val, cnt // 2, lam, **kw)
    monkeypatch.setattr(kops, "herm_lanes", herm_lanes)


@pytest.mark.parametrize("plant", [_plant_altered_answers,
                                   _plant_half_the_ratings],
                         ids=["altered_answers", "half_the_ratings"])
def test_theta_side_fault_fails_the_limit(monkeypatch, plant):
    """A fault planted in the lanes ops (a wrong lanes solve, a Hermitian
    of half the ratings), which both binned halves run, reaches the X and
    the Theta the iteration writes: both backward errors fail the
    benchmark's 1e-6.  The jitted update and solve are traced afresh so
    the plant is seen."""
    store, sched, (rows, cols, vals) = _store(q=1)
    theta0 = np.asarray(als_mod.als_init(M, N, _cfg()).theta)
    jax.clear_caches()
    plant(monkeypatch)
    try:
        fac, _, _ = run_streaming_als(store, sched, _cfg())
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    users = reference.Csr.from_coo(rows, cols, vals, M)
    items = reference.Csr.from_coo(cols, rows, vals, N)
    x, theta = fac.x[:M], fac.theta
    # judged as the harness judges: a reading fails unless it is within
    # the limit (a singular system's NaN fails)
    for fixed, csr, rr, got in ((theta0, users, np.arange(M), x),
                                (x, items, np.arange(N), theta)):
        a, b = reference.row_systems(fixed, csr, rr, LAM)
        assert np.sum(~(reference.backward_errors(got, a, b) <= 1e-6)) > 0


def _old_sums(x_batch, binned):
    """The ``[n, f, f]`` partial sums as the accumulate half formed them
    before its accumulator moved to the lanes: each whole bin's Hermitian
    scattered into full-size arrays."""
    A = np.zeros((binned.m, F, F), np.float32)
    B = np.zeros((binned.m, F), np.float32)
    c = np.zeros(binned.m, np.float32)
    for b, r in zip(binned.bins, binned.rows):
        if b.m == 0:
            continue
        idx, val, cnt = als_mod.ell_triplet(b)
        Ab, Bb = kops.fused_herm(x_batch, idx, val, cnt, LAM,
                                 diag_fallback=False)
        A[r] += np.asarray(Ab)
        B[r] += np.asarray(Bb)
        c[r] += b.cnt
    return A, B, c


def test_lanes_accumulator_equals_the_nff_sums():
    """After both batches of a q = 2 store, the lanes accumulators hold
    the ``[n, f, f]`` sums transposed, column ``col_of[i]`` for item i;
    the first batch adds into runs of columns, the second scatters."""
    store, sched, _ = _store(q=2)
    rng = np.random.default_rng(1)
    layout = als_mod.acc_layout(store.theta_batch_binned(0), BLOCK)
    assert layout.width >= N and layout.width % 128 == 0
    w = layout.width
    A = jnp.zeros((F * F, w)), jnp.zeros((F, w)), jnp.zeros(w)
    want = [np.zeros((N, F, F)), np.zeros((N, F)), np.zeros(N)]
    runs = []
    for b in sched.waves:
        (batch,) = b.batches
        xb = jnp.asarray(rng.normal(0, 0.3, (batch.rows, F)), jnp.float32)
        binned = store.theta_batch_binned(batch.index)
        lbs = als_mod.lanes_bins(binned, BLOCK, layout)
        runs.append([lb.cols is None for lb in lbs])
        for lb in lbs:
            A = als_mod.accumulate_bin(*A, xb, lb, _cfg())
        for acc, part in zip(want, _old_sums(xb, binned)):
            acc += part
    assert all(runs[0]) and not all(runs[1])
    A_l, B_l, c_l = (np.asarray(a) for a in A)
    np.testing.assert_allclose(A_l[:, layout.col_of].T.reshape(N, F, F),
                               want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(B_l[:, layout.col_of].T, want[1],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(c_l[layout.col_of], want[2])
    pad = np.setdiff1d(np.arange(w), layout.col_of)
    assert not np.any(A_l[:, pad]) and not np.any(c_l[pad])


@pytest.mark.parametrize("f", [10, 100])
def test_herm_lanes_is_fused_herm_transposed(f):
    """Either arithmetic of the lanes Hermitian (the vector unit at small
    f, the product and a transpose at large f) gives ``fused_herm``'s
    partials in the lanes layout."""
    rng = np.random.default_rng(f)
    m, K, n = 200, 16, 90
    theta = jnp.asarray(rng.normal(0, 0.3, (n, f)), jnp.float32)
    cnt = rng.integers(0, K + 1, m).astype(np.int32)
    idx = rng.integers(0, n, (m, K)).astype(np.int32)
    val = rng.normal(0, 1, (m, K)).astype(np.float32)
    A, B = kops.fused_herm(theta, idx, val, cnt, LAM, diag_fallback=False)
    At, Bt = kops.herm_lanes(theta, idx.T, val.T, cnt, LAM)
    np.testing.assert_allclose(np.asarray(At).T.reshape(m, f, f),
                               np.asarray(A), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(Bt).T, np.asarray(B),
                               rtol=1e-5, atol=1e-5)


def test_mid_half_checkpoint_resumes_bit_exact(tmp_path):
    """Killed after the first of two accumulate waves, the run resumes
    from the lanes accumulators its checkpoint holds, at their width, to
    the uninterrupted run's factors bit for bit."""
    store, sched, _ = _store(q=2)
    cfg = _cfg(iters=2)
    ref, _, _ = run_streaming_als(store, sched, cfg)
    ckpt = str(tmp_path / "ckpt")
    shutil.rmtree(ckpt, ignore_errors=True)
    with pytest.raises(SimulatedFailure):
        run_streaming_als(store, sched, cfg, ckpt_dir=ckpt,
                          fail_after_waves=3)
    step = latest_step(ckpt)
    assert step == 3
    like = {k: np.zeros((), np.float32)
            for k in ("x", "theta", "a_acc", "b_acc", "c_acc")}
    tree = restore_checkpoint(ckpt, like, step)
    width = als_mod.acc_layout(store.theta_batch_binned(0), BLOCK).width
    assert tree["a_acc"].shape == (F * F, width)
    assert tree["b_acc"].shape == (F, width)
    assert np.any(tree["a_acc"])
    fac, _, tel = run_streaming_als(store, sched, cfg, ckpt_dir=ckpt)
    assert tel.resumed_from_step == 3
    np.testing.assert_array_equal(fac.x, ref.x)
    np.testing.assert_array_equal(fac.theta, ref.theta)


def test_plan_prices_the_cell_accumulator_tiled():
    """At one chip's share of SparkALS the planner keeps q = 1 within
    16 GB and prices the accumulator as laid out: ``[f*f, n]`` with its
    100 rows padded to 104 sublanes, ``[f, n]`` with 10 padded to 16,
    ``c [n]``, and the scatter-add's items-major ``[n, f*f]`` with 100
    lanes padded to 128; with ``block_rows`` the Hermitians are one row
    block's."""
    m, n, nnz, f = 1_885_714, 2_400_000, 9_000_000, 10
    hbm = 16 << 30
    assert plan_partitions(m, n, nnz, f, hbm_bytes=hbm, n_model=1,
                           n_data=1).q == 1
    acc = streaming_acc_bytes(n, f)
    assert acc == (104 + 16 + 1 + 128) * n * 4
    assert acc == sum(lanes_bytes(s) for s in ((f * f, n), (f, n), (n,),
                                               (n, f * f)))
    plan = plan_for(m, n, nnz, f, p=1, q=1, n_data=1, hbm_bytes=hbm,
                    buffers=4, acc_bytes=acc, block_rows=16384)
    assert plan.fits and plan.terms["Herm_acc"] == acc
    # one block of the X half's [rows, f, f], f = 10 padded to (16, 128)
    assert plan.terms["A_batch"] == 16384 * 16 * 128 * 4
    assert plan.terms["B_batch"] == 16384 * 128 * 4
