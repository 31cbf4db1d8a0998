"""Out-of-core wave scheduler: ISSUE-2 acceptance suite.

Fast tests cover the Prefetcher lifecycle regression and the store/schedule
invariants; the multi-wave end-to-end runs (streaming == in-core oracle,
capacity, kill/resume) are marked ``slow`` and run in their own CI job.
"""
import shutil
import time

import numpy as np
import pytest

from repro.core import als as als_mod
from repro.core.partition import plan_for
from repro.data.prefetch import Prefetcher
from repro.outofcore import (RatingStore, SimulatedFailure, TileStore,
                             build_schedule, build_sgd_schedule,
                             required_capacity_bytes, run_streaming_als,
                             run_streaming_sgd)
from repro.sgd import SgdConfig, block_ell, sgd_train
from repro.sparse import synth
from repro.sparse.padded import PaddedELL, bin_padded

SPEC = synth.SynthSpec("oc", 96, 40, 1500, 8, 0.05)


def _problem(seed=0):
    return synth.make_synthetic_ratings(SPEC, seed=seed)


def _forced_plan(r, q=4, n_data=2, store=None, depth=2):
    """A waves >= 2 plan on in-core-sized data, priced with the store's real
    padding fills and the driver's accumulator + double-buffer residents
    (depth queued + one loader-held + one being consumed)."""
    fill = store.worst_fill if store is not None else r.fill
    acc_eps = SPEC.n * (SPEC.f * SPEC.f + 3 * SPEC.f + 1) * 4
    return plan_for(SPEC.m, SPEC.n, r.nnz, SPEC.f, p=1, q=q, n_data=n_data,
                    fill=fill, eps=acc_eps, buffers=depth + 2,
                    hbm_bytes=1 << 22)


# ---------------------------------------------------------------------------
# Prefetcher lifecycle (satellite: abandoning iteration must not leak the
# worker thread blocked on Queue.put)
# ---------------------------------------------------------------------------

def _join(pf, timeout=5.0):
    deadline = time.monotonic() + timeout
    while pf._thread.is_alive() and time.monotonic() < deadline:
        time.sleep(0.01)
    return not pf._thread.is_alive()


def test_prefetcher_close_unblocks_worker():
    pf = Prefetcher(({"x": np.asarray([i])} for i in range(1000)), depth=1)
    next(pf)                      # worker is now blocked on a full queue
    pf.close()
    assert _join(pf), "worker thread leaked after close()"
    assert pf.closed
    with pytest.raises(StopIteration):
        next(pf)
    pf.close()                    # idempotent


def test_prefetcher_context_manager():
    with Prefetcher(iter(range(1000)), depth=1,
                    put=lambda x: x) as pf:
        assert next(pf) == 0
    assert _join(pf)


def test_prefetcher_close_after_exhaustion():
    pf = Prefetcher(iter(range(3)), depth=2, put=lambda x: x)
    assert list(pf) == [0, 1, 2]
    pf.close()
    assert _join(pf)


def test_prefetcher_still_propagates_errors():
    def boom():
        yield 1
        raise ValueError("boom")

    with Prefetcher(boom(), depth=2, put=lambda x: x) as pf:
        assert next(pf) == 1
        with pytest.raises(ValueError, match="boom"):
            next(pf)


# ---------------------------------------------------------------------------
# Store / schedule invariants (fast)
# ---------------------------------------------------------------------------

def test_rating_store_keeps_both_orientations():
    r, _, _, _ = _problem()
    store = RatingStore(r, q=4)
    assert store.m_pad % 4 == 0 and store.m_pad >= r.m
    assert store.r.m == store.m_pad
    # padded rows are empty
    assert int(store.r.cnt[r.m:].sum()) == 0
    # the q R^T shards hold exactly the same nonzeros as R
    assert int(store.rt_parts.cnt.sum()) == r.nnz == store.nnz
    # shard j only references batch-local user coordinates
    npp = store.m_pad // 4
    for j in range(4):
        idx, val, cnt = store.theta_batch_triplet(j)
        live = np.arange(idx.shape[1])[None, :] < cnt[:, None]
        assert live.sum() and idx[live].max() < npp
    assert store.fill_rt >= 1.0 and store.worst_fill >= store.fill_r


def test_rating_store_roundtrips_the_matrix():
    """Sum of R^T shard j's entries == sum over batch j's rows of R."""
    r, _, _, _ = _problem()
    q = 4
    store = RatingStore(r, q=q)
    npp = store.m_pad // q
    for j in range(q):
        idx, val, cnt = store.x_slice_triplet(j * npp, (j + 1) * npp)
        live = np.arange(idx.shape[1])[None, :] < cnt[:, None]
        _, tval, tcnt = store.theta_batch_triplet(j)
        tlive = np.arange(tval.shape[1])[None, :] < tcnt[:, None]
        assert int(cnt.sum()) == int(tcnt.sum())
        np.testing.assert_allclose(val[live].sum(), tval[tlive].sum(),
                                   rtol=1e-5)


def test_build_schedule_covers_rows_once():
    r, _, _, _ = _problem()
    store = RatingStore(r, q=4)
    plan = _forced_plan(r, q=4, n_data=2, store=store)
    sched = build_schedule(plan, SPEC.m, SPEC.n, n_data=2)
    assert plan.waves == len(sched.waves) == 2
    assert sched.m_pad == store.m_pad
    covered = np.zeros(sched.m_pad, np.int32)
    for wave in sched.waves:
        for b in wave.batches:
            covered[b.row_start:b.row_stop] += 1
    assert (covered == 1).all()
    assert required_capacity_bytes(store, sched, SPEC.f) > 0


def test_streaming_ragged_last_wave():
    """q not divisible by n_data: the last wave carries fewer batches and its
    per-device metering divides by the actual batch count."""
    r, rt, rte, _ = _problem()
    cfg = als_mod.AlsConfig(f=SPEC.f, lam=SPEC.lam, iters=1, mode="ref")
    rr, rtt, rtest = (als_mod.ell_triplet(e) for e in (r, rt, rte))
    _, hist = als_mod.als_train(rr, rtt, r.m, rt.m, cfg)

    store = RatingStore(r, q=3)
    plan = _forced_plan(r, q=3, n_data=2, store=store)
    sched = build_schedule(plan, SPEC.m, SPEC.n, n_data=2)
    assert len(sched.waves) == 2 and len(sched.waves[-1].batches) == 1
    _, shist, tel = run_streaming_als(store, sched, cfg, train_eval=rr)
    assert abs(shist[-1]["train_rmse"] - hist[-1]["train_rmse"]) < 1e-4
    assert tel.peak_bytes <= tel.capacity_bytes


# ---------------------------------------------------------------------------
# End-to-end multi-wave runs (slow lane)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_streaming_matches_incore_rmse():
    """Acceptance: forced waves >= 2 streaming == in-core als_run to 1e-4,
    and the peak simulated device footprint respects the plan's budget."""
    r, rt, rte, _ = _problem()
    cfg = als_mod.AlsConfig(f=SPEC.f, lam=SPEC.lam, iters=3, mode="ref")
    rr, rtt, rtest = (als_mod.ell_triplet(e) for e in (r, rt, rte))
    _, hist = als_mod.als_train(rr, rtt, r.m, rt.m, cfg, test=rtest)

    store = RatingStore(r, q=4)
    plan = _forced_plan(r, q=4, n_data=2, store=store)
    assert plan.waves >= 2
    sched = build_schedule(plan, SPEC.m, SPEC.n, n_data=2)
    _, shist, tel = run_streaming_als(store, sched, cfg, train_eval=rr,
                                      test_eval=rtest)

    assert len(shist) == len(hist)
    for a, b in zip(shist, hist):
        assert abs(a["train_rmse"] - b["train_rmse"]) < 1e-4
        assert abs(a["test_rmse"] - b["test_rmse"]) < 1e-4
    # memory: under the plan's per-device budget, and genuinely streaming
    # (well below holding the whole padded problem resident)
    assert tel.peak_bytes <= tel.capacity_bytes
    in_core_bytes = store.host_nbytes + (store.m_pad + store.n) * SPEC.f * 4
    assert tel.peak_bytes < in_core_bytes
    assert tel.waves_run == 2 * len(sched.waves) * cfg.iters


@pytest.mark.slow
def test_wave_update_fn_on_mesh_matches_oracle():
    """`distributed.su_als.make_wave_update_fn` — the driver's hook for
    running a wave slice on a real mesh — must match the single-device
    per-slice solve.  Runs in a subprocess with 8 forced host devices
    (same harness as test_distributed)."""
    from test_distributed import run_script
    run_script("""
import numpy as np, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.sparse import synth, padded
from repro.distributed.su_als import make_wave_update_fn
from repro.kernels import ops as kops

mesh = make_mesh((4, 2), ("data", "model"))
spec = synth.SynthSpec("oc", 64, 16, 600, 8, 0.05)
r, _, _, _ = synth.make_synthetic_ratings(spec, seed=0)
parts = padded.partition_padded(r, 2)       # model-axis column shards
P, m, K = parts.idx.shape
idx = np.transpose(parts.idx, (1, 0, 2)).reshape(m, P * K)[:32]
val = np.transpose(parts.val, (1, 0, 2)).reshape(m, P * K)[:32]
cnt = np.transpose(parts.cnt, (1, 0)).reshape(m, P)[:32]
theta = np.random.default_rng(0).standard_normal((16, 8)).astype(np.float32)

out = make_wave_update_fn(mesh, lam=0.05, mode="ref")(theta, idx, val, cnt)
ref = np.asarray(kops.als_update_factor(
    jnp.asarray(theta), jnp.asarray(r.idx[:32]), jnp.asarray(r.val[:32]),
    jnp.asarray(r.cnt[:32]), 0.05))
assert out.shape == (32, 8), out.shape
assert np.abs(out - ref).max() < 1e-4, np.abs(out - ref).max()
print("wave update on mesh OK")
""")


# ---------------------------------------------------------------------------
# Streaming SGD: tile-wave schedule invariants (fast) + parity suite (slow)
# ---------------------------------------------------------------------------

def _sgd_problem(g=4, n_workers=2):
    r, _, rte, _ = _problem()
    grid = block_ell(r, g=g)
    tiles = TileStore(grid)
    sched = build_sgd_schedule(grid, SPEC.f, n_workers=n_workers)
    return r, rte, grid, tiles, sched


def _sgd_cfg(**kw):
    kw.setdefault("schedule", "inverse_time")
    kw.setdefault("decay", 1.0)
    return SgdConfig(f=SPEC.f, lam=SPEC.lam, lr=0.1, mode="ref", seed=3, **kw)


def test_sgd_schedule_covers_every_tile_once():
    """Every (i, j) tile appears in exactly one wave per epoch, waves never
    mix diagonal sets, and n_workers < g forces multiple waves per set."""
    _, _, grid, tiles, sched = _sgd_problem(g=4, n_workers=3)   # ragged
    g = grid.g
    assert sched.waves_per_epoch == g * 2       # ceil(4/3) = 2 waves/set
    seen = set()
    for s, ws in enumerate(sched.set_waves):
        for w in ws:
            assert w.set_index == s
            for i, j in w.tiles:
                assert (j - i) % g == s          # tile belongs to its set
                assert (i, j) not in seen
                seen.add((i, j))
    assert len(seen) == g * g
    # epoch flattening follows the permuted set order and renumbers
    order = [2, 0, 3, 1]
    waves = sched.epoch_waves(order)
    assert [w.index for w in waves] == list(range(sched.waves_per_epoch))
    assert [w.set_index for w in waves] == [2, 2, 0, 0, 3, 3, 1, 1]
    with pytest.raises(AssertionError):
        sched.epoch_waves([0, 1, 2, 2])          # not a permutation


def test_tile_store_views_grid():
    _, _, grid, tiles, _ = _sgd_problem()
    assert (tiles.g, tiles.mb, tiles.nb, tiles.K) == \
        (grid.g, grid.mb, grid.nb, grid.K)
    assert tiles.nnz == grid.nnz
    idx, val, cnt = tiles.tile_triplet(1, 2)
    np.testing.assert_array_equal(idx, grid.idx[1, 2])
    assert np.shares_memory(val, grid.val), "tile views must not copy"
    assert tiles.host_nbytes > 0


@pytest.mark.slow
def test_streaming_sgd_matches_incore():
    """Acceptance: a forced waves >= 2 tile plan matches the in-core SGD
    RMSE trajectory, and peak metered bytes stay under the plan capacity."""
    r, rte, grid, tiles, sched = _sgd_problem(g=4, n_workers=2)
    assert all(len(ws) >= 2 for ws in sched.set_waves)
    rtest = als_mod.ell_triplet(rte)
    cfg = _sgd_cfg(epochs=3)
    state, hist = sgd_train(grid, cfg, test=rtest)
    fac, shist, tel = run_streaming_sgd(tiles, sched, cfg, test_eval=rtest)
    assert len(shist) == len(hist)
    for a, b in zip(shist, hist):
        assert abs(a["test_rmse"] - b["test_rmse"]) < 1e-3
    np.testing.assert_allclose(fac.x, np.asarray(state.x), atol=1e-5)
    np.testing.assert_allclose(fac.theta, np.asarray(state.theta), atol=1e-5)
    # memory: under budget, and genuinely streaming (well below resident)
    assert tel.peak_bytes <= tel.capacity_bytes
    assert tel.peak_bytes < tiles.host_nbytes + fac.nbytes
    assert tel.waves_run == sched.waves_per_epoch * cfg.epochs


@pytest.mark.slow
@pytest.mark.parametrize("kill_after", [3, 11])
def test_streaming_sgd_kill_and_resume_bit_exact(tmp_path, kill_after):
    """Acceptance: killed after wave ``kill_after`` (3 = mid-first-epoch,
    11 = mid-second-epoch across the set-order reshuffle), the resumed run
    reaches bit-identical factors."""
    _, _, grid, tiles, sched = _sgd_problem(g=4, n_workers=2)
    cfg = _sgd_cfg(epochs=2)
    assert kill_after < cfg.epochs * sched.waves_per_epoch
    ref_fac, ref_hist, _ = run_streaming_sgd(tiles, sched, cfg)

    ckpt = str(tmp_path / "sgd_ckpt")
    shutil.rmtree(ckpt, ignore_errors=True)
    with pytest.raises(SimulatedFailure):
        run_streaming_sgd(tiles, sched, cfg, ckpt_dir=ckpt,
                          fail_after_waves=kill_after)
    fac, hist, tel = run_streaming_sgd(tiles, sched, cfg, ckpt_dir=ckpt)
    assert tel.resumed_from_step == kill_after
    assert len(hist) == cfg.epochs - kill_after // sched.waves_per_epoch
    np.testing.assert_array_equal(fac.x, ref_fac.x)
    np.testing.assert_array_equal(fac.theta, ref_fac.theta)


@pytest.mark.slow
def test_streaming_hybrid_runs_both_phases_streamed(tmp_path):
    """Streaming warm start + streaming refine under one budget; a restart
    with a committed SGD checkpoint skips the ALS phase."""
    from repro.sgd import run_streaming_hybrid
    r, rte, grid, tiles, sched = _sgd_problem(g=4, n_workers=2)
    rtest = als_mod.ell_triplet(rte)
    store = RatingStore(r, q=4)
    plan = _forced_plan(r, q=4, n_data=2, store=store)
    als_sched = build_schedule(plan, SPEC.m, SPEC.n, n_data=2)
    als_cfg = als_mod.AlsConfig(f=SPEC.f, lam=SPEC.lam, iters=2, mode="ref")
    cfg = _sgd_cfg(epochs=2)

    ck = str(tmp_path / "hyb")
    fac, hist, tel = run_streaming_hybrid(
        store, als_sched, tiles, sched, als_cfg, cfg, test_eval=rtest,
        ckpt_dir=ck)
    assert [h["phase"] for h in hist] == ["als"] * 2 + ["sgd"] * 2
    # warm start pays off: first SGD epoch starts below the cold ALS start
    assert hist[2]["test_rmse"] < hist[0]["test_rmse"]
    # ONE merged telemetry (ISSUE 7 satellite): the per-phase views stay
    # reachable and each ran within its own budget
    atel, stel = tel.phases["als"], tel.phases["sgd"]
    assert atel.peak_bytes <= atel.capacity_bytes
    assert stel.peak_bytes <= stel.capacity_bytes
    assert tel.waves_run == atel.waves_run + stel.waves_run
    assert tel.peak_bytes == max(atel.peak_bytes, stel.peak_bytes)
    assert tel.wall_seconds >= max(atel.wall_seconds, stel.wall_seconds)
    assert any(k.startswith("als/") for k in tel.phase_seconds)
    assert any(k.startswith("sgd/") for k in tel.phase_seconds)
    fac2, hist2, tel2 = run_streaming_hybrid(
        store, als_sched, tiles, sched, als_cfg, cfg, test_eval=rtest,
        ckpt_dir=ck)
    # complete: no ALS re-run, so the merged view has no ALS phase
    assert hist2 == [] and "als" not in tel2.phases
    np.testing.assert_array_equal(fac2.x, fac.x)
    np.testing.assert_array_equal(fac2.theta, fac.theta)


@pytest.mark.slow
@pytest.mark.parametrize("kill_after", [1, 3])
def test_kill_and_resume_reaches_same_result(tmp_path, kill_after):
    """Acceptance: a run killed after wave ``kill_after`` (1 = first solve-X
    wave, 3 = mid accumulate-Theta) resumes from checkpoint to the same
    final factors."""
    r, _, _, _ = _problem()
    cfg = als_mod.AlsConfig(f=SPEC.f, lam=SPEC.lam, iters=2, mode="ref")
    rr = als_mod.ell_triplet(r)
    store = RatingStore(r, q=4)
    plan = _forced_plan(r, q=4, n_data=2, store=store)
    sched = build_schedule(plan, SPEC.m, SPEC.n, n_data=2)

    ref_fac, ref_hist, _ = run_streaming_als(store, sched, cfg, train_eval=rr)

    ckpt = str(tmp_path / "ckpt")
    shutil.rmtree(ckpt, ignore_errors=True)
    with pytest.raises(SimulatedFailure):
        run_streaming_als(store, sched, cfg, ckpt_dir=ckpt, train_eval=rr,
                          fail_after_waves=kill_after)
    fac, hist, tel = run_streaming_als(store, sched, cfg, ckpt_dir=ckpt,
                                       train_eval=rr)
    assert tel.resumed_from_step == kill_after
    assert abs(hist[-1]["train_rmse"] - ref_hist[-1]["train_rmse"]) < 1e-4
    np.testing.assert_allclose(fac.x, ref_fac.x, atol=1e-5)
    np.testing.assert_allclose(fac.theta, ref_fac.theta, atol=1e-5)


# ---------------------------------------------------------------------------
# Degree-binned stores and streaming (ISSUE 9)
# ---------------------------------------------------------------------------

def test_rating_store_binned_invariants():
    """Binned shards hold the same nonzeros, expose per-component fills,
    and price the planner through per-bin (slots, nnz) pairs whose
    aggregate equals the worst-orientation fill."""
    r, _, _, _ = _problem()
    store_u = RatingStore(r, q=4)
    store_b = RatingStore(r, q=4, n_bins=4)
    assert store_b.n_bins == 4 and store_u.n_bins == 1
    assert store_b.r_binned.nnz == r.nnz
    assert sum(b.nnz for b in store_b.rt_binned) == r.nnz
    # binned fills never exceed the uniform ones
    assert store_b.fill_r <= store_u.fill_r
    assert store_b.fill_rt <= store_u.fill_rt
    assert store_b.worst_fill <= store_u.worst_fill
    fb = store_b.fill_breakdown()
    assert set(fb) == {"r", "rt"}
    assert fb["r"] == store_b.fill_r and fb["rt"] == store_b.fill_rt
    pairs = store_b.bin_fill_pairs()
    slots = sum(s for s, _ in pairs)
    nnz = sum(z for _, z in pairs)
    assert abs(slots / nnz - store_b.worst_fill) < 1e-12
    # the planner prices exactly that aggregate
    pa = plan_for(SPEC.m, SPEC.n, r.nnz, SPEC.f, p=1, q=4,
                  fill=store_b.worst_fill)
    pb = plan_for(SPEC.m, SPEC.n, r.nnz, SPEC.f, p=1, q=4, bin_fills=pairs)
    assert pa.terms["R_shard"] == pb.terms["R_shard"]
    # row slices cover the binned matrix exactly
    npp = store_b.m_pad // 4
    assert sum(store_b.x_slice_binned(j * npp, (j + 1) * npp).nnz
               for j in range(4)) == r.nnz
    # uniform stores don't grow binned shards or accept binned queries
    assert store_u.r_binned is None
    with pytest.raises(AssertionError):
        store_u.x_slice_binned(0, npp)


def test_binned_store_bins_rt_from_csr_like_the_uniform_shards():
    """A binned p = 1 store bins each batch's R^T straight from CSR and
    keeps no uniform R^T stack; its bins equal re-binning the uniform
    store's shards, slot for slot."""
    r, _, _, _ = _problem()
    store_u = RatingStore(r, q=4)
    store_b = RatingStore(r, q=4, n_bins=4)
    assert store_b.rt_parts is None
    with pytest.raises(AssertionError):
        store_b.theta_batch_triplet(0)
    for j, got in enumerate(store_b.rt_binned):
        want = bin_padded(
            PaddedELL(idx=store_u.rt_parts.idx[j], val=store_u.rt_parts.val[j],
                      cnt=store_u.rt_parts.cnt[j],
                      n_cols=store_u.m_pad // 4), 4)
        assert got.m == want.m and got.n_cols == want.n_cols
        for gb, wb, gr, wr in zip(got.bins, want.bins, got.rows, want.rows):
            np.testing.assert_array_equal(gr, wr)
            np.testing.assert_array_equal(gb.idx, wb.idx)
            np.testing.assert_array_equal(gb.val, wb.val)
            np.testing.assert_array_equal(gb.cnt, wb.cnt)
        assert len(got.bins) == len(want.bins)


def test_binned_store_with_model_shards_builds_stacks():
    """Binned + p > 1 now builds the batch-uniform stacked theta bins
    (``rt_stacked``) instead of the p = 1 per-batch BinnedELL shards —
    the layout the mesh herm stack can shard (one shape per bin)."""
    r, _, _, _ = _problem()
    store = RatingStore(r, q=4, p=2, n_bins=4)
    assert store.r_binned is None and store.rt_binned is None
    stacks = store.rt_stacked
    assert stacks is not None and len(stacks) >= 2
    # every stack is batch-uniform and p-divisible; nonzeros conserved
    assert all(st.idx.shape[0] == 4 and st.rows % 2 == 0 for st in stacks)
    assert sum(st.nnz for st in stacks) == r.nnz
    # caps ascend and the fill pairs price exactly the stacked slots
    caps = [st.cap for st in stacks]
    assert caps == sorted(caps)
    assert store.bin_fill_pairs() == [(st.padded_slots, st.nnz)
                                      for st in stacks]


@pytest.mark.slow
def test_binned_streaming_matches_unbinned():
    """Acceptance: a binned waves >= 2 streaming run reproduces the
    unbinned factors to 1e-5 (padding slots are exact zeros, so binning is
    a layout change only), its ledger stays green, and the measured
    fill_waste_ratio drops vs the uniform layout."""
    r, _, _, _ = _problem()
    cfg = als_mod.AlsConfig(f=SPEC.f, lam=SPEC.lam, iters=3, mode="ref")
    rr = als_mod.ell_triplet(r)

    store_u = RatingStore(r, q=4)
    plan_u = _forced_plan(r, q=4, n_data=2, store=store_u)
    sched_u = build_schedule(plan_u, SPEC.m, SPEC.n, n_data=2)
    fac_u, hist_u, tel_u = run_streaming_als(store_u, sched_u, cfg,
                                             train_eval=rr)

    store_b = RatingStore(r, q=4, n_bins=4)
    acc_eps = SPEC.n * (SPEC.f * SPEC.f + 3 * SPEC.f + 1) * 4
    plan_b = plan_for(SPEC.m, SPEC.n, r.nnz, SPEC.f, p=1, q=4, n_data=2,
                      bin_fills=store_b.bin_fill_pairs(), eps=acc_eps,
                      buffers=4, hbm_bytes=1 << 22)
    sched_b = build_schedule(plan_b, SPEC.m, SPEC.n, n_data=2)
    assert len(sched_b.waves) >= 2
    fac_b, hist_b, tel_b = run_streaming_als(store_b, sched_b, cfg,
                                             train_eval=rr)

    np.testing.assert_allclose(fac_b.x, fac_u.x, atol=1e-5)
    np.testing.assert_allclose(fac_b.theta, fac_u.theta, atol=1e-5)
    for a, b in zip(hist_b, hist_u):
        assert abs(a["train_rmse"] - b["train_rmse"]) < 1e-5
    assert tel_b.peak_bytes <= tel_b.capacity_bytes

    def _rec(tel, name):
        return next(rec for rec in tel.ledger["records"]
                    if rec["name"] == name)

    for tel in (tel_u, tel_b):
        assert all(rec["ok"] for rec in tel.ledger["records"]), \
            [rec for rec in tel.ledger["records"] if not rec["ok"]]
    assert tel_u.ledger["run"]["n_bins"] == 1
    assert tel_b.ledger["run"]["n_bins"] == 4
    # the measured fill actually dropped, and the per-half fills exist
    fwu = _rec(tel_u, "fill_waste_ratio")["measured"]
    fwb = _rec(tel_b, "fill_waste_ratio")["measured"]
    assert fwb < fwu
    for name in ("fill/solve_x", "fill/accumulate_theta",
                 "fill_bound/r", "fill_bound/rt"):
        assert _rec(tel_b, name)["ok"]


@pytest.mark.slow
def test_binned_kill_and_resume_bit_exact(tmp_path):
    """A binned streaming run killed mid-iteration resumes to the same
    factors as the uninterrupted binned run — checkpoint state is
    layout-agnostic (factors in original row order)."""
    r, _, _, _ = _problem()
    cfg = als_mod.AlsConfig(f=SPEC.f, lam=SPEC.lam, iters=2, mode="ref")
    store = RatingStore(r, q=4, n_bins=4)
    acc_eps = SPEC.n * (SPEC.f * SPEC.f + 3 * SPEC.f + 1) * 4
    plan = plan_for(SPEC.m, SPEC.n, r.nnz, SPEC.f, p=1, q=4, n_data=2,
                    bin_fills=store.bin_fill_pairs(), eps=acc_eps,
                    buffers=4, hbm_bytes=1 << 22)
    sched = build_schedule(plan, SPEC.m, SPEC.n, n_data=2)
    ref_fac, _, _ = run_streaming_als(store, sched, cfg)

    ckpt = str(tmp_path / "binned_ckpt")
    shutil.rmtree(ckpt, ignore_errors=True)
    with pytest.raises(SimulatedFailure):
        run_streaming_als(store, sched, cfg, ckpt_dir=ckpt,
                          fail_after_waves=3)
    fac, _, tel = run_streaming_als(store, sched, cfg, ckpt_dir=ckpt)
    assert tel.resumed_from_step == 3
    np.testing.assert_array_equal(fac.x, ref_fac.x)
    np.testing.assert_array_equal(fac.theta, ref_fac.theta)


@pytest.mark.slow
def test_streaming_sgd_per_tile_k_matches_uniform():
    """Per-tile-K tiles stream through the grouped same-K dispatch and must
    land on bit-identical factors (slot-column slicing drops only masked
    padding) while storing strictly fewer padded slots."""
    r, rte, grid_u, tiles_u, sched_u = _sgd_problem(g=4, n_workers=2)
    grid_b = block_ell(r, g=4, per_tile_k=True)
    tiles_b = TileStore(grid_b)
    sched_b = build_sgd_schedule(grid_b, SPEC.f, n_workers=2)
    assert grid_b.padded_slots <= grid_u.padded_slots
    cfg = _sgd_cfg(epochs=2)
    fac_u, hist_u, _ = run_streaming_sgd(tiles_u, sched_u, cfg)
    fac_b, hist_b, tel_b = run_streaming_sgd(tiles_b, sched_b, cfg)
    np.testing.assert_array_equal(fac_b.x, fac_u.x)
    np.testing.assert_array_equal(fac_b.theta, fac_u.theta)
    assert tel_b.peak_bytes <= tel_b.capacity_bytes


@pytest.mark.slow
def test_streaming_hybrid_binned_matches_uniform(tmp_path):
    """Hybrid parity: binned ALS warm start + per-tile-K SGD refine lands
    within 1e-5 of the all-uniform hybrid (ALS layout change is exact to
    float roundoff; the SGD phase is bit-exact given the same start)."""
    from repro.sgd import run_streaming_hybrid
    r, rte, grid_u, tiles_u, sched_sgd_u = _sgd_problem(g=4, n_workers=2)
    rtest = als_mod.ell_triplet(rte)
    als_cfg = als_mod.AlsConfig(f=SPEC.f, lam=SPEC.lam, iters=2, mode="ref")
    cfg = _sgd_cfg(epochs=2)

    store_u = RatingStore(r, q=4)
    plan_u = _forced_plan(r, q=4, n_data=2, store=store_u)
    als_sched_u = build_schedule(plan_u, SPEC.m, SPEC.n, n_data=2)
    fac_u, hist_u, _ = run_streaming_hybrid(
        store_u, als_sched_u, tiles_u, sched_sgd_u, als_cfg, cfg,
        test_eval=rtest, ckpt_dir=str(tmp_path / "hyb_u"))

    store_b = RatingStore(r, q=4, n_bins=4)
    acc_eps = SPEC.n * (SPEC.f * SPEC.f + 3 * SPEC.f + 1) * 4
    plan_b = plan_for(SPEC.m, SPEC.n, r.nnz, SPEC.f, p=1, q=4, n_data=2,
                      bin_fills=store_b.bin_fill_pairs(), eps=acc_eps,
                      buffers=4, hbm_bytes=1 << 22)
    als_sched_b = build_schedule(plan_b, SPEC.m, SPEC.n, n_data=2)
    grid_b = block_ell(r, g=4, per_tile_k=True)
    tiles_b = TileStore(grid_b)
    sched_sgd_b = build_sgd_schedule(grid_b, SPEC.f, n_workers=2)
    fac_b, hist_b, _ = run_streaming_hybrid(
        store_b, als_sched_b, tiles_b, sched_sgd_b, als_cfg, cfg,
        test_eval=rtest, ckpt_dir=str(tmp_path / "hyb_b"))

    np.testing.assert_allclose(fac_b.x, fac_u.x, atol=1e-5)
    np.testing.assert_allclose(fac_b.theta, fac_u.theta, atol=1e-5)
    for a, b in zip(hist_b, hist_u):
        assert a["phase"] == b["phase"]
        assert abs(a["test_rmse"] - b["test_rmse"]) < 1e-5
