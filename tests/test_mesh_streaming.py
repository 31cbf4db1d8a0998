"""ISSUE-5 acceptance suite: out-of-core waves on a real (data, model) mesh.

Fast tests cover the topology-aware reduction (bit-for-bit f64 vs the naive
all-reduce oracle, schedule determinism, traffic accounting) and the
p-sharded store invariants — pure host-side, no devices needed.

The end-to-end mesh runs are marked ``mesh`` and execute in a subprocess
with ``--xla_force_host_platform_device_count=8`` (the same harness as
test_distributed.run_script), so the main pytest process keeps its real
single-device view; CI runs them in the dedicated mesh-streaming lane.
"""
import numpy as np
import pytest

from repro.distributed.reduce import (DeviceTopology, allreduce_oracle,
                                      linear_topology, reduce_traffic,
                                      topology_reduce)
from repro.outofcore import FactorStore, RatingStore
from repro.sparse import synth

SPEC = synth.SynthSpec("oc", 96, 40, 1500, 8, 0.05)


def _bitexact(a: np.ndarray, b: np.ndarray) -> bool:
    assert a.dtype == b.dtype == np.float64, (a.dtype, b.dtype)
    return bool((a.view(np.uint64) == b.view(np.uint64)).all())


# ---------------------------------------------------------------------------
# Topology-aware reduction (fast, host-side)
# ---------------------------------------------------------------------------

def _parts(n_dev=8, shape=(6, 4, 4), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal(shape).astype(np.float32)
            for _ in range(n_dev)]


@pytest.mark.parametrize("groups", [
    ((0, 1, 2, 3, 4, 5, 6, 7),),                  # flat ring
    ((0, 1), (2, 3), (4, 5), (6, 7)),             # paper: 2 per PCIe switch
    ((0, 1, 2, 3), (4, 5, 6, 7)),                 # 2 sockets
    ((0, 1, 2), (3, 4, 5), (6, 7)),               # ragged domains
])
def test_topology_reduce_matches_allreduce_oracle_bitexact(groups):
    """Acceptance: for f32 partials the staged f64 reduction is exact, so
    ANY declared grouping must match the flat oracle bit for bit."""
    parts = _parts()
    got = topology_reduce(parts, DeviceTopology(groups))
    assert _bitexact(got, allreduce_oracle(parts))


def test_topology_reduce_deterministic_order():
    """The schedule depends only on the declared topology: scrambled group
    spellings normalize to the same ascending-device-id fold, and repeated
    runs are bit-identical."""
    parts = _parts(4)
    a = topology_reduce(parts, DeviceTopology(((1, 0), (3, 2))))
    b = topology_reduce(parts, DeviceTopology(((0, 1), (2, 3))))
    c = topology_reduce(parts, DeviceTopology(((0, 1), (2, 3))))
    assert _bitexact(a, b) and _bitexact(b, c)
    # default topology (single flat group) is the oracle itself
    assert _bitexact(topology_reduce(parts), allreduce_oracle(parts))


def test_topology_validation_and_helpers():
    with pytest.raises(AssertionError):
        DeviceTopology(((0, 1), (1, 2)))           # overlapping
    with pytest.raises(AssertionError):
        DeviceTopology(((0, 2),))                  # gap
    topo = linear_topology(6, 4)
    assert topo.groups == ((0, 1, 2, 3), (4, 5)) and topo.n_devices == 6
    assert "0,1,2,3" in topo.describe()


def test_reduce_traffic_two_phase_beats_flat_on_slow_link():
    """The paper's Fig. 5b claim in the analytic model: grouping keeps
    slow-link traffic at one already-reduced partial per extra domain,
    while the flat scheme drags (D-1)/D of everything across every link."""
    nbytes = 1 << 20
    grouped = reduce_traffic(nbytes, linear_topology(8, 2))
    flat = reduce_traffic(nbytes, linear_topology(8, 8))
    assert flat["slow_link_bytes"] == 0 and flat["slow_link_crossings"] == 0
    # a single flat domain IS the flat scheme: byte counts must coincide
    assert flat["fast_link_bytes"] == flat["flat_all_links_bytes"]
    assert grouped["slow_link_crossings"] == 3
    assert grouped["slow_link_bytes"] == 3 * nbytes
    assert grouped["slow_link_bytes"] < grouped["flat_all_links_bytes"]
    # staging rearranges the D-1 partial moves, it never adds any
    for t in (grouped, flat):
        assert t["fast_link_bytes"] + t["slow_link_bytes"] == \
            t["flat_all_links_bytes"] == 7 * nbytes


# ---------------------------------------------------------------------------
# p-sharded store invariants (fast, host-side)
# ---------------------------------------------------------------------------

def test_rating_store_model_partition_roundtrips():
    """p > 1 stores carry R column-partitioned into the p theta shards:
    same nonzeros, shard-local item coordinates, mesh-layout slices."""
    r, _, _, _ = synth.make_synthetic_ratings(SPEC, seed=0)
    store = RatingStore(r, q=4, p=2)
    parts = store.r_model_parts
    assert parts.idx.shape[0] == 2
    assert int(parts.cnt.sum()) == r.nnz
    npp = store.n // 2
    idx, val, cnt = store.x_slice_mesh_triplet(0, store.m_pad // 4)
    rows, pk = idx.shape
    K_loc = pk // 2
    assert cnt.shape == (rows, 2)
    # per-shard columns only reference shard-local coordinates
    for k in range(2):
        blk = idx[:, k * K_loc:(k + 1) * K_loc]
        live = np.arange(K_loc)[None, :] < cnt[:, k][:, None]
        if live.any():
            assert blk[live].max() < npp
    # slice holds the same nonzero values as the same rows of plain R
    rows_r = slice(0, store.m_pad // 4)
    rval, rcnt = store.r.val[rows_r], store.r.cnt[rows_r]
    assert int(cnt.sum()) == int(rcnt.sum())
    np.testing.assert_allclose(np.sort(val[val != 0]),
                               np.sort(rval[rval != 0]), rtol=1e-6)
    assert store.fill_r_model >= 1.0
    assert store.worst_fill >= store.fill_r_model
    # p = 1 store refuses to cut mesh slices
    with pytest.raises(AssertionError):
        RatingStore(r, q=4).x_slice_mesh_triplet(0, 8)


def test_factor_store_shard_io():
    fs = FactorStore.from_arrays(np.zeros((8, 3), np.float32),
                                 np.arange(12, dtype=np.float32).reshape(6, 2))
    np.testing.assert_array_equal(fs.read_shard("theta", 1, 3),
                                  fs.theta[2:4])
    fs.write_shard("theta", 2, 3, np.full((2, 2), 9.0))
    assert (fs.theta[4:6] == 9.0).all() and (fs.theta[:4] != 9.0).all()
    with pytest.raises(AssertionError):
        fs.shard_bounds("theta", 0, 4)          # 6 rows not divisible by 4


# ---------------------------------------------------------------------------
# End-to-end mesh runs (subprocess-pinned to 8 host devices)
# ---------------------------------------------------------------------------

MESH_COMMON = """
import numpy as np, jax
from repro.core import als as als_mod
from repro.core.partition import plan_for, streaming_acc_bytes
from repro.outofcore import (RatingStore, SimulatedFailure, TileStore,
                             build_schedule, build_sgd_schedule,
                             required_capacity_bytes, run_streaming_als,
                             run_streaming_sgd)
from repro.sparse import synth
from repro.launch.mesh import make_mesh

assert len(jax.devices()) == 8, jax.devices()
SPEC = synth.SynthSpec("oc", 96, 40, 1500, 8, 0.05)
r, rt, rte, _ = synth.make_synthetic_ratings(SPEC, seed=0)
rtest = als_mod.ell_triplet(rte)

def als_plan(store, q, n_data, p):
    return plan_for(SPEC.m, SPEC.n, r.nnz, SPEC.f, p=p, q=q, n_data=n_data,
                    fill=store.worst_fill, eps=0, buffers=4,
                    acc_bytes=streaming_acc_bytes(SPEC.n, SPEC.f),
                    hbm_bytes=1 << 22)
"""


@pytest.mark.mesh
def test_streaming_als_on_mesh_matches_incore():
    """Acceptance: forced waves >= 2 streaming ALS on a (data=2, model=2)
    mesh with p = 2 theta shards matches the in-core single-device factors
    to 1e-4, under the p-sharded plan capacity."""
    from test_distributed import run_script
    run_script(MESH_COMMON + """
cfg = als_mod.AlsConfig(f=SPEC.f, lam=SPEC.lam, iters=3, mode="ref")
rr, rtt = als_mod.ell_triplet(r), als_mod.ell_triplet(rt)
state, hist = als_mod.als_train(rr, rtt, r.m, rt.m, cfg, test=rtest)

store = RatingStore(r, q=4, p=2)
plan = als_plan(store, q=4, n_data=2, p=2)
assert plan.waves >= 2 and plan.p == 2
sched = build_schedule(plan, SPEC.m, SPEC.n, n_data=2)
mesh = make_mesh((2, 2), ("data", "model"))
fac, shist, tel = run_streaming_als(store, sched, cfg, mesh=mesh,
                                    train_eval=rr, test_eval=rtest)
assert len(shist) == len(hist)
for a, b in zip(shist, hist):
    assert abs(a["train_rmse"] - b["train_rmse"]) < 1e-4, (a, b)
    assert abs(a["test_rmse"] - b["test_rmse"]) < 1e-4, (a, b)
assert np.abs(fac.x[:r.m] - np.asarray(state.x)).max() < 1e-4
assert np.abs(fac.theta - np.asarray(state.theta)).max() < 1e-4
# per-device simulated peak under the plan capacity AND the honest model
assert tel.peak_bytes <= tel.capacity_bytes, (tel.peak_bytes, tel.capacity_bytes)
assert tel.peak_bytes <= required_capacity_bytes(store, sched, SPEC.f)
assert tel.waves_run == 2 * len(sched.waves) * cfg.iters
assert tel.topology and tel.reduce_fast_bytes > 0
print("mesh ALS parity OK")
""")


@pytest.mark.mesh
def test_streaming_als_mesh_ragged_last_wave():
    """q = 3 with n_data = 2, p = 2 (q not divisible by n_data * p): the
    last wave carries one batch, is padded with empty rows/batches on the
    mesh, and still matches the in-core trajectory."""
    from test_distributed import run_script
    run_script(MESH_COMMON + """
cfg = als_mod.AlsConfig(f=SPEC.f, lam=SPEC.lam, iters=2, mode="ref")
rr, rtt = als_mod.ell_triplet(r), als_mod.ell_triplet(rt)
state, hist = als_mod.als_train(rr, rtt, r.m, rt.m, cfg)

store = RatingStore(r, q=3, p=2)
plan = als_plan(store, q=3, n_data=2, p=2)
sched = build_schedule(plan, SPEC.m, SPEC.n, n_data=2)
assert len(sched.waves) == 2 and len(sched.waves[-1].batches) == 1
mesh = make_mesh((2, 2), ("data", "model"))
fac, shist, tel = run_streaming_als(store, sched, cfg, mesh=mesh,
                                    train_eval=rr)
assert abs(shist[-1]["train_rmse"] - hist[-1]["train_rmse"]) < 1e-4
assert np.abs(fac.x[:r.m] - np.asarray(state.x)).max() < 1e-4
assert np.abs(fac.theta - np.asarray(state.theta)).max() < 1e-4
assert tel.peak_bytes <= tel.capacity_bytes
print("mesh ALS ragged OK")
""")


@pytest.mark.mesh
def test_streaming_als_mesh_kill_resume_bit_exact():
    """Killed mid-solve-X (wave 1) and mid-accumulate-Theta (wave 3), the
    mesh run resumes to bit-identical factors: the checkpoint carries the
    per-data-shard f64 partials, so the topology reduce replays exactly."""
    from test_distributed import run_script
    run_script(MESH_COMMON + """
import tempfile
cfg = als_mod.AlsConfig(f=SPEC.f, lam=SPEC.lam, iters=2, mode="ref")
store = RatingStore(r, q=4, p=2)
sched = build_schedule(als_plan(store, 4, 2, 2), SPEC.m, SPEC.n, n_data=2)
mesh = make_mesh((2, 2), ("data", "model"))
ref, _, _ = run_streaming_als(store, sched, cfg, mesh=mesh)
for kill in (1, 3):
    with tempfile.TemporaryDirectory() as d:
        try:
            run_streaming_als(store, sched, cfg, mesh=mesh, ckpt_dir=d,
                              fail_after_waves=kill)
            raise SystemExit("simulated kill did not fire")
        except SimulatedFailure:
            pass
        fac, _, tel = run_streaming_als(store, sched, cfg, mesh=mesh,
                                        ckpt_dir=d)
        assert tel.resumed_from_step == kill
        assert np.array_equal(fac.x, ref.x), kill
        assert np.array_equal(fac.theta, ref.theta), kill
print("mesh ALS resume OK")
""")


@pytest.mark.mesh
def test_streaming_als_mesh_binned_matches_incore():
    """PR-10 acceptance: a degree-binned store (n_bins = 4) streams on a
    p = 2 mesh — theta half through the batch-uniform stacked bins — and
    still matches the in-core trajectory, with a validating zero-error
    ledger priced from the store's real bin fills."""
    from test_distributed import run_script
    run_script(MESH_COMMON + """
from repro.obs.ledger import validate_ledger
cfg = als_mod.AlsConfig(f=SPEC.f, lam=SPEC.lam, iters=3, mode="ref")
rr, rtt = als_mod.ell_triplet(r), als_mod.ell_triplet(rt)
state, hist = als_mod.als_train(rr, rtt, r.m, rt.m, cfg, test=rtest)

store = RatingStore(r, q=4, p=2, n_bins=4)
assert store.rt_stacked is not None and len(store.rt_stacked) >= 2
plan = plan_for(SPEC.m, SPEC.n, r.nnz, SPEC.f, p=2, q=4, n_data=2,
                bin_fills=store.bin_fill_pairs(), eps=0, buffers=4,
                acc_bytes=streaming_acc_bytes(SPEC.n, SPEC.f),
                hbm_bytes=1 << 22)
assert plan.waves >= 2 and plan.p == 2
sched = build_schedule(plan, SPEC.m, SPEC.n, n_data=2)
mesh = make_mesh((2, 2), ("data", "model"))
fac, shist, tel = run_streaming_als(store, sched, cfg, mesh=mesh,
                                    train_eval=rr, test_eval=rtest)
for a, b in zip(shist, hist):
    assert abs(a["train_rmse"] - b["train_rmse"]) < 1e-4, (a, b)
assert np.abs(fac.x[:r.m] - np.asarray(state.x)).max() < 1e-4
assert np.abs(fac.theta - np.asarray(state.theta)).max() < 1e-4
assert tel.peak_bytes <= tel.capacity_bytes
assert tel.peak_bytes <= required_capacity_bytes(store, sched, SPEC.f)
led = tel.ledger
assert led["run"]["n_bins"] == 4 and led["run"]["p"] == 2
assert led["run"]["autotune"] is None          # layout was pinned by hand
summary = validate_ledger(led)
assert summary["errors"] == 0 and summary["ok"], summary
names = {rec["name"] for rec in led["records"]}
assert {"bytes_streamed", "padded_slots", "nnz_streamed"} <= names
for rec in led["records"]:
    if rec["check"] == "exact":
        assert rec["ok"] and rec["drift"] == 0.0, rec
print("mesh binned ALS parity OK")
""")


@pytest.mark.mesh
def test_streaming_als_mesh_binned_kill_resume_bit_exact():
    """Binned mesh runs (p = 2, n_bins = 4) killed mid-half resume to
    bit-identical factors — the stacked-bin theta half checkpoints its
    per-data-shard f64 partials like the uniform path."""
    from test_distributed import run_script
    run_script(MESH_COMMON + """
import tempfile
cfg = als_mod.AlsConfig(f=SPEC.f, lam=SPEC.lam, iters=2, mode="ref")
store = RatingStore(r, q=4, p=2, n_bins=4)
plan = plan_for(SPEC.m, SPEC.n, r.nnz, SPEC.f, p=2, q=4, n_data=2,
                bin_fills=store.bin_fill_pairs(), eps=0, buffers=4,
                acc_bytes=streaming_acc_bytes(SPEC.n, SPEC.f),
                hbm_bytes=1 << 22)
sched = build_schedule(plan, SPEC.m, SPEC.n, n_data=2)
mesh = make_mesh((2, 2), ("data", "model"))
ref, _, _ = run_streaming_als(store, sched, cfg, mesh=mesh)
for kill in (1, 3, 5):
    with tempfile.TemporaryDirectory() as d:
        try:
            run_streaming_als(store, sched, cfg, mesh=mesh, ckpt_dir=d,
                              fail_after_waves=kill)
            raise SystemExit("simulated kill did not fire")
        except SimulatedFailure:
            pass
        fac, _, tel = run_streaming_als(store, sched, cfg, mesh=mesh,
                                        ckpt_dir=d)
        assert tel.resumed_from_step == kill
        assert np.array_equal(fac.x, ref.x), kill
        assert np.array_equal(fac.theta, ref.theta), kill
print("mesh binned ALS resume OK")
""")


@pytest.mark.mesh
def test_streaming_sgd_on_mesh_matches_incore():
    """Streaming SGD with each wave's tiles sharded one-per-device over the
    joint (data, model) axes matches the in-core trajectory to 1e-4 —
    including a ragged wave split (n_workers = 3 on a g = 4 grid)."""
    from test_distributed import run_script
    run_script(MESH_COMMON + """
from repro.sgd import SgdConfig, block_ell, sgd_train
grid = block_ell(r, g=4)
cfg = SgdConfig(f=SPEC.f, lam=SPEC.lam, lr=0.1, mode="ref", seed=3,
                schedule="inverse_time", decay=1.0, epochs=3)
state, hist = sgd_train(grid, cfg, test=rtest)
mesh = make_mesh((4, 2), ("data", "model"))
for n_workers in (2, 3):          # 3 -> ragged waves (3 tiles + 1 tile)
    tiles = TileStore(grid)
    sched = build_sgd_schedule(grid, SPEC.f, n_workers=n_workers)
    fac, shist, tel = run_streaming_sgd(tiles, sched, cfg, test_eval=rtest,
                                        mesh=mesh)
    assert np.abs(fac.x - np.asarray(state.x)).max() < 1e-4, n_workers
    assert np.abs(fac.theta - np.asarray(state.theta)).max() < 1e-4
    assert abs(shist[-1]["test_rmse"] - hist[-1]["test_rmse"]) < 1e-4
    assert tel.peak_bytes <= tel.capacity_bytes
    assert tel.waves_run == sched.waves_per_epoch * cfg.epochs
print("mesh SGD parity OK")
""")
