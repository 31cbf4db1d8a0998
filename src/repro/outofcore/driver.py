"""Streaming ALS driver: execute a wave schedule end to end (§4.4).

Per iteration the driver runs the two halves of the schedule:

- **solve-X**: Theta resident on device; each wave's R rows, cut into the
  store's degree bins in the accumulate update's layout (made once per
  store), are double-buffered host->device through
  ``data.prefetch.Prefetcher`` while the current wave solves its X rows
  through the accumulate half's programs (``core.als.solve_rows_lanes``);
  solved slices are written straight back to the host ``FactorStore``.
- **accumulate-Theta**: A/B Hermitian accumulators resident, with the
  systems on the lanes (``A [f*f, n]``, ``B [f, n]``, the solve kernel's
  layout but flat); each wave streams its batches' binned R^T column
  shards together with the freshly solved X slices, each bin added by
  one donated program (``core.als.accumulate_bin``), and after the last
  wave the accumulated systems are solved as they lie
  (``core.als.solve_accumulated``).

``n_bins = 1`` is one degree bin per orientation, streamed by the same
halves.

Every wave completion checkpoints the full resumable state (factors +
accumulators + global step) through ``checkpoint.CheckpointManager``, so a
killed run restarts mid-iteration — the paper's §4.4 fault tolerance at wave
rather than iteration granularity.

A ``MemoryMeter`` tracks the *simulated device* footprint: the meter models
one device of the ``n_data`` axis (wave payloads are divided by ``n_data``;
replicated residents — the fixed factor, the accumulators — are counted in
full), which is what the planner's eq. (8) budget prices.

**Mesh streaming** (``mesh=`` set): the same schedule executes on a real
``(data, model)`` device mesh — the paper's full data x model parallelism
instead of one model-shard's simulated view:

- the solve-X half dispatches each wave through
  ``distributed.su_als.make_wave_update_fn`` (shard-mapped SU-ALS: local
  partial Hermitians from each device's theta shard, psum-scatter over the
  model axis, p-way parallel solve, gather);
- theta lives as ``p`` model shards — each device holds only its
  ``[n/p, f]`` shard plus its column block of the wave's R slice, and the
  meter prices exactly that;
- the accumulate-Theta half computes per-(data, model) partial Hermitians
  on the mesh (``make_wave_herm_fn``) with **no in-program reduction**:
  each data shard accumulates its own partials across waves (float64 on
  host, standing in for device-resident partial state), and the half ends
  with ``distributed.reduce.topology_reduce`` — the paper's Fig. 5b
  intra-socket-ring-then-inter-socket-tree schedule, validated bit-for-bit
  against the flat all-reduce oracle — before each model shard solves and
  writes back its own theta rows.
"""
from __future__ import annotations

import contextlib
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.core import als as als_mod
from repro.core.objective import rmse_padded
from repro.data.prefetch import Prefetcher
from repro.kernels import ops as kops
from repro.kernels.batch_solve import lanes_per_step
from repro.kernels.budgets import BUDGETS, footprint_bytes
from repro.obs.ledger import Ledger
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import compile_listener, current_tracer, phase
from repro.outofcore.runtime import (MemoryMeter, SimulatedFailure,
                                     StreamTelemetry, WaveCheckpointer)
from repro.outofcore.schedule import (IterationSchedule,
                                      predicted_stream_stats,
                                      required_capacity_bytes)
from repro.outofcore.store import FactorStore, RatingStore, binned_nbytes

__all__ = ["MemoryMeter", "SimulatedFailure", "StreamTelemetry",
           "run_streaming_als"]


def _zeros_ckpt_tree(m_pad: int, n: int, f: int, n_dev: int = 0) -> dict:
    """Checkpoint structure.  The acc leaves are committed EMPTY (zero rows)
    by solve-X-half saves — restore never reads them there, and shipping
    full-sized zeros would dominate the per-wave checkpoint I/O — and are
    replaced with the live accumulators by mid-accumulate-half saves: the
    serial f32 partial sums (systems on the lanes: ``A [f*f, n]``,
    ``B [f, n]``, ``c [n]``), or, on the mesh path (``n_dev`` > 0), the
    PER-DATA-SHARD float64 partials so a resume replays the topology-aware
    reduction bit-exactly from the same summands.
    """
    acc_dt = np.float64 if n_dev else np.float32
    lead = (n_dev,) if n_dev else ()
    # the layout each path resumes from: [n, f, f] per data shard on the
    # mesh, the systems on the lanes ([f*f, n]) on one device
    a0, b0 = (lead + (0, f, f), lead + (0, f)) if n_dev else \
        ((f * f, 0), (f, 0))
    return {
        "x": np.zeros((m_pad, f), np.float32),
        "theta": np.zeros((n, f), np.float32),
        "a_acc": np.zeros(a0, acc_dt),
        "b_acc": np.zeros(b0, acc_dt),
        "c_acc": np.zeros(lead + (0,), acc_dt),
    }


def _mesh_axes(mesh) -> tuple[int, int, object]:
    """(n_data, p, col_dim spec entry) of a streaming mesh."""
    from repro.distributed.su_als import _col_axes
    assert "data" in mesh.axis_names, mesh.axis_names
    col_axes, col_dim = _col_axes(mesh)
    assert col_axes, f"mesh needs a model axis, got {mesh.axis_names}"
    p = 1
    for a in col_axes:
        p *= mesh.shape[a]
    return mesh.shape["data"], p, col_dim


@contextlib.contextmanager
def _counting_compiles(registry, tracer):
    """JAX's traces and compiles booked into ``registry`` (and ``tracer``)
    while the block runs: ``obs.compile_listener``, registered with
    ``jax.monitoring`` for the block only."""
    listener = compile_listener(registry, tracer)
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


def run_streaming_als(
    ratings: RatingStore,
    sched: IterationSchedule,
    cfg: als_mod.AlsConfig,
    *,
    factors: Optional[FactorStore] = None,
    ckpt_dir: Optional[str] = None,
    keep: int = 3,
    prefetch_depth: int = 2,
    train_eval=None,                 # (idx, val, cnt) for per-iteration RMSE
    test_eval=None,
    fail_after_waves: Optional[int] = None,
    mesh=None,
    topology=None,
    callback=None,
    tracer=None,
    registry=None,
) -> tuple[FactorStore, List[dict], StreamTelemetry]:
    """Run ``cfg.iters`` streaming ALS iterations of ``sched`` over ``ratings``.

    Returns (factor store, per-iteration history, telemetry).  With
    ``ckpt_dir`` set the run resumes from the latest committed wave.

    Observability: every hot phase runs inside an ``obs`` span — the whole
    run (``driver``), each iteration/half, one ``solve`` span per wave,
    ``reduce`` for the mesh epilogue, ``checkpoint`` per commit, and on the
    prefetch worker ``prefetch_slice`` / ``prefetch_load`` per wave — and
    all counting/timing goes through ``registry`` (an
    ``obs.MetricsRegistry``; one is created when not passed), which also
    counts JAX's traces and compiles during the run (``jax/traces``,
    ``jax/traces/<function>``, ``jax/trace_seconds``,
    ``jax/compile_seconds``; ``obs.compile_listener``).  ``tracer``
    defaults to the process-wide tracer (``obs.set_tracer`` /
    ``--trace``); with the default ``NULL_TRACER`` the spans are no-ops
    and only the cheap per-wave metrics remain.  The returned telemetry is
    ``StreamTelemetry.from_registry`` — same fields as ever, plus the
    ``phase_seconds`` breakdown, which each history record also carries as
    its per-iteration delta.

    Both halves stream the store's degree bins (one bin at ``n_bins``
    1) and dispatch the kernels once per bin at that bin's own K.  On one
    device (a p = 1 store) both halves add each bin's Hermitians into
    lanes accumulators and solve them once (``core.als.accumulate_bin`` /
    ``solve_accumulated``), the bins made once per store.

    With ``mesh`` set (axes ``("data", "model")``, sizes matching
    ``sched.n_data`` and ``sched.p``, a ``p > 1`` store) every wave
    executes shard-mapped on the real mesh and theta is handled as p model
    shards: the solve-X half streams the mesh layout of R
    (``x_slice_mesh_triplet``), the accumulate-Theta half the store's
    batch-uniform stacked bins (``rt_stacked``) — one ``wave_herm``
    dispatch per bin, partials host-scattered through the ``items`` maps.
    ``topology`` is the ``distributed.reduce.DeviceTopology`` of the data
    axis for the accumulate-half reduction (default: fast domains of 2,
    the paper's 2-GPUs-per-PCIe-switch machine).
    """
    assert ratings.m_pad == sched.m_pad and ratings.n == sched.n, \
        "RatingStore and IterationSchedule were built for different shapes"
    f = cfg.f
    m_pad, n, n_data = sched.m_pad, sched.n, sched.n_data
    W = len(sched.waves)
    wpi = sched.waves_per_iteration            # 2 * W checkpoint steps/iter
    n_bins = ratings.n_bins
    assert (mesh is None) == (ratings.rt_stacked is None), \
        "mesh streaming needs the batch-uniform bins (rt_stacked) of a " \
        "RatingStore built with p > 1; one device streams a p = 1 store"

    p = 1
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed import reduce as dreduce
        from repro.distributed.su_als import (make_wave_herm_fn,
                                              make_wave_update_fn)
        mesh_n_data, p, col_dim = _mesh_axes(mesh)
        assert mesh_n_data == n_data, \
            f"mesh data axis {mesh_n_data} != schedule n_data {n_data}"
        assert p == sched.p == ratings.p, (p, sched.p, ratings.p)
        assert n % p == 0, (n, p)
        topo = topology or dreduce.linear_topology(n_data, group_size=2)
        assert topo.n_devices == n_data, (topo.describe(), n_data)
        # per-reduce link traffic is a pure function of the payload size and
        # the topology — priced once here, measured against in the ledger
        topo_traffic = dreduce.reduce_traffic(n * (f * f + f + 1) * 4, topo)
        wave_update = make_wave_update_fn(
            mesh, cfg.lam, mode=cfg.mode,
            tm=cfg.tm, tk=cfg.tk, f_mult=cfg.f_mult)
        wave_herm = make_wave_herm_fn(
            mesh, cfg.lam, mode=cfg.mode,
            tm=cfg.tm, tk=cfg.tk, f_mult=cfg.f_mult)
        rows_sh = NamedSharding(mesh, P("data", col_dim))
        fixed_sh = NamedSharding(mesh, P(col_dim, None))

    meter = MemoryMeter()
    tracer = tracer if tracer is not None else current_tracer()
    reg = registry if registry is not None else MetricsRegistry()
    topo_desc = topo.describe() if mesh is not None else ""

    mgr = CheckpointManager(ckpt_dir, keep=keep) if ckpt_dir else None
    acc_restored = None
    start_step = 0
    if mgr is not None:
        tree, start_step = mgr.restore_or_init(
            _zeros_ckpt_tree(m_pad, n, f, n_data if mesh is not None else 0),
            lambda: None)
        if start_step:
            factors = FactorStore.from_arrays(tree["x"], tree["theta"])
            if start_step % wpi > W:       # killed mid-accumulate-Theta
                acc_restored = (tree["a_acc"], tree["b_acc"], tree["c_acc"])
    reg.gauge("resumed_from_step").set(start_step)
    if factors is None:
        st = als_mod.als_init(ratings.m, n, cfg)
        x0 = np.zeros((m_pad, f), np.float32)
        x0[:ratings.m] = np.asarray(st.x)
        factors = FactorStore.from_arrays(x0, np.asarray(st.theta))

    ckpt = WaveCheckpointer(mgr, fail_after_waves,
                            tracer=tracer, registry=reg)

    def _save(step: int, acc=None):
        def tree_fn():
            tree = _zeros_ckpt_tree(m_pad, n, f,
                                    n_data if mesh is not None else 0)
            # snapshot copies: the manager commits async while later waves
            # keep mutating the live factor arrays
            tree["x"], tree["theta"] = factors.x.copy(), factors.theta.copy()
            if acc is not None:
                # np.array, NOT np.asarray: on the mesh path the acc leaves
                # are the live f64 per-shard accumulators and asarray would
                # alias them (same dtype), racing the async commit against
                # the next wave's in-place `A_dev += A_w`
                tree["a_acc"] = np.array(acc[0], tree["a_acc"].dtype)
                tree["b_acc"] = np.array(acc[1], tree["b_acc"].dtype)
                tree["c_acc"] = np.array(acc[2], tree["c_acc"].dtype)
            return tree
        ckpt.save(step, tree_fn)

    def _count_solve(rows: int, factored: int):
        """``rows`` real systems solved; ``factored`` systems the solve
        factored for them (lane padding included)."""
        reg.counter("solve_systems").inc(rows)
        reg.counter("solve_padded_systems").inc(factored)

    # ------------------------------------------------------------------
    # Single-device halves: each wave cut bin-wise into lanes bins that
    # the store makes once, each bin added at its own K into lanes
    # accumulators, the systems solved once a wave (solve-X) or once a
    # half (accumulate-Theta).  Padding slots are exact zeros.
    # ------------------------------------------------------------------
    def _acc_init(acc0, width: int):
        """The single-device accumulators, systems on the lanes in
        ``width`` columns: zeros, or the ones a mid-half checkpoint
        restored."""
        if acc0 is not None:
            return tuple(jnp.asarray(a, jnp.float32) for a in acc0)
        return (jnp.zeros((f * f, width), jnp.float32),
                jnp.zeros((f, width), jnp.float32),
                jnp.zeros((width,), jnp.float32))

    def _solve_theta(A, B, c, col_of):
        """Solve the accumulated systems and write Theta back; ``col_of``
        is each item's column."""
        meter.alloc("theta_out", n * f * 4)
        with phase("als.solve_accumulated", cat="solve_accumulated",
                   tracer=tracer, registry=reg, systems=A.shape[-1], f=f):
            theta = jnp.take(als_mod.solve_accumulated(A, B, c, cfg),
                             col_of, axis=0)
            factors.write_slice("theta", 0, n, np.asarray(theta))
        _count_solve(n, kops.solve_width(A.shape[-1], f, cfg.mode))
        meter.free("theta_out")

    def _x_half(it: int, first_wave: int):
        theta_dev = jnp.asarray(factors.theta)
        meter.alloc("fixed_theta", factors.theta.nbytes)
        scratch = (sched.waves[0].rows * (f * f + 2 * f) * 4) // n_data

        def gen():
            for wave in sched.waves[first_wave:]:
                cut = (wave.row_start, wave.row_stop, cfg.batch_rows)
                yield (wave, ratings.x_wave_layout(*cut),
                       ratings.x_wave_lanes(*cut))

        def put(item):
            wave, layout, lbs = item
            # counted as the wave's bin-wise cut weighs (x_slice_binned:
            # each bin's rows at its K), which the plan predicts
            nb = sum(lb.rows * (8 * lb.idx.shape[0] + 4) for lb in lbs)
            slots = sum(lb.rows * lb.idx.shape[0] for lb in lbs)
            nz = sum(lb.nnz for lb in lbs)
            meter.alloc(f"xwave{wave.index}", nb // len(wave.batches))
            reg.counter("padded_slots").inc(slots)
            reg.counter("nnz_streamed").inc(nz)
            reg.counter("x_padded_slots").inc(slots)
            reg.counter("x_nnz_streamed").inc(nz)
            dev = [lb.to_device() for lb in lbs]
            return wave, (layout.width, jnp.asarray(layout.col_of), dev), nb

        def bin_span(k, lb):
            reg.counter("x_accumulate_slots").inc(lb.slots)
            reg.counter("x_accumulate_ratings").inc(lb.nnz)
            return phase("als.accumulate", cat="accumulate", tracer=tracer,
                         registry=reg, half="x", bin=k, rows=lb.rows,
                         slots=lb.slots, nnz=lb.nnz, x_rows=n, n=m_w, f=f)

        try:
            with Prefetcher(gen(), depth=prefetch_depth, put=put,
                            tracer=tracer, registry=reg) as pf:
                for wave, (width, col_of, lbs), nb in pf:
                    m_w = wave.row_stop - wave.row_start
                    with phase("als.wave_x", cat="solve", tracer=tracer,
                               registry=reg, wave=wave.index,
                               iteration=it + 1, bytes=nb, bins=len(lbs)):
                        meter.alloc("x_scratch", scratch)
                        rows = np.asarray(als_mod.solve_rows_lanes(
                            theta_dev, lbs, col_of, width, cfg,
                            each_bin=bin_span))
                        meter.free("x_scratch")
                        _count_solve(m_w, kops.solve_width(width, f,
                                                           cfg.mode))
                        factors.write_slice("x", wave.row_start,
                                            wave.row_stop, rows)
                    meter.free(f"xwave{wave.index}")
                    reg.counter("waves_run").inc()
                    reg.counter("batches_loaded").inc(len(wave.batches))
                    reg.counter("bytes_streamed").inc(nb)
                    _save(it * wpi + wave.index + 1)
        finally:
            meter.free("fixed_theta")

    def _theta_half(it: int, first_wave: int, acc0=None):
        acc_bytes = n * (f * f + f + 1) * 4
        meter.alloc("acc", acc_bytes)
        # the first R^T shard's bins lie end to end in the accumulators,
        # so they add into runs of columns; the other shards scatter
        layout = ratings.theta_acc_layout(cfg.batch_rows)
        A, B, c = _acc_init(acc0, layout.width)

        def gen():
            for wave in sched.waves[first_wave:]:
                payload = [
                    (b, ratings.theta_batch_binned(b.index),
                     ratings.theta_batch_lanes(b.index, cfg.batch_rows),
                     factors.read_slice("x", b.row_start, b.row_stop))
                    for b in wave.batches]
                yield wave, payload

        def put(item):
            wave, payload = item
            nb = sum(binned_nbytes(bell) + x.nbytes
                     for _, bell, _lb, x in payload)
            meter.alloc(f"twave{wave.index}", nb // len(payload))
            slots = sum(int(bell.padded_slots) for _, bell, _lb, _x in payload)
            nz = sum(int(bell.nnz) for _, bell, _lb, _x in payload)
            reg.counter("padded_slots").inc(slots)
            reg.counter("nnz_streamed").inc(nz)
            reg.counter("t_padded_slots").inc(slots)
            reg.counter("t_nnz_streamed").inc(nz)
            dev = [([lb.to_device() for lb in lbs], jnp.asarray(x))
                   for _b, _bell, lbs, x in payload]
            return wave, dev, nb

        try:
            with Prefetcher(gen(), depth=prefetch_depth, put=put,
                            tracer=tracer, registry=reg) as pf:
                for wave, payload, nb in pf:
                    last = wave.index == W - 1
                    with phase("als.wave_theta", cat="solve", tracer=tracer,
                               registry=reg, wave=wave.index,
                               iteration=it + 1, bytes=nb, bins=n_bins):
                        for lbs, x_dev in payload:
                            for k, lb in enumerate(lbs):
                                with phase("als.accumulate",
                                           cat="accumulate", tracer=tracer,
                                           registry=reg, half="theta",
                                           bin=k, rows=lb.rows,
                                           slots=lb.slots, nnz=lb.nnz,
                                           x_rows=x_dev.shape[0], n=n, f=f):
                                    A, B, c = als_mod.accumulate_bin(
                                        A, B, c, x_dev, lb, cfg)
                                reg.counter("accumulate_slots").inc(lb.slots)
                                reg.counter("accumulate_ratings").inc(lb.nnz)
                        meter.free(f"twave{wave.index}")
                        if last:
                            _solve_theta(A, B, c,
                                         jnp.asarray(layout.col_of))
                    reg.counter("waves_run").inc()
                    reg.counter("batches_loaded").inc(len(payload))
                    reg.counter("bytes_streamed").inc(nb)
                    _save(it * wpi + W + wave.index + 1,
                          acc=None if last else (A, B, c))
        finally:
            meter.free("acc")

    # ------------------------------------------------------------------
    # Mesh halves: the same waves, shard-mapped on the real (data, model)
    # mesh with theta as p shards and a host-scheduled partial reduction.
    # ------------------------------------------------------------------
    def _x_half_mesh(it: int, first_wave: int):
        theta_dev = jax.device_put(factors.theta, fixed_sh)
        meter.alloc("fixed_theta", factors.theta.nbytes // p)  # one shard
        full_rows = sched.waves[0].rows          # n_data * rows-per-batch
        scratch = (full_rows * (f * f + 2 * f) * 4) // n_data

        def gen():
            for wave in sched.waves[first_wave:]:
                yield wave, ratings.x_slice_mesh_triplet(
                    wave.row_start, wave.row_stop)

        def put(item):
            wave, (idx, val, cnt) = item
            nb = int(idx.nbytes + val.nbytes + cnt.nbytes)
            # per-device share: one batch's rows x one model column block
            meter.alloc(f"xwave{wave.index}", nb // (len(wave.batches) * p))
            reg.counter("padded_slots").inc(idx.size)
            reg.counter("nnz_streamed").inc(int(cnt.sum()))
            reg.counter("x_padded_slots").inc(idx.size)
            reg.counter("x_nnz_streamed").inc(int(cnt.sum()))
            pad = full_rows - idx.shape[0]
            if pad:      # ragged last wave: empty rows solve to x_u = 0
                idx = np.pad(idx, ((0, pad), (0, 0)))
                val = np.pad(val, ((0, pad), (0, 0)))
                cnt = np.pad(cnt, ((0, pad), (0, 0)))
            dev = (jax.device_put(idx, rows_sh),
                   jax.device_put(val, rows_sh),
                   jax.device_put(cnt, rows_sh))
            return wave, dev, nb

        try:
            with Prefetcher(gen(), depth=prefetch_depth, put=put,
                            tracer=tracer, registry=reg) as pf:
                for wave, (idx, val, cnt), nb in pf:
                    with phase("als.wave_x", cat="solve", tracer=tracer,
                               registry=reg, wave=wave.index,
                               iteration=it + 1, bytes=nb, mesh=True):
                        meter.alloc("x_scratch", scratch)
                        rows = np.asarray(
                            wave_update(theta_dev, idx, val, cnt))
                        meter.free("x_scratch")
                        # each of the n_data * p devices solves its slice
                        shards = n_data * p
                        _count_solve(wave.rows, shards * kops.solve_width(
                            full_rows // shards, f, cfg.mode))
                        factors.write_slice("x", wave.row_start,
                                            wave.row_stop, rows[:wave.rows])
                    meter.free(f"xwave{wave.index}")
                    reg.counter("waves_run").inc()
                    reg.counter("batches_loaded").inc(len(wave.batches))
                    reg.counter("bytes_streamed").inc(nb)
                    _save(it * wpi + wave.index + 1)
        finally:
            meter.free("fixed_theta")

    def _theta_half_mesh(it: int, first_wave: int, acc0=None):
        """Mesh theta half over the batch-uniform stacked bins: one
        ``wave_herm`` dispatch per bin per wave (one compiled shape per
        bin — ``make_wave_herm_fn`` is shape-polymorphic), partials
        host-scattered into the per-data-shard f64 accumulators through
        each stack's ``items`` map (float64: a host stand-in for
        device-resident partial state, exact for f32 summands, so the
        final topology reduce is order-free).  Padding rows/batches carry
        cnt = 0 and produce exact-zero partials, so scattering them
        (``np.add.at``, duplicate-safe) changes nothing; the accumulators
        are in item order, so kill/resume stays bit-exact and
        layout-agnostic."""
        acc_shard = n * (f * f + f + 1) * 4 // p
        meter.alloc("acc", acc_shard)
        if acc0 is not None:
            A_dev = np.asarray(acc0[0], np.float64).copy()
            B_dev = np.asarray(acc0[1], np.float64).copy()
            c_dev = np.asarray(acc0[2], np.float64).copy()
        else:
            A_dev = np.zeros((n_data, n, f, f), np.float64)
            B_dev = np.zeros((n_data, n, f), np.float64)
            c_dev = np.zeros((n_data, n), np.float64)

        def gen():
            for wave in sched.waves[first_wave:]:
                bins = ratings.theta_wave_stacked(
                    [b.index for b in wave.batches])
                xs = [factors.read_slice("x", b.row_start, b.row_stop)
                      for b in wave.batches]
                yield wave, bins, xs

        def put(item):
            wave, bins, xs = item
            nbatch = len(xs)
            trip_nb = sum(int(i.nbytes + v.nbytes + c.nbytes)
                          for i, v, c, _ in bins)
            x_nb = sum(x.nbytes for x in xs)
            slots = sum(i.size for i, _v, _c, _it in bins)
            nz = sum(int(c.sum()) for _i, _v, c, _it in bins)
            reg.counter("padded_slots").inc(slots)
            reg.counter("nnz_streamed").inc(nz)
            reg.counter("t_padded_slots").inc(slots)
            reg.counter("t_nnz_streamed").inc(nz)
            meter.alloc(f"twave{wave.index}",
                        trip_nb // (nbatch * p) + x_nb // nbatch)
            pad = n_data - nbatch
            x_stack = np.stack(xs)
            if pad:      # ragged last wave: empty batches contribute A = 0
                z3 = ((0, pad), (0, 0), (0, 0))
                bins = [(np.pad(i, z3), np.pad(v, z3),
                         np.pad(c, ((0, pad), (0, 0))), items)
                        for i, v, c, items in bins]
                x_stack = np.pad(x_stack, z3)
            return wave, (x_stack, bins, nbatch), trip_nb + x_nb

        try:
            with Prefetcher(gen(), depth=prefetch_depth, put=put,
                            tracer=tracer, registry=reg) as pf:
                for wave, (x_stack, bins, nbatch), nb in pf:
                    with phase("als.wave_theta", cat="solve", tracer=tracer,
                               registry=reg, wave=wave.index,
                               iteration=it + 1, bytes=nb, mesh=True,
                               bins=len(bins)):
                        for idx_b, val_b, cnt_b, items_b in bins:
                            A_w, B_w = wave_herm(x_stack, idx_b, val_b,
                                                 cnt_b)
                            A_w = np.asarray(A_w, np.float64)
                            B_w = np.asarray(B_w, np.float64)
                            for d in range(nbatch):
                                np.add.at(A_dev[d], items_b[d], A_w[d])
                                np.add.at(B_dev[d], items_b[d], B_w[d])
                                np.add.at(c_dev[d], items_b[d],
                                          cnt_b[d].astype(np.float64))
                    meter.free(f"twave{wave.index}")
                    reg.counter("waves_run").inc()
                    reg.counter("batches_loaded").inc(len(wave.batches))
                    reg.counter("bytes_streamed").inc(nb)
                    last = wave.index == W - 1
                    if last:
                        _reduce_and_solve(A_dev, B_dev, c_dev)
                    _save(it * wpi + W + wave.index + 1,
                          acc=None if last else (A_dev, B_dev, c_dev))
        finally:
            meter.free("acc")

    def _reduce_and_solve(A_dev, B_dev, c_dev):
        """Combine per-data-shard partials (paper Fig. 5b schedule), then
        each model shard solves and writes back its own theta rows."""
        traffic = topo_traffic
        with phase("als.reduce_partials", cat="reduce", tracer=tracer,
                   registry=reg, topology=topo_desc,
                   fast_bytes=traffic["fast_link_bytes"],
                   slow_bytes=traffic["slow_link_bytes"]):
            A = dreduce.topology_reduce(list(A_dev), topo, tracer=tracer)
            B = dreduce.topology_reduce(list(B_dev), topo, tracer=tracer)
            c = dreduce.topology_reduce(list(c_dev), topo, tracer=tracer)
        reg.counter("reduce_fast_bytes").inc(traffic["fast_link_bytes"])
        reg.counter("reduce_slow_bytes").inc(traffic["slow_link_bytes"])
        meter.alloc("theta_out", n * f * 4 // p)
        npp = n // p
        for k in range(p):
            lo, hi = k * npp, (k + 1) * npp
            # the reduced [n, f, f] partials, moved to the lanes solve's
            # layout
            th_k = als_mod.solve_accumulated(
                jnp.asarray(A[lo:hi].reshape(npp, f * f).T, jnp.float32),
                jnp.asarray(B[lo:hi].T, jnp.float32),
                jnp.asarray(c[lo:hi], jnp.float32), cfg)
            _count_solve(npp, kops.solve_width(npp, f, cfg.mode))
            factors.write_shard("theta", k, p, np.asarray(th_k))
        meter.free("theta_out")

    x_half, theta_half = ((_x_half, _theta_half) if mesh is None
                          else (_x_half_mesh, _theta_half_mesh))

    # ------------------------------------------------------------------
    # Plan side of the ledger: per-wave predictions summed over exactly the
    # waves this run will execute (resume-aware), before any wave streams.
    pstats = predicted_stream_stats(ratings, sched, f)
    pred = {"bytes": 0, "slots": 0, "nnz": 0, "reduces": 0,
            "x_slots": 0, "x_nnz": 0, "t_slots": 0, "t_nnz": 0}

    def _predict_iteration(r: int):
        for wi in range(r if r < W else W, W):          # solve-X half
            pred["bytes"] += pstats["x_bytes"][wi]
            pred["slots"] += pstats["x_slots"][wi]
            pred["nnz"] += pstats["x_nnz"][wi]
            pred["x_slots"] += pstats["x_slots"][wi]
            pred["x_nnz"] += pstats["x_nnz"][wi]
        for wi in range(max(0, r - W), W):              # accumulate-Theta
            pred["bytes"] += pstats["t_bytes"][wi]
            pred["slots"] += pstats["t_slots"][wi]
            pred["nnz"] += pstats["t_nnz"][wi]
            pred["t_slots"] += pstats["t_slots"][wi]
            pred["t_nnz"] += pstats["t_nnz"][wi]
        if mesh is not None:
            pred["reduces"] += 1         # one Fig. 5b reduce per theta half

    # ------------------------------------------------------------------
    history: List[dict] = []
    it0 = start_step // wpi
    with _counting_compiles(reg, tracer), \
            phase("als.stream", cat="driver", tracer=tracer, registry=reg,
                  iterations=cfg.iters, waves=W, topology=topo_desc):
        for it in range(it0, cfg.iters):
            resume_here = it == it0
            r = start_step % wpi if resume_here else 0
            _predict_iteration(r)
            ph0 = reg.phase_seconds()
            with phase("als.iteration", cat="iteration", tracer=tracer,
                       registry=reg, iteration=it + 1):
                if r < W:
                    with phase("als.solve_x_half", cat="half",
                               tracer=tracer, registry=reg,
                               iteration=it + 1):
                        x_half(it, first_wave=r)
                if r < wpi:
                    with phase("als.accumulate_theta_half", cat="half",
                               tracer=tracer, registry=reg,
                               iteration=it + 1):
                        theta_half(it, first_wave=max(0, r - W),
                                   acc0=acc_restored if resume_here
                                   else None)
            ph1 = reg.phase_seconds()
            rec = {"iteration": it + 1,
                   "waves_run": int(reg.counter("waves_run").value),
                   "peak_bytes": meter.peak_bytes,
                   "phase_seconds": {
                       cat: s - ph0.get(cat, 0.0)
                       for cat, s in ph1.items()
                       if s - ph0.get(cat, 0.0) > 0.0}}
            if train_eval is not None or test_eval is not None:
                x_dev = jnp.asarray(factors.x[:ratings.m])
                t_dev = jnp.asarray(factors.theta)
                if test_eval is not None:
                    rec["test_rmse"] = float(
                        rmse_padded(x_dev, t_dev, *test_eval))
                if train_eval is not None:
                    rec["train_rmse"] = float(
                        rmse_padded(x_dev, t_dev, *train_eval))
            history.append(rec)
            if callback is not None:
                callback(it, rec)
        if mgr is not None:
            mgr.wait()
    reg.gauge("peak_bytes").set(meter.peak_bytes)

    # ------------------------------------------------------------------
    # Close the loop: every prediction the planner/schedule/budget layer
    # made for this run, confronted with what the meters measured.
    led = Ledger(solver="als", mesh=mesh is not None, p=p,
                 n_data=n_data, waves=W, iterations=cfg.iters - it0,
                 f=f, m_pad=m_pad, n=n, mode=cfg.mode, n_bins=n_bins,
                 resumed_from_step=start_step, topology=topo_desc,
                 autotune=getattr(ratings, "tune", None),
                 phase_seconds=reg.phase_seconds())
    led.record("peak_device_bytes", sched.capacity_bytes, meter.peak_bytes,
               unit="bytes", check="le")
    led.record("modeled_peak_bytes",
               required_capacity_bytes(ratings, sched, f,
                                       prefetch_depth=prefetch_depth),
               meter.peak_bytes, unit="bytes", check="le")
    meas_slots = int(reg.counter("padded_slots").value)
    meas_nnz = int(reg.counter("nnz_streamed").value)
    led.record("bytes_streamed", pred["bytes"],
               int(reg.counter("bytes_streamed").value), unit="bytes")
    led.record("padded_slots", pred["slots"], meas_slots, unit="slots")
    led.record("nnz_streamed", pred["nnz"], meas_nnz, unit="ratings")
    led.record("fill_waste_ratio",
               pred["slots"] / pred["nnz"] if pred["nnz"] else 0.0,
               meas_slots / meas_nnz if meas_nnz else 0.0,
               unit="ratio", check="rel", rel_tol=1e-9)
    led.record("worst_fill_bound", ratings.worst_fill,
               meas_slots / meas_nnz if meas_nnz else 0.0,
               unit="ratio", check="le")
    # per-half fill attribution: each streamed orientation pays only its own
    # padding (ISSUE 9 satellite — the old worst_fill max smeared them)
    mxs = int(reg.counter("x_padded_slots").value)
    mxn = int(reg.counter("x_nnz_streamed").value)
    mts = int(reg.counter("t_padded_slots").value)
    mtn = int(reg.counter("t_nnz_streamed").value)
    led.record("fill/solve_x",
               pred["x_slots"] / pred["x_nnz"] if pred["x_nnz"] else 0.0,
               mxs / mxn if mxn else 0.0,
               unit="ratio", check="rel", rel_tol=1e-9)
    led.record("fill/accumulate_theta",
               pred["t_slots"] / pred["t_nnz"] if pred["t_nnz"] else 0.0,
               mts / mtn if mtn else 0.0,
               unit="ratio", check="rel", rel_tol=1e-9)
    for comp, fb in ratings.fill_breakdown().items():
        led.record(f"fill_bound/{comp}", ratings.worst_fill, fb,
                   unit="ratio", check="le")
    if mesh is not None:
        led.record("reduce_fast_bytes",
                   pred["reduces"] * topo_traffic["fast_link_bytes"],
                   int(reg.counter("reduce_fast_bytes").value), unit="bytes")
        led.record("reduce_slow_bytes",
                   pred["reduces"] * topo_traffic["slow_link_bytes"],
                   int(reg.counter("reduce_slow_bytes").value), unit="bytes")
    F = -(-f // cfg.f_mult) * cfg.f_mult
    led.record("vmem/fused_herm_pallas",
               BUDGETS["fused_herm_pallas"].vmem_limit,
               footprint_bytes("fused_herm_pallas",
                               tm=cfg.tm, tk=cfg.tk, F=F),
               unit="bytes", check="le", mode=cfg.mode)
    led.record("vmem/batch_solve_pallas",
               BUDGETS["batch_solve_pallas"].vmem_limit,
               footprint_bytes("batch_solve_pallas", f=f,
                               T=lanes_per_step(f, max(m_pad, n))),
               unit="bytes", check="le", mode=cfg.mode)

    return factors, history, StreamTelemetry.from_registry(
        reg, capacity_bytes=sched.capacity_bytes, topology=topo_desc,
        ledger=led.to_obj())
