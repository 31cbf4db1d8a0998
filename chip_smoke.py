"""Smoke run of streaming ALS on a TPU, through the normal entry points.

    python chip_smoke.py               # one chip: ref and kernel modes
    python chip_smoke.py --four-chips  # a (data=2, model=2) mesh vs one chip

The one-chip run factorizes a seeded rating matrix at the Netflix widths of
the paper's Table 5 (m = 480,189 users, n = 17,770 items, f = 100,
lambda = 0.05, item skew alpha = 0.8, rating noise 0.1) with nnz cut from
99M to 10M: the synthetic generator holds ``[nnz, f]`` factor gathers in
host RAM, about 80 GB at 99M.  The data goes ``RatingStore`` -> ``plan_for``
-> ``run_streaming_als`` with q = 2 user batches, so each half-iteration
streams two waves, and runs two ALS iterations with ``mode="ref"`` and two
with ``mode="kernel"`` (the compiled Pallas kernels) from the same initial
factors.  It fails unless

- a float64 numpy solve of 256 sampled rows of every half-iteration agrees
  with the device's rows to a relative error of 1e-3 in both modes,
- kernel-mode test RMSE is within 1% of ref mode, and test RMSE falls from
  iteration 1 to iteration 2,
- one compiled SGD tile (mb = nb = 1024, f = 100) cut from the same data
  matches ``kernels.ref.sgd_block_ref``.

``--four-chips`` runs only the mesh path and what it is compared with: one
streaming iteration on a ``(data=2, model=2)`` mesh (SU-ALS solve waves and
the staged reduction of the Theta partials) against the single-device
streaming iteration on the same data, at nnz = 2M (the mesh layout keeps a
uniform-K copy of R^T on the host).

Times printed here are smoke output, not a benchmark.  The last line of
standard output is one JSON object naming the device.  Without a TPU the
script exits non-zero at once and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# Table 5, Netflix
M, N, F, LAM = 480_189, 17_770, 100, 0.05
ALPHA, NOISE, SEED = 0.8, 0.1, 0
NNZ_PAPER, NNZ_ONE_CHIP, NNZ_FOUR_CHIPS = 99_000_000, 10_000_000, 2_000_000
N_BINS, BATCH_ROWS, PREFETCH_DEPTH = 8, 16_384, 2
Q_ONE_CHIP, Q_FOUR_CHIPS = 2, 8
N_CHECK_ROWS = 256
HOST_REF_TOL, RMSE_TOL, MESH_TOL, SGD_TOL = 1e-3, 0.01, 2e-3, 1e-4
GB = 1e9


class SmokeFailure(Exception):
    """A phase's check failed; the message says which and by how much."""


def mem_stats(device) -> dict:
    """``device.memory_stats()``; empty where the backend reports none."""
    return device.memory_stats() or {}


def check(ok: bool, what: str) -> None:
    print(("PASS " if ok else "FAIL ") + what, flush=True)
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Sums JAX's own trace/lower/compile durations (jax.monitoring)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def make_data(nnz: int):
    """Seeded Netflix-width ratings: (R rows=users, R test split)."""
    from repro.sparse import synth
    spec = synth.SynthSpec("netflix", M, N, nnz, F, LAM)
    t0 = time.perf_counter()
    # binned construction: R^T is never padded to its top item degree
    r_b, _, r_te, _ = synth.make_synthetic_ratings_binned(
        spec, N_BINS, seed=SEED, noise=NOISE, alpha=ALPHA)
    r = r_b.to_padded()
    print(f"data: m={M} n={N} f={F} lambda={LAM} alpha={ALPHA} "
          f"noise={NOISE}; {r.nnz} train + {r_te.nnz} test ratings, "
          f"user K={r.K} ({time.perf_counter() - t0:.1f}s)", flush=True)
    return r, r_te


def build_plan(r, q: int, p: int, n_data: int, n_bins: int, hbm: int):
    from repro.core.partition import plan_for, streaming_acc_bytes
    from repro.outofcore import (RatingStore, build_schedule,
                                 required_capacity_bytes)
    t0 = time.perf_counter()
    store = RatingStore(r, q=q, p=p, n_bins=n_bins)
    plan = plan_for(M, N, r.nnz, F, p=p, q=q, n_data=n_data, hbm_bytes=hbm,
                    buffers=PREFETCH_DEPTH + 2,
                    acc_bytes=streaming_acc_bytes(N, F),
                    bin_fills=store.bin_fill_pairs())
    sched = build_schedule(plan, M, N, n_data=n_data)
    print(f"store: q={q} p={p} n_bins={n_bins} fill_r={store.fill_r:.3f} "
          f"fill_rt={store.fill_rt:.3f} host {store.host_nbytes / GB:.2f} GB "
          f"({time.perf_counter() - t0:.1f}s)", flush=True)
    print(f"plan: {plan.describe()}", flush=True)
    print(f"schedule: {sched.describe()}; driver model "
          f"{required_capacity_bytes(store, sched, F) / GB:.2f} GB/device",
          flush=True)
    check(plan.fits, f"plan_for fits bytes_limit {hbm / GB:.2f} GB")
    check(len(sched.waves) >= 2, f"{len(sched.waves)} waves per half (>= 2)")
    return store, sched


# ---------------------------------------------------------------------------
# placement: reckoned device bytes, then the compiler's own count
# ---------------------------------------------------------------------------

def _largest_bin(binned_list):
    """(rows, K) of the bin whose gathered [rows, K, f] block is largest."""
    return max(((b.m, b.K) for bl in binned_list for b in bl.bins),
               key=lambda s: s[0] * s[1])


def placement(store, sched, hbm: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import als as als_mod

    F_pad = -(-F // 128) * 128
    x_bins = [store.x_slice_binned(w.row_start, w.row_stop)
              for w in sched.waves]
    xr, xk = _largest_bin(x_bins)
    tr, tk = _largest_bin(store.rt_binned)
    print(f"reckoned: factors {(M + N) * F * 4 / GB:.3f} GB; Hermitian block "
          f"[{BATCH_ROWS}, {F_pad}, {F_pad}] {BATCH_ROWS * F_pad ** 2 * 4 / GB:.3f}"
          f" GB; theta accumulator [{N}, {F_pad}, {F_pad}] "
          f"{N * F_pad ** 2 * 4 / GB:.3f} GB; largest R^T bin gather "
          f"[{tr}, {tk}, {F_pad}] {tr * tk * F_pad * 4 / GB:.3f} GB",
          flush=True)
    S = jax.ShapeDtypeStruct
    i32, f32 = jnp.int32, jnp.float32
    for mode in ("ref", "kernel"):
        cfg = als_mod.AlsConfig(f=F, lam=LAM, mode=mode,
                                batch_rows=BATCH_ROWS)
        progs = {
            f"solve-X bin [{xr}, {xk}]": (
                lambda th, i, v, c, cfg=cfg: als_mod.update_rows(
                    th, i, v, c, cfg),
                (S((N, F), f32), S((xr, xk), i32), S((xr, xk), f32),
                 S((xr,), i32))),
            f"R^T bin [{tr}, {tk}]": (
                lambda x, i, v, c, cfg=cfg: als_mod.partial_herm(
                    x, i, v, c, cfg),
                (S((sched.m_pad // store.q, F), f32), S((tr, tk), i32),
                 S((tr, tk), f32), S((tr,), i32))),
            f"theta solve [{N}]": (
                lambda a, b, c, cfg=cfg: als_mod.solve_accumulated(
                    a, b, c, cfg),
                (S((N, F, F), f32), S((N, F), f32), S((N,), f32))),
        }
        for name, (fn, shapes) in progs.items():
            ma = jax.jit(fn).lower(*shapes).compile().memory_analysis()
            total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                     + ma.temp_size_in_bytes)
            print(f"memory_analysis {mode:6s} {name}: args "
                  f"{ma.argument_size_in_bytes / GB:.3f} + out "
                  f"{ma.output_size_in_bytes / GB:.3f} + temp "
                  f"{ma.temp_size_in_bytes / GB:.3f} = {total / GB:.3f} GB",
                  flush=True)
            check(total < hbm, f"{mode} {name} fits {hbm / GB:.2f} GB")


# ---------------------------------------------------------------------------
# float64 host reference
# ---------------------------------------------------------------------------

def _sample_rows(cnt: np.ndarray) -> np.ndarray:
    """N_CHECK_ROWS rows spread over the whole degree range (max included)."""
    order = np.argsort(cnt, kind="stable")
    return order[np.linspace(0, len(order) - 1, N_CHECK_ROWS).astype(int)]


def host_solve(fixed: np.ndarray, cols, vals) -> np.ndarray:
    """x = (F^T F + lambda n I)^-1 F^T r in float64 (x = 0 for empty rows)."""
    out = np.zeros((len(cols), F))
    for i, (c, v) in enumerate(zip(cols, vals)):
        if len(c) == 0:
            continue
        g = fixed[c].astype(np.float64)
        a = g.T @ g + LAM * len(c) * np.eye(F)
        out[i] = np.linalg.solve(a, g.T @ v.astype(np.float64))
    return out


class HostReference:
    """Both orientations of R as host CSR, for 256-row float64 solves."""

    def __init__(self, r):
        from repro.sparse.padded import csr_from_coo
        self.u_idx, self.u_val, self.u_cnt = r.idx, r.val, r.cnt[:M]
        items, users, vals = r.transpose_coo()
        self.t_ptr, self.t_users, self.t_vals = csr_from_coo(
            items, users, vals, N)
        self.t_cnt = np.diff(self.t_ptr)
        self.users = _sample_rows(self.u_cnt)
        self.items = _sample_rows(self.t_cnt)

    def rel_error(self, side: str, fixed, solved) -> float:
        if side == "x":
            rows = self.users
            cols = [self.u_idx[u, :self.u_cnt[u]] for u in rows]
            vals = [self.u_val[u, :self.u_cnt[u]] for u in rows]
        else:
            rows = self.items
            sl = [slice(self.t_ptr[v], self.t_ptr[v + 1]) for v in rows]
            cols = [self.t_users[s] for s in sl]
            vals = [self.t_vals[s] for s in sl]
        ref = host_solve(fixed, cols, vals)
        dev = solved[rows].astype(np.float64)
        return float(np.linalg.norm(dev - ref) / np.linalg.norm(ref))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def run_mode(mode, store, sched, r_te, init, host_ref, clock, ckpt_root):
    """Two streaming iterations in one mode; checks every half-iteration
    against the float64 host reference.  Returns per-iteration test RMSE."""
    import jax

    from repro.core import als as als_mod
    from repro.outofcore import FactorStore, run_streaming_als

    cfg = als_mod.AlsConfig(f=F, lam=LAM, iters=2, mode=mode,
                            batch_rows=BATCH_ROWS)
    factors = FactorStore.from_arrays(*init)
    snaps = [(factors.x[:M].copy(), factors.theta.copy())]
    stamps = [time.perf_counter()]
    compile0 = clock.seconds

    def on_iteration(it, rec):
        stamps.append(time.perf_counter())
        snaps.append((factors.x[:M].copy(), factors.theta.copy()))

    ckpt = os.path.join(ckpt_root, mode)
    _, hist, tel = run_streaming_als(
        store, sched, cfg, factors=factors, ckpt_dir=ckpt, keep=1,
        prefetch_depth=PREFETCH_DEPTH, test_eval=als_mod.ell_triplet(r_te),
        callback=on_iteration)
    shutil.rmtree(ckpt, ignore_errors=True)
    peak = mem_stats(jax.devices()[0]).get("peak_bytes_in_use", 0)
    rmse = [h["test_rmse"] for h in hist]
    for it in range(2):
        print(f"{mode:6s} iteration {it + 1}: wall "
              f"{stamps[it + 1] - stamps[it]:.3f}s (device synced by the "
              f"host write-back of every wave); test_rmse {rmse[it]:.6f}",
              flush=True)
    print(f"{mode:6s} compile {clock.seconds - compile0:.3f}s; "
          f"peak_bytes_in_use {peak / GB:.3f} GB; "
          f"{tel.waves_run} waves, {tel.bytes_streamed / GB:.3f} GB streamed",
          flush=True)
    for it in range(2):
        (x0, t0), (x1, t1) = snaps[it], snaps[it + 1]
        ex = host_ref.rel_error("x", t0, x1)
        et = host_ref.rel_error("theta", x1, t1)
        check(ex <= HOST_REF_TOL, f"{mode} iteration {it + 1} solve-X vs "
              f"float64 host reference ({N_CHECK_ROWS} rows): rel err "
              f"{ex:.3e} <= {HOST_REF_TOL:g}")
        check(et <= HOST_REF_TOL, f"{mode} iteration {it + 1} solve-Theta "
              f"vs float64 host reference ({N_CHECK_ROWS} rows): rel err "
              f"{et:.3e} <= {HOST_REF_TOL:g}")
    check(rmse[1] < rmse[0], f"{mode} test RMSE falls: {rmse[0]:.6f} -> "
          f"{rmse[1]:.6f}")
    return rmse


def sgd_tile(r) -> None:
    """One compiled SGD tile cut from the same data vs the jnp oracle."""
    import jax.numpy as jnp

    from repro.kernels import ref as kref
    from repro.kernels.sgd_update import sgd_block_update

    mb = nb = 1024
    idx, val, cnt = r.idx[:mb], r.val[:mb], r.cnt[:mb]
    keep = (np.arange(r.K)[None, :] < cnt[:, None]) & (idx < nb)
    k = max(int(keep.sum(1).max()), 1)
    t_idx = np.zeros((mb, k), np.int32)
    t_val = np.zeros((mb, k), np.float32)
    t_cnt = keep.sum(1).astype(np.int32)
    for u in range(mb):
        t_idx[u, :t_cnt[u]] = idx[u][keep[u]]
        t_val[u, :t_cnt[u]] = val[u][keep[u]]
    rng = np.random.default_rng(SEED)
    x = jnp.asarray(rng.uniform(0, 0.3, (mb, F)), jnp.float32)
    th = jnp.asarray(rng.uniform(0, 0.3, (nb, F)), jnp.float32)
    args = (x, th, jnp.asarray(t_idx), jnp.asarray(t_val),
            jnp.asarray(t_cnt), jnp.float32(0.05))
    xk, tk = sgd_block_update(*args, LAM, mode="kernel")
    xr, tr = kref.sgd_block_ref(*args, LAM)
    err = max(float(jnp.linalg.norm(xk - xr) / jnp.linalg.norm(xr)),
              float(jnp.linalg.norm(tk - tr) / jnp.linalg.norm(tr)))
    check(err <= SGD_TOL, f"SGD tile mb={mb} nb={nb} f={F} K={k} "
          f"({int(t_cnt.sum())} ratings) kernel vs sgd_block_ref: rel err "
          f"{err:.3e} <= {SGD_TOL:g}")


def one_chip(out_dir: str, hbm: int) -> None:
    from repro import compat
    from repro.core import als as als_mod

    check(not compat.interpret_default(), "Pallas kernels compile (no "
          "interpreter)")
    clock = CompileClock()
    print(f"nnz cut: {NNZ_PAPER} (Table 5) -> {NNZ_ONE_CHIP} (host RAM of "
          f"the generator); every width kept", flush=True)
    r, r_te = make_data(NNZ_ONE_CHIP)
    store, sched = build_plan(r, Q_ONE_CHIP, 1, 1, N_BINS, hbm)
    placement(store, sched, hbm)
    host_ref = HostReference(store.r)
    st = als_mod.als_init(M, N, als_mod.AlsConfig(f=F, lam=LAM))
    x0 = np.zeros((sched.m_pad, F), np.float32)
    x0[:M] = np.asarray(st.x)
    init = (x0, np.asarray(st.theta))
    rmse = {mode: run_mode(mode, store, sched, r_te, init, host_ref, clock,
                           os.path.join(out_dir, "ckpt"))
            for mode in ("ref", "kernel")}
    for it in range(2):
        rel = abs(rmse["kernel"][it] - rmse["ref"][it]) / rmse["ref"][it]
        check(rel <= RMSE_TOL, f"iteration {it + 1} kernel test RMSE "
              f"{rmse['kernel'][it]:.6f} vs ref {rmse['ref'][it]:.6f}: "
              f"{rel:.3e} <= {RMSE_TOL:g}")
    sgd_tile(store.r)


def four_chips(out_dir: str, hbm: int) -> None:
    import jax

    from repro.core import als as als_mod
    from repro.launch.mesh import make_mesh
    from repro.outofcore import run_streaming_als

    devices = jax.devices()
    check(len(devices) == 4, f"{len(devices)} devices visible (need 4)")
    print(f"nnz cut: {NNZ_PAPER} (Table 5) -> {NNZ_FOUR_CHIPS} (host RAM of "
          f"the mesh layout's uniform R^T); every width kept", flush=True)
    r, r_te = make_data(NNZ_FOUR_CHIPS)
    cfg = als_mod.AlsConfig(f=F, lam=LAM, iters=1, mode="ref",
                            batch_rows=BATCH_ROWS)
    test = als_mod.ell_triplet(r_te)

    mesh = make_mesh((2, 2), ("data", "model"))
    store, sched = build_plan(r, Q_FOUR_CHIPS, 2, 2, N_BINS, hbm)
    t0 = time.perf_counter()
    fm, hm, _ = run_streaming_als(store, sched, cfg, mesh=mesh,
                                  prefetch_depth=PREFETCH_DEPTH,
                                  test_eval=test)
    print(f"mesh (data=2, model=2): {time.perf_counter() - t0:.3f}s; "
          f"test_rmse {hm[0]['test_rmse']:.6f}", flush=True)
    stats = [mem_stats(d) for d in devices]
    for d, s in zip(devices, stats):
        print(f"device {d.id}: bytes_in_use "
              f"{s.get('bytes_in_use', 0) / GB:.3f} GB; peak_bytes_in_use "
              f"{s.get('peak_bytes_in_use', 0) / GB:.3f} GB", flush=True)
    peaks = [s.get("peak_bytes_in_use", 0) for s in stats]
    check(max(peaks) > 0 and min(peaks) >= 0.1 * max(peaks),
          "every device held a share of the "
          f"mesh run (min peak {min(peaks) / GB:.3f} GB >= 10% of max "
          f"{max(peaks) / GB:.3f} GB)")

    store1, sched1 = build_plan(r, Q_FOUR_CHIPS, 1, 1, N_BINS, hbm)
    t0 = time.perf_counter()
    f1, h1, _ = run_streaming_als(store1, sched1, cfg,
                                  prefetch_depth=PREFETCH_DEPTH,
                                  test_eval=test)
    print(f"single device: {time.perf_counter() - t0:.3f}s; "
          f"test_rmse {h1[0]['test_rmse']:.6f}", flush=True)
    dx = float(np.abs(fm.x[:M] - f1.x[:M]).max())
    dt = float(np.abs(fm.theta - f1.theta).max())
    check(max(dx, dt) <= MESH_TOL, f"mesh vs single-device factors: max "
          f"|dx| {dx:.3e}, max |dtheta| {dt:.3e} <= {MESH_TOL:g}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 mesh path and its one-device "
                         "comparison")
    args = ap.parse_args(argv)

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX backend is {backend!r}, not 'tpu'; this "
              f"script runs only on the chip", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    out_dir = os.path.join(ROOT, ".ckpt", "chip_smoke")
    shutil.rmtree(out_dir, ignore_errors=True)
    hbm = mem_stats(jax.devices()[0])["bytes_limit"]
    t0 = time.perf_counter()
    try:
        (four_chips if args.four_chips else one_chip)(out_dir, hbm)
    except SmokeFailure:
        return 1
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    print(f"total {time.perf_counter() - t0:.1f}s", flush=True)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
