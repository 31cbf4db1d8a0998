"""The benchmark harness: find a cell by name, drive the program's normal
streaming-ALS entry through set-up, a warm-up iteration and a timed window,
check the window's last iteration against the float64 reference, and read
the cell's metrics.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by name:

- ``BENCHMARK.json`` (checkout root): cells, metrics, configuration files;
- ``chipbench/traffic/<traffic>.json``: the mix's generator parameters;
- ``chipbench/metrics/<metric>.py``: a ``read(window)`` function returning
  the metric from a :class:`Window`, or None where it finds nothing.

The program sees only what its own layout code builds from the generated
COO: ``repro.sparse.padded`` CSR/ELL -> ``outofcore.RatingStore`` ->
``core.partition`` plan -> ``outofcore.build_schedule`` ->
``outofcore.run_streaming_als``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Callable, Optional

import numpy as np

from chipbench import flops, gen, reference

#: the checkout root: this file is ``<root>/chipbench/harness.py``
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: JAX's own trace, lower and compile (or compile-cache load) durations
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

#: per-run checkpoint directory of a checkpointing mix, inside the checkout
CKPT_DIR = ".bench_ckpt"


class BenchError(Exception):
    """A cell, file or device the benchmark cannot run with."""


# ---------------------------------------------------------------------------
# finding cells, configurations, mixes and metric readers by name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise BenchError(f"missing {path}") from e


def _applies(metric: dict, cell: str, reported: set) -> bool:
    """A metric's ``workloads`` names its cells; without it an end-to-end
    metric is every cell's, and a per-layer metric is that of every cell
    that reports the end-to-end metric it ``moves``."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def load_cell(root: str, workload: str) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _read_json(os.path.join(root, "chipbench", "traffic",
                                      w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload, set())]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if _applies(m, workload, reported)]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer)


def load_reader(root: str, metric: str) -> Callable:
    """``read`` of ``<root>/chipbench/metrics/<metric>.py``."""
    path = os.path.join(root, "chipbench", "metrics", metric + ".py")
    if not os.path.exists(path):
        raise BenchError(f"no reader {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(root: str, device_kind: str) -> dict:
    table = _read_json(os.path.join(root, "chipbench", "peaks.json"))
    if device_kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {device_kind!r}")
    return table["devices"][device_kind]


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def program_on_path(root: str = ROOT) -> None:
    """Make the program (``<root>/src``) importable."""
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def require_devices(chips: int):
    """The TPU devices, or None (with the reason on stderr) when JAX finds
    no TPU or fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"chipbench: needs {chips} TPU chip(s), JAX found "
              f"{len(devs)} {devs[0].platform!r} device(s)", file=sys.stderr)
        return None
    return devs


def enable_program_cache() -> str:
    """The program's persistent compilation cache, at its fixed path."""
    from repro.launch.cache import enable_compile_cache
    return enable_compile_cache()


def _memory(dev) -> dict:
    return dev.memory_stats() or {}


class CompileClock:
    """Sums JAX's compile-path durations (``jax.monitoring``)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self._on)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Window:
    """What the metric readers see of one run (times in seconds)."""

    iterations: int
    seconds: float              # window length: whole iterations only
    phase_seconds: dict         # the driver's registry, window only
    spans: Optional[list]       # obs Tracer spans (traced runs only)
    compile_s: float            # JAX compile-path time inside the window
    store_build_s: float        # program layout + plan, host clock
    flops_per_iter: float
    peak_flops: Optional[float]
    trace: Optional[object]     # trace_reduce.Summary (traced runs only)
    memory_peak_bytes: Optional[int]


@dataclasses.dataclass
class RunOutput:
    correct: bool
    attempted: int
    failed: int
    metrics: dict
    device: dict
    compared: dict
    breakdown: Optional[dict] = None
    readings: Optional[dict] = None     # every candidate number's worst
    control: Optional[dict] = None


def _log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


class _WindowClosed(Exception):
    """Raised from the driver's iteration callback to close the window."""


def _layout(config: dict, problem, hbm: int):
    """The program's own host layout and plan for the generated COO."""
    from repro.core.partition import (plan_for, plan_partitions,
                                      streaming_acc_bytes)
    from repro.outofcore import RatingStore, build_schedule
    from repro.sparse.padded import csr_from_coo, pad_csr_fast

    m, n, f = problem.m, problem.n, problem.f
    tr = problem.train
    r = pad_csr_fast(*csr_from_coo(tr.rows, tr.cols, tr.vals, m), n)
    q = plan_partitions(m, n, r.nnz, f, hbm_bytes=hbm, n_model=1,
                        n_data=1).q
    store = RatingStore(r, q=q, n_bins=config["n_bins"])
    plan = plan_for(m, n, r.nnz, f, p=1, q=q, n_data=1, hbm_bytes=hbm,
                    buffers=config["prefetch_depth"] + 2,
                    acc_bytes=streaming_acc_bytes(n, f),
                    bin_fills=store.bin_fill_pairs())
    return store, build_schedule(plan, m, n, n_data=1)


def _join_commits(before: set) -> None:
    """Wait until the checkpoint commits that the window started are on
    disk: the driver's own ``wait`` at the end of a run is skipped when the
    iteration callback closes the window."""
    for t in threading.enumerate():
        if t not in before and t.name.endswith("(commit)"):
            t.join()


def _checkpoint_readback(ckpt: str, step: int, factors) -> dict:
    """The commit the program's restart path would read, against the
    factors the window left: steps it lags behind the last wave, and the
    largest difference of a value (both exact, limit 0)."""
    from repro.checkpoint.store import latest_step, restore_checkpoint

    got = latest_step(ckpt) if os.path.isdir(ckpt) else None
    if got is None:
        return {"ckpt_lag_steps": float(step), "ckpt_max_diff": np.inf}
    with open(os.path.join(ckpt, f"step_{got:08d}", "meta.json")) as f:
        meta = json.load(f)
    like = {k: np.zeros((), d) for k, d in zip(meta["names"], meta["dtypes"])}
    tree = restore_checkpoint(ckpt, like, got)
    diff = max(float(np.max(np.abs(tree[k] - live), initial=0.0))
               if tree[k].shape == live.shape else np.inf
               for k, live in (("x", factors.x), ("theta", factors.theta)))
    return {"ckpt_lag_steps": float(step - got), "ckpt_max_diff": diff}


def _judge(errs: dict, limits: dict) -> tuple[dict, int]:
    """Each compared number's worst reading beside its limit, and how many
    readings pass their limit."""
    compared = {k: {"value": float(np.max(errs[k])), "limit": v}
                for k, v in limits.items()}
    failed = sum(int(np.sum(~(np.asarray(errs[k]) <= v)))
                 for k, v in limits.items())
    return compared, failed


def _errors(solve_x, solve_theta, systems) -> dict:
    """Forward and backward errors of both sides' sampled rows."""
    (ax, bx, x_ref), (at, bt, t_ref) = systems
    return {"x_rel_err": reference.row_errors(solve_x, x_ref),
            "theta_rel_err": reference.row_errors(solve_theta, t_ref),
            "x_backward_err": reference.backward_errors(solve_x, ax, bx),
            "theta_backward_err": reference.backward_errors(solve_theta,
                                                            at, bt)}


def _check(config: dict, problem, seed: int, theta_prev, x, theta,
           control: bool, extra: Optional[dict] = None):
    """Compare the window's last iteration with the float64 reference on
    rows sampled from ``seed``, plus the ``extra`` exact readings; with
    ``control`` the control is judged by the same limits in the program's
    place.  Returns (compared, failed, attempted, every reading's worst,
    control)."""
    from chipbench.control import solve_rows_low

    chk, lam = config["check"], config["lam"]
    tr = problem.train
    users = reference.Csr.from_coo(tr.rows, tr.cols, tr.vals, problem.m)
    items = reference.Csr.from_coo(tr.cols, tr.rows, tr.vals, problem.n)
    rng = np.random.default_rng([seed, 2])
    u = reference.sample_rows(users.degrees, chk["rows_per_side"], rng)
    v = reference.sample_rows(items.degrees, chk["rows_per_side"], rng)
    systems = []
    for fixed, csr, rows in ((theta_prev, users, u), (x, items, v)):
        a, b = reference.row_systems(fixed, csr, rows, lam)
        systems.append((a, b, np.linalg.solve(a, b[..., None])[..., 0]))
    errs = _errors(x[u], theta[v], systems)
    extra = extra or {}
    limits = dict(chk["limits"], **{k: 0.0 for k in extra})
    compared, failed = _judge({**errs, **extra}, limits)
    readings = {k: float(np.max(e)) for k, e in {**errs, **extra}.items()}
    ctrl = None
    if control:
        c_errs = _errors(solve_rows_low(theta_prev, users, u, lam),
                         solve_rows_low(x, items, v, lam), systems)
        c_cmp, c_failed = _judge(c_errs, {k: limits[k] for k in limits
                                          if k in c_errs})
        ctrl = {"correct": c_failed == 0, "failed": c_failed,
                "compared": c_cmp,
                "readings": {k: float(np.max(e)) for k, e in c_errs.items()}}
    return compared, failed, u.size + v.size + len(extra), readings, ctrl


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             peaks: Optional[dict], t_start: Optional[float] = None,
             control: bool = False, root: str = ROOT) -> RunOutput:
    """Set up, warm up, run the window, check, read the metrics.

    ``peaks`` is the device's row of ``peaks.json`` (None off the chip:
    metrics that need a peak then read nothing).
    """
    import jax

    from repro.core.als import AlsConfig
    from repro.obs import MetricsRegistry, Tracer
    from repro.outofcore import FactorStore, run_streaming_als

    t_start = time.perf_counter() if t_start is None else t_start
    cfg, tr = cell.config, cell.traffic
    dev = jax.devices()[0]
    hbm = _memory(dev).get("bytes_limit", 16 << 30)

    t0 = time.perf_counter()
    problem = gen.seeded_ratings(cfg, tr, seed)
    _log(f"generated {problem.train.nnz} ratings in "
         f"{time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    store, sched = _layout(cfg, problem, hbm)
    store_build_s = time.perf_counter() - t0
    _log(f"layout q={store.q} bins={store.n_bins} waves={len(sched.waves)} "
         f"in {store_build_s:.3f} s")

    m, f = problem.m, problem.f
    x0 = np.zeros((sched.m_pad, f), np.float32)
    x0[:m] = problem.x0
    factors = FactorStore.from_arrays(x0, problem.theta0)
    als = AlsConfig(f=f, lam=cfg["lam"], batch_rows=cfg["batch_rows"])
    depth = cfg["prefetch_depth"]
    # warm-up: one whole iteration compiles every wave and bin program
    t0 = time.perf_counter()
    run_streaming_als(store, sched, dataclasses.replace(als, iters=1),
                      factors=factors, prefetch_depth=depth)
    _log(f"warm-up iteration in {time.perf_counter() - t0:.3f} s")

    ckpt = None
    if tr["checkpoint"]:
        ckpt = os.path.join(root, CKPT_DIR, cell.name)
        shutil.rmtree(ckpt, ignore_errors=True)
    theta_prev = factors.theta.copy()
    reg = MetricsRegistry()
    tracer = Tracer() if trace else None
    clock = CompileClock()
    stamps = []
    before = set(threading.enumerate())
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace \
        else None

    def on_iteration(it, rec):
        now = time.perf_counter()
        stamps.append(now)
        if now - t_win >= seconds:
            raise _WindowClosed
        np.copyto(theta_prev, factors.theta)

    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    annotation = jax.profiler.TraceAnnotation("chipbench.window")
    annotation.__enter__()
    t_win = time.perf_counter()
    clock.seconds = 0.0
    try:
        run_streaming_als(store, sched,
                          dataclasses.replace(als, iters=1 << 30),
                          factors=factors, ckpt_dir=ckpt,
                          keep=tr.get("keep", 1), prefetch_depth=depth,
                          registry=reg, tracer=tracer,
                          callback=on_iteration)
    except _WindowClosed:
        pass
    finally:
        _join_commits(before)
        t_end = time.perf_counter()
        annotation.__exit__(None, None, None)
        clock.close()
    n_it = len(stamps)
    summary, extra = None, None
    try:
        if trace:
            jax.profiler.stop_trace()
            summary = _reduce_trace(trace_dir, tracer, t_win)
        if ckpt:
            extra = _checkpoint_readback(
                ckpt, n_it * sched.waves_per_iteration, factors)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
        if ckpt:
            shutil.rmtree(os.path.join(root, CKPT_DIR), ignore_errors=True)

    window_s = t_end - t_win
    setup_s = t_win - t_start
    _log(f"window: {n_it} iteration(s) in {window_s:.3f} s after "
         f"{setup_s:.3f} s of set-up")
    peak_bytes = _memory(dev).get("peak_bytes_in_use")
    x = factors.x[:m]
    theta = factors.theta
    del store, sched
    gc.collect()
    t0 = time.perf_counter()
    compared, failed, attempted, readings, ctrl = _check(
        cfg, problem, seed, theta_prev, x, theta, control, extra)
    _log(f"checked {attempted} rows in {time.perf_counter() - t0:.3f} s")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    breakdown = None
    if trace:
        win = Window(
            iterations=n_it, seconds=window_s,
            phase_seconds=reg.phase_seconds(), spans=tracer.spans(),
            compile_s=clock.seconds, store_build_s=store_build_s,
            flops_per_iter=flops.als_iteration_flops(
                m, problem.n, problem.train.nnz, f),
            peak_flops=peaks["bf16_flops"] if peaks else None,
            trace=summary, memory_peak_bytes=peak_bytes)
        metrics = {}
        for entry in cell.per_layer:
            value = load_reader(root, entry["name"])(win)
            if value is not None:
                metrics[entry["name"]] = {"value": float(value),
                                          "unit": entry["unit"]}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = {"device_ops": summary.top_ops,
                     "idle_gaps": summary.idle_gaps}
    else:
        e2e = {"als_iter_s": window_s / n_it, "setup_s": setup_s}
        metrics = {}
        for entry in cell.end_to_end:
            if entry["name"] not in e2e:
                raise BenchError(f"the harness does not measure "
                                 f"{entry['name']!r}")
            metrics[entry["name"]] = {"value": e2e[entry["name"]],
                                      "unit": entry["unit"]}
    return RunOutput(correct=failed == 0, attempted=attempted,
                     failed=failed, metrics=metrics, device=device,
                     compared=compared, breakdown=breakdown,
                     readings=readings, control=ctrl)


def _reduce_trace(trace_dir: str, tracer, t_win: float):
    """Summary of the traced window, gaps labelled by the driver's spans
    (moved onto the trace's clock through the window annotation)."""
    import jax

    from chipbench import trace_reduce as tr

    pd = jax.profiler.ProfileData.from_file(tr.find_xplane(trace_dir))
    (win,) = tr.host_annotations(pd, "chipbench.window")
    offset = win.start - t_win * 1e9
    spans = [tr.Span(e.ts * 1e3 + tracer.epoch * 1e9 + offset,
                     (e.ts + e.dur) * 1e3 + tracer.epoch * 1e9 + offset,
                     e.name)
             for e in tracer.spans()
             if e.tid == threading.main_thread().ident]
    if jax.devices()[0].platform == "cpu":
        return tr.summarize(pd, (win.start, win.end), spans,
                            plane_prefix=tr.HOST_PLANE,
                            ops_of=tr.cpu_op_names)
    return tr.summarize(pd, (win.start, win.end), spans)


def result_line(out: RunOutput) -> str:
    """The last line of standard output; ``compared`` comes last."""
    obj = {"correct": out.correct, "attempted": out.attempted,
           "failed": out.failed, "metrics": out.metrics,
           "device": out.device}
    if out.breakdown is not None:
        obj["breakdown"] = out.breakdown
    obj["compared"] = out.compared
    return json.dumps(obj)
