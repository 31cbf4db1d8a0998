"""What the program's own spans and scopes give the benchmark: the
readers of the host slicing and retrace metrics on a synthetic window,
device time by ALS stage on a hand-made plane, and the program's spans
in a CPU profile, where the harness's clock offset places them."""
import dataclasses
import tempfile
import threading
import time

import numpy as np
import pytest
from chipbench_tiny import REPO
from chipbench import harness
from chipbench import scope_reduce as sr
from chipbench import trace_reduce as tr
from repro.obs import SpanEvent


def _span(name, dur_us, cat="", tid=1):
    return SpanEvent(name, cat, "X", 0.0, dur_us, tid, {})


def _window(spans, iterations=2):
    return harness.Window(
        iterations=iterations, seconds=4.0, phase_seconds={}, spans=spans,
        compile_s=0.0, store_build_s=0.0, flops_per_iter=1.0,
        peak_flops=None, trace=None, memory_peak_bytes=None)


def test_host_slice_reader():
    read = harness.load_reader(str(REPO), "host_slice_s_per_iter")
    w = _window([_span("prefetch.slice", 3e5, "prefetch_slice", tid=2),
                 _span("prefetch.slice", 1e5, "prefetch_slice", tid=2),
                 _span("prefetch.load", 7e5, "prefetch_load", tid=2),
                 _span("als.wave_x", 9e5, "solve")])
    assert read(w) == pytest.approx(0.2)
    assert read(_window([_span("als.wave_x", 9e5, "solve")])) is None
    assert read(_window(None)) is None


def test_traces_reader():
    read = harness.load_reader(str(REPO), "traces_per_iter")
    slice_ = _span("prefetch.slice", 1.0, "prefetch_slice", tid=2)
    w = _window([slice_] + [_span(f"jax.trace:{n}", 10.0, "compile")
                            for n in ("scan", "batch_solve", "add",
                                      "less", "scan", "batch_solve")])
    assert read(w) == 3.0
    assert read(_window([slice_])) == 0.0
    # a program without the compile listener has no slicing span either
    assert read(_window([_span("jax.trace:scan", 1.0, "compile")])) is None
    assert read(_window(None)) is None


def test_outer_scope():
    assert sr.outer_scope("jit(fused_herm)/als.herm/als.gather/jit(_take)/"
                          "and:") == "als.herm"
    assert sr.outer_scope("jit(scan)/while/body/jit(batch_solve)/als.solve/"
                          "cholesky") == "als.solve"
    assert sr.outer_scope("jit(add)/add") == sr.UNSCOPED
    assert sr.outer_scope("") == sr.UNSCOPED


def _xspace(ops, *, line_ns=1000, plane="/device:TPU:0"):
    """A serialized XSpace with one device plane whose ``XLA Ops`` line
    holds ``ops`` [(start_ns, end_ns, op name)] relative to ``line_ns``;
    the op names ride as the ``tf_op`` stat of each event's metadata, by
    string or by reference to a stat-metadata name as a TPU trace has
    them, and a host plane holds one event that must be skipped."""
    msg = sr.xplane_messages()
    space = msg["XSpace"]()
    p = space.planes.add(name=plane)
    p.stat_metadata.add(key=1, value=msg["XStatMetadata"](name="tf_op"))
    p.stat_metadata.add(key=2, value=msg["XStatMetadata"](name="flops"))
    line = p.lines.add(name=tr.OPS_LINE, timestamp_ns=line_ns)
    p.lines.add(name="XLA Modules").events.add(metadata_id=99,
                                               duration_ps=10**9)
    for i, (a, b, name) in enumerate(ops):
        md = p.event_metadata.add(key=i + 1).value
        md.name = f"%op.{i} = f32[8] fusion()"
        md.stats.add(metadata_id=2, str_value="6432")
        if i % 2:
            md.stats.add(metadata_id=1, str_value=name)
        elif name:
            key = 100 + i
            p.stat_metadata.add(key=key, value=msg["XStatMetadata"](
                name=name))
            md.stats.add(metadata_id=1, ref_value=key)
        line.events.add(metadata_id=i + 1, offset_ps=a * 1000,
                        duration_ps=(b - a) * 1000)
    host = space.planes.add(name=tr.HOST_PLANE)
    host.lines.add(name=tr.OPS_LINE).events.add(metadata_id=1,
                                                duration_ps=5000)
    return space.SerializeToString()


OPS = [(0, 60, "jit(scan)/while"),
       (5, 25, "jit(scan)/while/body/jit(batch_solve)/als.solve/cholesky"),
       (25, 30, "jit(scan)/while/body/jit(batch_solve)/als.solve/mul"),
       (62, 70, "jit(fused_herm)/als.herm/als.gather/jit(_take)/and"),
       (70, 90, "jit(fused_herm)/als.herm/dot_general"),
       (95, 110, "jit(scatter-add)/scatter-add"),
       (111, 112, "")]


def test_device_ops_read_op_names_from_event_metadata():
    ops = sr.device_ops(_xspace(OPS, line_ns=1000))
    assert list(ops) == ["/device:TPU:0"]
    assert ops["/device:TPU:0"] == [(a + 1000, b + 1000, n)
                                    for a, b, n in OPS]
    assert sr.device_ops(_xspace(OPS, plane="/device:CPU:0")) == {}


def test_scope_seconds_hand_made_plane():
    ops = sr.device_ops(_xspace(OPS, line_ns=0))
    got = sr.scope_seconds(ops, (0, 100))
    assert got == pytest.approx({"unscoped": (60 - 25 + 5) * 1e-9,
                                 "als.solve": 25e-9, "als.herm": 28e-9})
    assert sum(got.values()) == pytest.approx(
        tr.busy([(0, 60), (62, 90), (95, 100)], 0, 100) * 1e-9)
    two = {"a": ops["/device:TPU:0"], "b": []}
    assert sr.scope_seconds(two, (0, 100)) == pytest.approx(
        {k: v / 2 for k, v in got.items()})
    with pytest.raises(ValueError):
        sr.scope_seconds({}, (0, 100))


def test_scopes_of_a_recorded_cpu_trace_read_nothing():
    """A CPU trace has no device plane (its operations carry only
    ``hlo_op`` / ``hlo_module``): no stage is read from it."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: jnp.tanh(a @ a).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            f(x).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        with open(tr.find_xplane(d), "rb") as fh:
            assert sr.device_ops(fh.read()) == {}


def _tiny_streaming():
    from repro.core import als as als_mod
    from repro.core.partition import plan_for
    from repro.outofcore import RatingStore, build_schedule
    from repro.sparse import synth

    spec = synth.SynthSpec("spans", 96, 40, 1500, 8, 0.05)
    r, _, _, _ = synth.make_synthetic_ratings(spec, seed=0)
    store = RatingStore(r, q=2, n_bins=2)
    plan = plan_for(spec.m, spec.n, r.nnz, spec.f, p=1, q=2, n_data=1,
                    fill=store.worst_fill, buffers=4, hbm_bytes=1 << 22)
    sched = build_schedule(plan, spec.m, spec.n, n_data=1)
    cfg = als_mod.AlsConfig(f=spec.f, lam=spec.lam, iters=2)
    return store, sched, cfg


def test_program_spans_in_the_profile_on_the_harness_clock(tmp_path):
    """With ``annotate=jax.profiler.TraceAnnotation`` the driver's spans,
    those of the prefetch worker included, are host annotations of the
    profile; the harness's offset (taken at the ``chipbench.window``
    annotation, ``harness._reduce_trace``) puts the tracer's own copies
    within 1 ms of them."""
    import jax

    from repro.obs import MetricsRegistry, Tracer
    from repro.outofcore import run_streaming_als

    store, sched, cfg = _tiny_streaming()
    run_streaming_als(store, sched, dataclasses.replace(cfg, iters=1))
    tracer = Tracer(annotate=jax.profiler.TraceAnnotation)
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("chipbench.window"):
                t_win = time.perf_counter()
                run_streaming_als(store, sched, cfg,
                                  ckpt_dir=str(tmp_path / "ck"),
                                  tracer=tracer, registry=MetricsRegistry())
        finally:
            jax.profiler.stop_trace()
        pd = jax.profiler.ProfileData.from_file(tr.find_xplane(d))
    (win,) = tr.host_annotations(pd, "chipbench.window")
    offset = win.start - t_win * 1e9
    # the worker's last next() finds its generator exhausted: annotated,
    # but no span (one per half: two halves an iteration)
    extra = {"prefetch.slice": 2 * cfg.iters}
    main = threading.main_thread().ident
    for name in ("als.wave_x", "prefetch.slice", "prefetch.load",
                 "checkpoint.commit"):
        ann = np.array([(a.start, a.end) for a in
                        tr.host_annotations(pd, name) if a.name == name])
        own = [(e.ts * 1e3 + tracer.epoch * 1e9 + offset,
                (e.ts + e.dur) * 1e3 + tracer.epoch * 1e9 + offset)
               for e in tracer.spans() if e.name == name]
        assert len(ann) == len(own) + extra.get(name, 0) > 0, name
        for span in own:
            nearest = np.min(np.max(np.abs(ann - span), axis=1))
            assert nearest < 1e6, (name, nearest)
    worker = {e.tid for e in tracer.spans(cat="prefetch_slice")}
    assert worker and main not in worker
