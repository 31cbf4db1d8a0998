"""The observability layer's hooks into JAX: the profiler annotation hook
of ``Tracer``, host slicing spans on the prefetch worker, the compile
listener's trace counters, and the ALS stage names in the lowered HLO."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.prefetch import Prefetcher
from repro.kernels import ops as kops
from repro.obs import MetricsRegistry, NULL_TRACER, Tracer, compile_listener
from repro.obs.trace import phase


class _Annotations:
    """A stand-in for ``jax.profiler.TraceAnnotation`` that logs
    (event, name, thread) on enter and exit."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        outer = self

        class _Ann:
            def __enter__(self):
                outer.log.append(("enter", name, threading.get_ident()))

            def __exit__(self, *exc):
                outer.log.append(("exit", name, threading.get_ident()))
        return _Ann()


class TestAnnotateHook:
    def test_spans_and_phases_enter_the_annotation_on_their_thread(self):
        ann = _Annotations()
        tr = Tracer(annotate=ann)
        with tr.span("outer", cat="driver"):
            with phase("inner", cat="solve", tracer=tr):
                pass
        t = threading.Thread(target=lambda: phase(
            "worker", cat="prefetch_slice", tracer=tr).__enter__().__exit__(
                None, None, None))
        t.start()
        t.join()
        main = threading.get_ident()
        assert ann.log[:4] == [("enter", "outer", main),
                               ("enter", "inner", main),
                               ("exit", "inner", main),
                               ("exit", "outer", main)]
        assert ann.log[4:] == [("enter", "worker", t.ident),
                               ("exit", "worker", t.ident)]
        assert [e.name for e in tr.spans()] == ["inner", "outer", "worker"]

    def test_pre_timed_records_and_quiet_tracers_annotate_nothing(self):
        ann = _Annotations()
        tr = Tracer(annotate=ann)
        t = time.perf_counter()
        tr.record("jax.trace:f", "compile", t - 1e-3, t)
        with phase("p", cat="solve", tracer=NULL_TRACER):
            pass
        with phase("p", cat="solve", registry=MetricsRegistry()):
            pass
        assert ann.log == [] and len(tr.spans()) == 1

    def test_annotation_exits_when_the_body_raises(self):
        ann = _Annotations()
        tr = Tracer(annotate=ann)
        with pytest.raises(KeyError):
            with phase("p", cat="solve", tracer=tr):
                raise KeyError
        assert [e for e, _, _ in ann.log] == ["enter", "exit"]


class TestPrefetchSlice:
    def test_one_slice_span_per_item_on_the_worker(self):
        def gen():
            for i in range(4):
                time.sleep(0.005)           # the host slicing being timed
                yield np.full(3, i, np.float32)

        tr, reg = Tracer(), MetricsRegistry()
        with Prefetcher(gen(), depth=2, tracer=tr, registry=reg) as pf:
            got = [np.asarray(a) for a in pf]
        assert [int(a[0]) for a in got] == [0, 1, 2, 3]
        sl = tr.spans(cat="prefetch_slice")
        assert [e.name for e in sl] == ["prefetch.slice"] * 4
        assert len(tr.spans(cat="prefetch_load")) == 4
        (tid,) = {e.tid for e in sl}
        assert tid != threading.get_ident()
        assert tr.thread_names[tid] == "prefetch-worker"
        assert all(e.dur >= 5e3 for e in sl)       # microseconds
        assert reg.phase_seconds()["prefetch_slice"] == pytest.approx(
            sum(e.dur for e in sl) * 1e-6, rel=1e-6)
        assert reg.histogram("prefetch_slice_seconds").count == 4

    def test_generator_error_still_reaches_the_consumer(self):
        def gen():
            yield 1
            raise RuntimeError("slice failed")

        tr = Tracer()
        pf = Prefetcher(gen(), depth=2, put=lambda x: x, tracer=tr)
        assert next(pf) == 1
        with pytest.raises(RuntimeError, match="slice failed"):
            next(pf)
        pf.close()
        assert len(tr.spans(cat="prefetch_slice")) == 2

    def test_exhausting_next_records_no_phase(self):
        tr, reg = Tracer(), MetricsRegistry()
        it = iter(())
        with pytest.raises(StopIteration):
            with phase("prefetch.slice", cat="prefetch_slice", tracer=tr,
                       registry=reg):
                next(it)
        assert tr.spans() == [] and reg.phase_seconds() == {}


@pytest.fixture
def listening():
    """A compile listener on a fresh registry and tracer, for one test."""
    reg, tr = MetricsRegistry(), Tracer()
    listener = compile_listener(reg, tr)
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield reg, tr
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


def _counters(reg):
    return reg.snapshot()["counters"]


class TestCompileListener:
    def test_new_lambda_in_lax_map_retraces_every_call(self, listening):
        reg, tr = listening
        x = jnp.arange(12.0).reshape(4, 3)
        for k in range(1, 3):
            jax.lax.map(lambda r: r * 2.0, x).block_until_ready()
            c = _counters(reg)
            assert c["jax/traces/scan"] == k
            assert c["jax/traces"] >= k
        assert c["jax/trace_seconds"] > 0 and c["jax/compile_seconds"] > 0
        names = [e.name for e in tr.spans(cat="compile")]
        assert names.count("jax.trace:scan") == 2
        assert names.count("jax.lower:jit(scan)") == 2
        assert names.count("jax.compile:jit(scan)") == 2
        assert sum(n.startswith("jax.trace:") for n in names) == \
            c["jax/traces"]
        assert {e.tid for e in tr.spans()} == {threading.get_ident()}

    def test_cached_jit_counts_nothing(self, listening):
        reg, tr = listening
        f = jax.jit(lambda a: jnp.tanh(a) * 3.0)
        x = jnp.ones((5, 7))
        f(x).block_until_ready()
        first = _counters(reg)
        assert first["jax/traces"] >= 1
        spans = len(tr.spans())
        f(x).block_until_ready()
        assert _counters(reg) == first
        assert len(tr.spans()) == spans

    def test_quiet_tracer_still_counts(self):
        reg = MetricsRegistry()
        on = compile_listener(reg, NULL_TRACER)
        on("/jax/core/compile/jaxpr_trace_duration", 0.25, fun_name="f")
        on("/jax/core/compile/backend_compile_duration", 0.5,
           fun_name="jit(f)")
        on("/jax/some/other_duration", 9.0)
        assert _counters(reg) == {"jax/traces": 1, "jax/traces/f": 1,
                                  "jax/trace_seconds": 0.25,
                                  "jax/compile_seconds": 0.5}


class TestNamedScopes:
    @pytest.mark.parametrize("mode", ["ref", "kernel_interpret"])
    def test_lowered_ops_carry_the_stage_names(self, mode):
        theta = jnp.ones((12, 8), jnp.float32)
        idx = jnp.zeros((16, 8), jnp.int32)
        val = jnp.ones((16, 8), jnp.float32)
        cnt = jnp.full((16,), 8, jnp.int32)
        herm = kops.fused_herm.lower(
            theta, idx, val, cnt, 0.05, mode=mode, tm=8, tk=8,
            f_mult=8).as_text(debug_info=True)
        assert "als.herm/als.gather" in herm
        assert "als.solve" not in herm
        A = jnp.broadcast_to(jnp.eye(8, dtype=jnp.float32), (16, 8, 8))
        B = jnp.ones((16, 8), jnp.float32)
        solve = kops.batch_solve.lower(
            A, B, mode=mode).as_text(debug_info=True)
        assert "als.solve" in solve and "als.herm" not in solve
