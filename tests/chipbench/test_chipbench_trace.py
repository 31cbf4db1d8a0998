"""Trace reduction: interval arithmetic on hand-made events, and a small
trace recorded here on the CPU read back through ``jax.profiler``."""
import dataclasses
import tempfile
import time

import numpy as np
import pytest

import chipbench_tiny  # noqa: F401  (puts the checkout root on sys.path)
from chipbench import trace_reduce as tr


def test_merge_busy_gaps():
    ivs = [(5, 7), (0, 2), (1, 3), (6, 9), (12, 13)]
    assert tr.merge(ivs) == [(0, 3), (5, 9), (12, 13)]
    assert tr.busy(ivs, 0, 20) == 3 + 4 + 1
    assert tr.busy(ivs, 2, 6) == 1 + 1
    assert tr.gaps(ivs, 0, 20) == [(3, 5), (9, 12), (13, 20)]
    assert tr.gaps(ivs, 1, 8) == [(3, 5)]
    assert tr.gaps([], 0, 4) == [(0, 4)]
    assert tr.busy([(0, 10)], 2, 4) == 2 and tr.gaps([(0, 10)], 2, 4) == []


def test_label_is_innermost_span():
    spans = [tr.Span(0, 100, "iteration"), tr.Span(10, 40, "wave"),
             tr.Span(20, 30, "checkpoint")]
    assert tr.label_at(25, spans, "none") == "checkpoint"
    assert tr.label_at(35, spans, "none") == "wave"
    assert tr.label_at(60, spans, "none") == "iteration"
    assert tr.label_at(200, spans, "none") == "none"


@dataclasses.dataclass
class _Ev:
    name: str
    start_ns: float
    end_ns: float

    @property
    def duration_ns(self):
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class _Line:
    name: str
    events: list


@dataclasses.dataclass
class _Plane:
    name: str
    lines: list


@dataclasses.dataclass
class _Profile:
    planes: list


WHILE = ("%while = (s32[]{:T(128)}, f32[9,16384,100]{1,2,0:T(8,128)}) "
         "while((s32[]{:T(128)}, f32[9,16384,100]{1,2,0:T(8,128)}) "
         "%tuple.15), condition=%wide.region_2.9, body=%wide.region_0.8")
CHOLESKY = ('%custom-call.19 = f32[16384,100,100]{2,1,0:T(8,128)} custom-call'
            '(f32[16384,100,100]{2,1,0:T(8,128)} %fusion.32), '
            'custom_call_target="Cholesky", operand_layout_constraints='
            '{f32[16384,100,100]{2,1,0}}')
FUSION = ("%fusion.32 = f32[16384,100,100]{2,1,0:T(8,128)} fusion(f32[16384,"
          "100,100]{1,2,0:T(8,128)} %copy.29), kind=kLoop, calls=%f.3")


def test_op_kind_of_hlo_text():
    assert tr.op_kind(WHILE) == "while"
    assert tr.op_kind(CHOLESKY) == "custom-call:Cholesky"
    assert tr.op_kind(FUSION) == "fusion"
    assert tr.op_kind("dot_general.8") == "dot_general.8"


def test_self_times_subtract_nested_ops():
    ops = [(0, 10, "loop"), (2, 4, "body"), (5, 8, "body"), (12, 13, "x")]
    assert tr.self_times(ops) == {"loop": 5, "body": 5, "x": 1}


def test_summarize_hand_made_device_plane():
    dev = _Plane("/device:TPU:0", [
        _Line("XLA Modules", [_Ev("jit_step(1)", 0, 50),
                              _Ev("jit_solve(2)", 60, 100)]),
        _Line("XLA Ops", [_Ev(WHILE, 0, 30), _Ev(CHOLESKY, 5, 20),
                          _Ev(FUSION, 40, 48), _Ev("cholesky", 60, 90)]),
    ])
    host = _Plane("/host:CPU", [_Line("python", [
        _Ev("chipbench.window", 0, 100)])])
    pd = _Profile([dev, host])
    (win,) = tr.host_annotations(pd, "chipbench.window")
    spans = [tr.Span(25, 45, "als.wave_x"), tr.Span(85, 100, "ckpt")]
    s = tr.summarize(pd, (win.start, win.end), spans)
    assert s.n_devices == 1
    assert s.busy_s == pytest.approx(68e-9)
    assert s.window_s == pytest.approx(100e-9)
    assert s.idle_share == pytest.approx(0.32)
    assert dict((k, v) for k, v in s.top_ops) == pytest.approx({
        "jit_solve/cholesky": 30e-9, "jit_step/while": 15e-9,
        "jit_step/custom-call:Cholesky": 15e-9, "jit_step/fusion": 8e-9})
    assert s.top_ops[0][0] == "jit_solve/cholesky"
    assert dict((k, v) for k, v in s.idle_gaps) == pytest.approx(
        {"als.wave_x": 10e-9, "outside spans": 12e-9, "ckpt": 10e-9})
    assert s.idle_gaps[0][0] == "outside spans"
    with pytest.raises(ValueError):
        tr.summarize(_Profile([host]), (0, 100))


def test_recorded_cpu_trace():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: jnp.tanh(a @ a).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("chipbench.window"):
                for _ in range(3):
                    f(x).block_until_ready()
                time.sleep(0.02)
        finally:
            jax.profiler.stop_trace()
        pd = jax.profiler.ProfileData.from_file(tr.find_xplane(d))
    (win,) = tr.host_annotations(pd, "chipbench.window")
    s = tr.summarize(pd, (win.start, win.end), plane_prefix=tr.HOST_PLANE,
                     ops_of=tr.cpu_op_names)
    assert s.window_s >= 0.02
    assert 0 < s.busy_s < s.window_s
    assert s.top_ops and all(v > 0 for _, v in s.top_ops)
    assert sum(v for _, v in s.idle_gaps) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
    assert np.isfinite(s.idle_share)
