"""The readers of the accumulate-Theta update's device time and HBM
roofline share, on hand-made windows: what they read from a TPU trace's
program names and the ``als.accumulate`` spans, and that they read
nothing where either is missing or no peak is known."""
import jax
import pytest
from chipbench_tiny import REPO
from chipbench import harness
from chipbench import trace_reduce as tr
from repro.obs import SpanEvent

TOP_OPS = [["jit__accumulate_bin/fusion", 0.6],
           ["jit__accumulate_bin/dynamic-update-slice", 0.2],
           ["jit__solve_accumulated/custom-call:tpu_custom_call", 0.5],
           ["jit_fused_herm/fusion", 0.9]]


def _trace(top_ops):
    return tr.Summary(window_s=4.0, busy_s=2.0, n_devices=1,
                      top_ops=top_ops, idle_gaps=[])


def _acc_span(bin_, rows, nnz, x_rows=1000, f=10):
    return SpanEvent("als.accumulate", "accumulate", "X", 0.0, 5.0, 1,
                     {"bin": bin_, "rows": rows, "slots": 8 * rows,
                      "nnz": nnz, "x_rows": x_rows, "n": 5000, "f": f})


def _window(spans, trace, peak_flops=197e12, iterations=2):
    return harness.Window(
        iterations=iterations, seconds=4.0, phase_seconds={}, spans=spans,
        compile_s=0.0, store_build_s=0.0, flops_per_iter=1.0,
        peak_flops=peak_flops, trace=trace, memory_peak_bytes=None)


def test_accumulate_device_seconds_reader():
    read = harness.load_reader(str(REPO), "accumulate_device_s_per_iter")
    assert read(_window([], _trace(TOP_OPS))) == pytest.approx(0.4)
    assert read(_window([], _trace(TOP_OPS[2:]))) is None
    assert read(_window([], None)) is None


def test_accumulate_roofline_reader(monkeypatch):
    read = harness.load_reader(str(REPO), "accumulate_roofline")
    spans = [_acc_span(0, 3000, 9000), _acc_span(1, 200, 4000),
             _acc_span(0, 3000, 9000), _acc_span(1, 200, 4000)]
    # ratings, each bin's systems written once, the X slice read once
    one_iter = 8 * 13000 + 4 * 3200 * 111 + 4 * 1000 * 10

    class _Dev:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda: [_Dev()])
    got = read(_window(spans, _trace(TOP_OPS)))
    assert got == pytest.approx(100 * one_iter / 819e9 / 0.4)
    assert 0 < got < 100
    assert read(_window(spans, _trace(TOP_OPS), peak_flops=None)) is None
    assert read(_window([], _trace(TOP_OPS))) is None
    assert read(_window(spans, _trace(TOP_OPS[2:]))) is None
