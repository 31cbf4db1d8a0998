"""Whole runs of every cell, cut to a CPU size: sound runs come out
correct with every metric read; a broken timed path, or the lower-
precision control in the program's place, comes out not correct."""
import json

import numpy as np
import pytest

from chipbench_tiny import REPO, run_tiny, tiny_root

CELLS = [w["name"] for w in
         json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(root, workload):
    out = run_tiny(root, workload)
    assert out.correct and out.failed == 0, out.compared
    assert out.attempted > 0
    assert set(out.metrics) == {"als_iter_s", "setup_s"}
    assert all(m["value"] > 0 for m in out.metrics.values())
    for c in out.compared.values():
        assert 0 <= c["value"] <= c["limit"]


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reads_per_layer_metrics(root, workload):
    from chipbench import harness
    out = run_tiny(root, workload, trace=True)
    cell = harness.load_cell(str(root), workload)
    want = {m["name"] for m in cell.per_layer} - {"als_mfu", "peak_hbm_gb"}
    assert out.correct
    assert want <= set(out.metrics), (want, out.metrics)
    assert 0 < out.device["busy_s"] < out.device["window_s"]
    assert out.breakdown["device_ops"] and out.breakdown["idle_gaps"]


def _unchanged(side):
    def patch(monkeypatch):
        from repro.outofcore.store import FactorStore
        real = FactorStore.write_slice

        def write_slice(self, s, start, stop, rows):
            if s != side:
                real(self, s, start, stop, rows)
        monkeypatch.setattr(FactorStore, "write_slice", write_slice)
    return patch


def _half_the_ratings(monkeypatch):
    from repro.kernels import ops
    real = ops.fused_herm

    def fused_herm(theta, idx, val, cnt, lam, **kw):
        return real(theta, idx, val, cnt // 2, lam, **kw)
    monkeypatch.setattr(ops, "fused_herm", fused_herm)


def _altered_answers(monkeypatch):
    from repro.kernels import ops
    real = ops.batch_solve

    def batch_solve(A, B, **kw):
        x = real(A, B, **kw)
        return x.at[::8].multiply(1.01)
    monkeypatch.setattr(ops, "batch_solve", batch_solve)


def _commit_skipped(monkeypatch):
    """Every second checkpoint commit never reaches the disk."""
    from repro.checkpoint import manager
    real = manager.save_checkpoint

    def save_checkpoint(directory, step, tree, *a, **kw):
        if step % 2:
            return real(directory, step, tree, *a, **kw)
    monkeypatch.setattr(manager, "save_checkpoint", save_checkpoint)


FAULTS = {"x_unchanged": _unchanged("x"),
          "commit_skipped": _commit_skipped,
          "theta_unchanged": _unchanged("theta"),
          "half_the_ratings": _half_the_ratings,
          "altered_answers": _altered_answers}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    out = run_tiny(root, CELLS[0])
    assert not out.correct and out.failed > 0, (fault, out.compared)


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(root, workload):
    """The reference computed at ``high`` precision, in the program's
    place and judged by the same limits, comes out not correct where the
    program's own run is correct."""
    out = run_tiny(root, workload, control=True)
    assert out.correct
    assert not out.control["correct"] and out.control["failed"] > 0, (
        out.control, out.compared)
    assert all(np.isfinite(v) for v in out.control["readings"].values())
