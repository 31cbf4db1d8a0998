"""Shared helpers of the chipbench tests: a checkout root whose cells are
the real ones cut to a size the CPU runs in seconds."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

#: per-configuration sizes for the CPU (widths f and lambda kept)
TINY = {"netflix_f100": {"m": 1200, "n": 240, "nnz": 16000}}
TINY_BATCH_ROWS = 256


def tiny_root(dst: pathlib.Path) -> pathlib.Path:
    """A copy of the benchmark's files under ``dst`` with every
    configuration cut to :data:`TINY` (``batch_rows`` cut so the row-block
    path runs)."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for d in ("traffic", "metrics"):
        shutil.copytree(REPO / "chipbench" / d, dst / "chipbench" / d)
    shutil.copy(REPO / "chipbench" / "peaks.json", dst / "chipbench")
    os.makedirs(dst / "chipbench" / "configs")
    for c in bench["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        cfg.update(TINY[c["name"]], batch_rows=TINY_BATCH_ROWS)
        (dst / c["file"]).write_text(json.dumps(cfg))
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


def run_tiny(root: pathlib.Path, workload: str, *, seed: int = 2**31 + 7,
             trace: bool = False, control: bool = False):
    """One CPU run of a tiny cell through the whole harness but the look
    for a chip (no peaks, no compile cache)."""
    from chipbench import harness
    cell = harness.load_cell(str(root), workload)
    return harness.run_cell(cell, seed=seed, seconds=0.0, trace=trace,
                            peaks=None, control=control, root=str(root))
