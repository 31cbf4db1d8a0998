"""The control of the ``correct`` comparison: the reference's row solves
computed one precision step below what the configuration states.

The program computes in float32, its contractions at ``HIGHEST``
precision.  One step below that is ``high``: each contraction (the
Hermitians A = F^T F and F^T r) in three bf16 passes (hi*hi + hi*lo +
lo*hi, each float32 operand split as hi = bf16(a), lo = bf16(a - hi),
products summed in float32), what a TPU runs for a float32 contraction at
``Precision.HIGH``; the solve stays float32.  The passes are emulated on
the host with ``ml_dtypes.bfloat16``, so the control reads the same on any
machine and a CPU test can show it fail.

    python3 chipbench/control.py --workload <name> --seeds 11 12 13

runs, for each seed in one process, the benchmark's set-up and a window of
one iteration through the program, then prints the program's compared
numbers and the control's, each judged by the same limits (the readings
the limits are set from).  It needs the chip, as a benchmark run does.
"""
from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench.reference import Csr  # noqa: E402


def _bf16(a) -> np.ndarray:
    """``a`` rounded to bfloat16, held as float32."""
    import ml_dtypes
    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def _split(a: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    hi = _bf16(a)
    return hi, _bf16(a - hi)


def _dot_high(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """``a @ b`` in three bf16 passes, accumulated in float32."""
    ah, al = _split(a)
    bh, bl = _split(b)
    return ah @ bh + ah @ bl + al @ bh


def solve_rows_low(fixed: np.ndarray, csr: Csr, rows: np.ndarray,
                   lam: float) -> np.ndarray:
    """Solutions of ``rows`` one precision step below the program's:
    ``high`` contractions, float32 solve (a row without ratings is 0)."""
    f = fixed.shape[1]
    a = np.broadcast_to(np.eye(f, dtype=np.float32),
                        (rows.size, f, f)).copy()
    b = np.zeros((rows.size, f), np.float32)
    for i, u in enumerate(rows):
        lo, hi = csr.ptr[u], csr.ptr[u + 1]
        if hi == lo:
            continue
        g = fixed[csr.cols[lo:hi]].astype(np.float32)
        r = csr.vals[lo:hi].astype(np.float32)[:, None]
        a[i] = _dot_high(g.T, g) + np.float32(lam * (hi - lo)) * np.eye(
            f, dtype=np.float32)
        b[i] = _dot_high(g.T, r)[:, 0]
    return np.linalg.solve(a, b[..., None])[..., 0]


def main(argv=None) -> int:
    import argparse
    import json

    from chipbench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="read the control on the first this many seeds")
    args = ap.parse_args(argv)
    root = harness.ROOT
    cell = harness.load_cell(root, args.workload)
    harness.program_on_path()
    dev = harness.require_devices(cell.chips)
    if dev is None:
        return 2
    harness.enable_program_cache()
    for i, seed in enumerate(args.seeds):
        out = harness.run_cell(cell, seed=seed, seconds=0.0, trace=False,
                               peaks=None,
                               control=i < args.control_seeds)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": out.correct, "failed": out.failed,
                          "compared": out.compared,
                          "readings": out.readings,
                          "control": out.control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
