"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the checkout root.  ``--trace 0`` prints the cell's end-to-end
metrics (host clock); ``--trace 1`` runs the same window under the JAX
profiler and prints its per-layer metrics, the device's busy time and a
breakdown.  The last line of standard output is one JSON object; the
numbers the correctness check compared, each beside its limit, are also
the last lines of standard error.  Without a TPU (or with fewer chips
than the cell asks for) it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        cell = harness.load_cell(harness.ROOT, args.workload)
        harness.program_on_path()
        devs = harness.require_devices(cell.chips)
        if devs is None:
            return 1
        peaks = harness.load_peaks(harness.ROOT, devs[0].device_kind)
        harness.enable_program_cache()
        out = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                               trace=bool(args.trace), peaks=peaks,
                               t_start=T_START)
    except (harness.BenchError, ImportError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    for name, c in out.compared.items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(harness.result_line(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
