"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.  Any module that declares
a ``JSON_OUT`` filename has its ``run()`` return value serialized there —
one generic path, so BENCH_outofcore.json (bench_huge) and BENCH_sgd.json
(bench_sgd) flow identically and new JSON emitters need no run.py edits.

Selection::

    python benchmarks/run.py                      # everything
    python benchmarks/run.py --quick              # fast subset
    python benchmarks/run.py --only convergence --only sgd
    python benchmarks/run.py --only sgd --quick   # sgd at smoke scale

``--only`` takes the short names below (repeatable); unknown names fail
loudly rather than silently skipping (the old ``--quick`` truncated the
module list and never reached the JSON-emitting modules).  ``--quick``
without ``--only`` selects the fast subset; combined with ``--only`` it
keeps the explicit selection and is instead passed through to any module
whose ``run`` accepts a ``quick`` keyword (scaled-down problem sizes for
the CI smoke lane).
"""
from __future__ import annotations

import argparse
import importlib
import inspect
import json
import os
import sys

# make ``python benchmarks/run.py`` work from anywhere: the repo root (the
# parent of this file's directory) must be importable for ``benchmarks.*``
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

# short name -> module; order is the full-run execution order
MODULES = [
    ("convergence", "bench_convergence"),            # Fig. 6
    ("register_ablation", "bench_register_ablation"),  # Fig. 7
    ("texture", "bench_texture"),                    # Fig. 8
    ("scaling", "bench_scaling"),                    # Fig. 9/10
    ("huge", "bench_huge"),                          # Fig. 11 + out-of-core
    ("reduction", "bench_reduction"),                # Fig. 5
    ("kernels", "bench_kernels"),                    # kernel-level fusion
    ("lm_substrate", "bench_lm_substrate"),          # LM substrate overhead
    ("sgd", "bench_sgd"),                            # ALS vs SGD vs hybrid
]
QUICK = ("convergence", "register_ablation")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help=f"run only the fast subset: {', '.join(QUICK)}")
    ap.add_argument("--only", action="append", default=[], metavar="NAME",
                    help="run only the named benchmark (repeatable); "
                         f"names: {', '.join(n for n, _ in MODULES)}")
    ap.add_argument("--autotune", action="store_true",
                    help="let benchmarks that take an `autotune` keyword "
                         "add autotuned-layout rows (cuMF Alg.-2 sweep via "
                         "repro.core.autotune; see TUNING.md)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record obs spans across every selected benchmark "
                         "and write one Chrome-trace/Perfetto JSON file")
    ap.add_argument("--history", default="BENCH_HISTORY.jsonl",
                    metavar="JSONL",
                    help="append every JSON emission here with provenance "
                         "(git sha, backend, device count, quick flag); "
                         "gate with `python -m repro.obs.regress --history`")
    ap.add_argument("--no-history", action="store_true",
                    help="skip the history append (one-off local runs)")
    args = ap.parse_args(argv)

    known = {n for n, _ in MODULES}
    unknown = [n for n in args.only if n not in known]
    if unknown:
        ap.error(f"unknown benchmark name(s) {unknown}; "
                 f"choose from {sorted(known)}")
    if args.only:
        selected = set(args.only)
    elif args.quick:
        selected = set(QUICK)
    else:
        selected = known

    tracer = None
    if args.trace:
        from repro.obs import Tracer, set_tracer
        tracer = Tracer()
        set_tracer(tracer)      # streaming drivers pick it up themselves

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks.history import append_history, provenance, stamp
    prov = provenance(quick=args.quick)

    print("name,us_per_call,derived")
    try:
        for name, modname in MODULES:
            if name not in selected:
                continue
            mod = importlib.import_module(f"benchmarks.{modname}")
            kwargs = {}
            params = inspect.signature(mod.run).parameters
            if args.quick and "quick" in params:
                kwargs["quick"] = True
            if args.autotune and "autotune" in params:
                kwargs["autotune"] = True
            out = mod.run(**kwargs)
            json_out = getattr(mod, "JSON_OUT", None)
            if json_out and out:
                stamp(out, prov)
                with open(json_out, "w") as f:
                    json.dump(out, f, indent=2)
                print(f"# wrote {len(out)} records to {json_out}", flush=True)
                if not args.no_history:
                    append_history(args.history, modname, out, prov)
                    print(f"# history: {modname} -> {args.history}",
                          flush=True)
    finally:
        if tracer is not None:
            from repro.obs import write_trace
            write_trace(args.trace, tracer, process_name="benchmarks")
            print(f"# trace: {len(tracer.events)} events -> {args.trace}",
                  flush=True)


if __name__ == '__main__':
    main()
