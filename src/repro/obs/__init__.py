"""repro.obs — span tracing, metrics, and Chrome-trace export.

The observability layer for the streaming/mesh stack (see
OBSERVABILITY.md for the span/metric catalog and a how-to):

- ``obs.trace`` — thread-aware span tracer (no-op unless enabled), the
  ``phase`` helper that also feeds per-phase metrics, the process-wide
  ``set_tracer``/``current_tracer`` hook ``--trace`` installs, and the
  ``compile_listener`` that counts JAX's traces and compiles.
- ``obs.metrics`` — counters / gauges / fixed-bucket histograms;
  ``StreamTelemetry`` is a view over one of these registries.
- ``obs.export`` — Chrome-trace / Perfetto JSON emission + the schema
  validator CI runs over the emitted file.
- ``obs.ledger`` — plan-vs-actual records (predicted vs measured bytes /
  peaks / reduce traffic / fill waste) with recomputed verdicts;
  ``obs.report`` renders one, ``obs.regress`` exit-codes it (and the
  bench history) for CI.

Stdlib-only on purpose (like ``repro.analysis``): the lint job and the
import sweep load it in any environment the repo loads in, and nothing in
the hot path pulls jax/numpy through the instrumentation.
"""
from repro.obs.export import (chrome_trace, load_and_validate, span_counts,
                              validate_chrome_trace, write_trace)
from repro.obs.ledger import (LEDGER_SCHEMA, Ledger, merge_ledgers,
                              validate_ledger)
from repro.obs.metrics import (DEFAULT_LATENCY_BUCKETS, Counter, Gauge,
                               Histogram, MetricsRegistry)
from repro.obs.trace import (NOOP_SPAN, NULL_TRACER, NullTracer, SpanEvent,
                             Tracer, compile_listener, current_tracer, phase,
                             set_tracer)

__all__ = [
    "Counter", "DEFAULT_LATENCY_BUCKETS", "Gauge", "Histogram",
    "LEDGER_SCHEMA", "Ledger", "MetricsRegistry", "NOOP_SPAN",
    "NULL_TRACER", "NullTracer", "SpanEvent", "Tracer", "chrome_trace",
    "compile_listener", "current_tracer", "load_and_validate",
    "merge_ledgers", "phase", "set_tracer", "span_counts",
    "validate_chrome_trace", "validate_ledger", "write_trace",
]
