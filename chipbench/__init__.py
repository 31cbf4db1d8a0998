"""On-chip benchmark of streaming ALS (see PERF.md and BENCHMARK.json)."""
