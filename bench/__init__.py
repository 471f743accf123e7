"""The on-chip benchmark of the dynamic-graph server (see BENCHMARK.json
and PERF.md at the root of the checkout)."""
