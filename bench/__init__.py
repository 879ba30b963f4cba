"""The on-chip benchmark (see BENCHMARK.json and PERF.md)."""
