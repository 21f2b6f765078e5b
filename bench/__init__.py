"""Chip benchmark of the kNN service (see BENCHMARK.json and PERF.md)."""
