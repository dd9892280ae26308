"""Closed-burst benchmark of the served path on TPU chips; see
BENCHMARK.json and PERF.md."""
