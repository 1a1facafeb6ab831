"""The chip benchmark of the SQMD federation (BENCHMARK.json, PERF.md)."""
