"""The chip benchmark: BENCHMARK.json at the repository root names the
cells; everything that measures them lives here (see PERF.md)."""
