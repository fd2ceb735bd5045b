"""On-chip benchmark of the serving engine (see BENCHMARK.json at the root)."""
