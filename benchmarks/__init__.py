"""The benchmark: one command per run, cells as data (see BENCHMARK.json)."""
