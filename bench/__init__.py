"""On-chip benchmark of the served onerec-v2 engine (see BENCHMARK.json)."""
