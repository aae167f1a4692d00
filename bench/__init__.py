"""On-chip benchmark of the RaLMSpec serving path (see BENCHMARK.json)."""
