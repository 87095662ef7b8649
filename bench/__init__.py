"""The chip benchmark: harness, yardstick and data (see BENCHMARK.json)."""
