"""Chip benchmark of the serving engine: cells found by name in BENCHMARK.json."""
