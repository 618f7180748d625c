"""Chip benchmark of the durable flat-combining fabric (see BENCHMARK.json)."""
