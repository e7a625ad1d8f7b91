"""The on-chip benchmark: a data-driven harness that runs one cell of
``BENCHMARK.json`` once (see harness.py)."""
