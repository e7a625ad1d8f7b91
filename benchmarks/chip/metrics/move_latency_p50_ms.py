"""Median latency of the queries due in the window, each from its due
time to the poll that returned its answer (client clock)."""
from benchmarks.chip.metrics_lib import percentile


def read(ctx):
    return percentile(ctx["window"].get("latency_ms", []), 50)
