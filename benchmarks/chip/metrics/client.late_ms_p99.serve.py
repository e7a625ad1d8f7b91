"""How late the client sent its queries: the 99th percentile of send
time minus due time, over every query of the window."""
from benchmarks.chip.metrics_lib import percentile


def read(ctx):
    return percentile(ctx["window"].get("late_ms", []), 99)
