"""90th percentile of the same samples as the median."""
from benchmarks.chip.metrics_lib import percentile


def read(ctx):
    return percentile(ctx["window"].get("latency_ms", []), 90)
