"""Median time a query waits in the service's host queue, submit to
flush: the program's own queue histogram (``ServingMetrics``), counted
over the window only."""
from benchmarks.chip.metrics_lib import hist_percentile


def read(ctx):
    h = ctx["window"].get("queue_hist")
    if h is None:
        return None
    v = hist_percentile(h[0], h[1], 50)
    return None if v is None else 1e3 * v
