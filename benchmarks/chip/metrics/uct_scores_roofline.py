"""Share of the roofline reached by the ``uct_scores`` Pallas kernel:
the least time its calls could take on this chip, from the work each call
does and the published peaks, over the device time of its events.  None
where the trace holds no such call."""
from benchmarks.chip.roofline import roofline_share, uct_scores_cost


def read(ctx):
    t = ctx["trace"]
    k = (t or {}).get("kernels", {}).get("uct_scores")
    if not k or k["seconds"] <= 0:
        return None
    flops = nbytes = 0
    for shape, calls in k["shapes"].items():
        f, b = uct_scores_cost(shape, ctx["cfg"]["board_size"] ** 2 + 1)
        flops += calls * f
        nbytes += calls * b
    share, _ = roofline_share(flops, nbytes, k["seconds"],
                              ctx["device"]["kind"])
    return share
