"""Host time in the service's ``submit`` and ``poll`` less their
blocking device waits (the program's ``host_busy_s``) per superstep
reconciled in the window: whether the host or the chips set the pace."""


def read(ctx):
    w = ctx["window"]
    if w.get("host_busy_s") is None or not w.get("supersteps"):
        return None
    return 1e3 * w["host_busy_s"] / w["supersteps"]
