"""Share of the traced window in which no operation ran on the device
(averaged over the chips used); nothing where the device dropped trace
buffers, since its busy time is then short."""


def read(ctx):
    t = ctx["trace"]
    if not t or t["window_s"] <= 0 or t["dropped"]:
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
