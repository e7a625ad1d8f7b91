"""Simulations completed per second: searches run in the window (the
pool's occupied slot-steps) times the budget of each, over the wall time
of the window's whole dispatches."""


def read(ctx):
    w = ctx["window"]
    if "sims" not in w or w["wall_s"] <= 0:
        return None
    return w["sims"] / w["wall_s"]
