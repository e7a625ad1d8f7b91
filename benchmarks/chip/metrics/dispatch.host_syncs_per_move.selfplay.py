"""Blocking host-device round trips (``SearchService.host_syncs``) over
the window, per move searched (the pool's occupied slot-steps)."""


def read(ctx):
    w = ctx["window"]
    if not w.get("searches"):
        return None
    return w["host_syncs"] / w["searches"]
