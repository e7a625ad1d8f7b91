"""Blocking host-device round trips (``GoService.host_syncs``) over the
window, per answered query."""


def read(ctx):
    w = ctx["window"]
    if not w.get("answered"):
        return None
    return w["host_syncs"] / w["answered"]
