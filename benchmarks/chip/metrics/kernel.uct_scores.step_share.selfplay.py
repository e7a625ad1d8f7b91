"""Share of the device's busy time in the traced window spent in the
``uct_scores`` Pallas kernel; nothing where the device dropped trace
buffers."""


def read(ctx):
    t = ctx["trace"] or {}
    k = t.get("kernels", {}).get("uct_scores")
    if not k or t["dropped"] or t["busy_s"] <= 0:
        return None
    return k["seconds"] / t["busy_s"]
