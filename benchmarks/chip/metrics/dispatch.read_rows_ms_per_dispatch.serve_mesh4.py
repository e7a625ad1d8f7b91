"""Host time in ``poll``'s reads of new result-ring rows (the program's
``read_rows_s``: every shard ring's read, its blocking transfer
included) per superstep reconciled in the window; nothing where the
program does not count it."""


def read(ctx):
    w = ctx["window"]
    if w.get("read_rows_s") is None or not w.get("supersteps"):
        return None
    return 1e3 * w["read_rows_s"] / w["supersteps"]
