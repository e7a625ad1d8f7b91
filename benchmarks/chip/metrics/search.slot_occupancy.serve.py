"""Share of the searched slot-steps of the window that answer a query:
the pool's occupied slot-steps over steps times slots."""


def read(ctx):
    return ctx["window"].get("slot_occupancy")
