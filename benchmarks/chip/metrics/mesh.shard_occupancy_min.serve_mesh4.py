"""Occupancy of the least occupied shard: the smallest, over the shards,
share of that shard's slot-steps in the window that held a query."""


def read(ctx):
    occ = ctx["window"].get("shard_occupancy")
    return min(occ) if occ else None
