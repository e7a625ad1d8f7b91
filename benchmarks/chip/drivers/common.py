"""Set-up shared by the drivers."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def warm_ring_reads(svc, most: int) -> None:
    """Compile the eager gathers that ``SearchService.poll`` runs on its
    result ring for every count of new rows from 1 to ``most``.

    ``poll`` gathers exactly the unread rows, so each new count compiles
    its own small gather; run here, the same operations on the same
    shapes fill the compile cache before the window instead of inside it.
    """
    ring = svc._pool.ring
    bufs = tuple(getattr(ring, f) for f in svc._RING_FIELDS)
    shards = [bufs] if svc.mesh is None else [
        jax.tree.map(lambda b: b[s], bufs) for s in range(svc.n_shard)]
    for n in range(1, most + 1):
        idx = jnp.asarray(list(range(n)))
        for sb in shards:
            jax.block_until_ready(jax.tree.map(lambda b: b[idx], sb))
