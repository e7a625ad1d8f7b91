"""GPipe-style pipeline parallelism over a mesh axis (the ``pod`` axis).

Alternative to pure cross-pod DP when even compressed gradient exchange is
too expensive: split the layer stack into one *stage per pod* and stream
microbatches through with ``collective_permute`` boundary handoffs.  The
classic GPipe schedule runs ``M + S - 1`` ticks for M microbatches and S
stages (bubble fraction (S-1)/(M+S-1)); activations cross the slow link once
per boundary instead of every gradient every step.

Implemented with ``shard_map`` over the stage axis: every device holds its
stage's layer slice (params are sharded layer-wise over the axis) and the
tick loop is a ``lax.scan`` whose carry is each stage's in-flight microbatch.
``pipeline_forward`` is the schedule core — it is validated numerically
against the unpartitioned stack in tests and lowered in the dry-run extras.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(layer_fn: Callable, mesh: Mesh, axis: str = "pod"):
    """Build fn(stage_params, x_microbatches) -> y_microbatches.

    ``layer_fn(params_slice, x) -> x`` applies one stage's layers.
    ``stage_params``: pytree with leading dim = n_stages (sharded over
    ``axis``).  ``x_microbatches``: [M, mb, ...] replicated along ``axis``.
    """
    n_stages = mesh.shape[axis]

    def staged(params_l, xs):
        # params_l: this stage's slice (leading dim 1) ; xs: [M, mb, ...]
        params_me = jax.tree.map(lambda a: a[0], params_l)
        stage = jax.lax.axis_index(axis)
        m = xs.shape[0]
        ticks = m + n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            buf, outs = carry      # buf: [mb, ...] current stage input
            # stage s works on microbatch t - s when 0 <= t - s < m
            mb_idx = t - stage
            active = (mb_idx >= 0) & (mb_idx < m)
            x_in = jnp.where(
                stage == 0,
                xs[jnp.clip(mb_idx, 0, m - 1)],   # stage 0 pulls from feed
                buf)                               # others use handoff
            y = layer_fn(params_me, x_in)
            y = jnp.where(active, y, x_in)
            # last stage records its finished microbatch
            out_idx = t - (n_stages - 1)
            record = (stage == n_stages - 1) & (out_idx >= 0) & (out_idx < m)
            outs = jax.lax.cond(
                record,
                lambda o: o.at[jnp.clip(out_idx, 0, m - 1)].set(y),
                lambda o: o, outs)
            # hand off to the next stage
            nxt = jax.lax.ppermute(y, axis, perm)
            return (nxt, outs), None

        buf0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                    jnp.arange(ticks))
        # every stage holds outs; only the last stage's copy is real -> share
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    # P(axis) is a prefix spec: every param leaf shards its leading (stage)
    # dim over ``axis``; microbatches are replicated along it.
    return shard_map(staged, mesh=mesh, in_specs=(P(axis), P()),
                     out_specs=P(), check_vma=False)
