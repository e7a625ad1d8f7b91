"""Distributed MCTS: root parallelism across mesh devices.

The paper is bounded by one shared-memory board (240 threads).  The natural
next rung — which its conclusion calls for — is distributed trees.  We place
``root_trees`` independent tree-parallel searches across the mesh with
``shard_map`` and merge root statistics with a single small ``psum`` (a
[num_actions] vector per tree), the collective analogue of FUEGO's shared
root.  This is the configuration the multi-pod dry-run lowers at 256/512
chips.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.config import MCTSConfig
from repro.core import tree as tree_lib
from repro.core.mcts import MCTS
from repro.go.board import GoEngine, GoState


def distributed_best_move(engine: GoEngine, cfg: MCTSConfig, mesh: Mesh,
                          axis: str = "data", **mcts_kw):
    """Build a jitted ``(root_state, rng) -> action`` running root-parallel
    search sharded over ``axis`` (trees_per_device trees on each device)."""
    n_dev = mesh.shape[axis]
    total_trees = max(cfg.root_trees, n_dev)
    per_dev = max(1, total_trees // n_dev)
    searcher = MCTS(engine, cfg, **mcts_kw)

    def local_search(root: GoState, keys):
        # keys: [per_dev, 2] on this shard; tile the root per local tree
        roots = jax.tree.map(
            lambda x: jnp.broadcast_to(x, keys.shape[:1] + jnp.shape(x)),
            root)
        res = searcher.search_batch(roots, keys)
        visits = res.root_visits.sum(axis=0)
        return visits

    def sharded(root: GoState, keys):
        visits = local_search(root, keys)
        visits = jax.lax.psum(visits, axis)          # merge root statistics
        return tree_lib.select_action(visits, engine.legal_moves(root))

    other_axes = tuple(a for a in mesh.axis_names if a != axis)
    key_spec = P(axis)
    rep = P()

    fn = shard_map(
        sharded, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: rep, _state_spec(engine)), key_spec),
        out_specs=rep,
        check_vma=False,
    )

    @jax.jit
    def run(root: GoState, rng):
        keys = jax.random.split(rng, n_dev * per_dev).reshape(
            n_dev * per_dev, 2)
        return fn(root, keys)

    return run


def _state_spec(engine: GoEngine) -> GoState:
    # pytree skeleton for in_specs construction
    return engine.init_state()


def selfplay_step(engine: GoEngine, cfg: MCTSConfig, mesh: Mesh,
                  axis: str = "data", **mcts_kw):
    """jittable one-move step of distributed self-play: state -> state.

    This is the function ``launch/dryrun.py`` lowers on the production mesh
    for the paper's own application cells.
    """
    move_fn_inner = distributed_best_move(engine, cfg, mesh, axis, **mcts_kw)

    def step(root: GoState, rng):
        action = move_fn_inner(root, rng)
        return engine.play(root, action)

    return step
