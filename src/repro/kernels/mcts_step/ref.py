"""Pure-jnp oracle for the fused MCTS superstep (select + backup).

One ``mcts_select`` call performs what the unfused search does with a
``lax.scan`` of per-lane ``while_loop`` descents: all ``lanes`` root-to-leaf
walks of one iteration, sequentially, each lane scoring edges under the
virtual losses applied by the lanes before it.  ``mcts_backup`` is the
matching accumulation: the exact scatter-add of visits/values along every
lane's path.

Deferred-expansion semantics (the documented fused/unfused difference)
----------------------------------------------------------------------
The unfused lane scan *allocates* each lane's new child before the next
lane selects, so later lanes can descend into nodes expanded earlier in
the same iteration.  The fused selection runs over a **frozen** children
table: lanes still see earlier lanes' virtual losses (the decorrelation
that matters), but expansion is deferred — every lane reports the
``(leaf, action)`` edge it wants to expand and ``repro.core.mcts`` grows
the tree for all lanes at once, collapsing duplicate edge picks onto one
new node (mctx-style).  ``fused=False`` therefore stays bit-identical to
the historical program while ``fused=True`` is a search *variant* whose
contract is exact parity between this oracle and the Pallas kernel.

Tie-break noise is a counter-based hash (:func:`tie_break_noise`) rather
than a ``jax.random`` stream: the kernel cannot consume per-(lane, level)
PRNG keys without streaming an ``[L, D, A]`` noise tensor through HBM —
the exact traffic the fusion exists to remove — so both paths derive the
perturbation from ``(seed, lane, level, action)`` arithmetic alone.

Scoring reuses :func:`repro.kernels.uct_select.ref.uct_scores_ref` — one
formula, three call sites (unfused dispatch, this oracle, the Pallas
kernel) — with the same traced ``c_uct`` / ``vl_weight`` / ``prior_w``
contract.  All mask inputs arrive as f32 0/1 slabs (the kernel's native
type); boolean tests are ``> 0``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.uct_select.ref import uct_scores_ref

UNVISITED = -1
NOISE_SCALE = 1e-3          # matches the historical uniform tie-break
_MIX1 = 0x9E3779B9          # golden-ratio odd constants (lane / level / action)
_MIX2 = 0x85EBCA6B
_MIX3 = 0xC2B2AE35
_AVA1 = 0x7FEB352D          # 32-bit avalanche finalizer (degski / murmur-like)
_AVA2 = 0x846CA68B


def tie_break_noise(seed, lane, level, a_iota):
    """Deterministic per-(lane, level, action) noise in ``[0, NOISE_SCALE)``.

    ``seed`` / ``lane`` / ``level`` are traced integer scalars, ``a_iota``
    a uint32 action-index array of any shape.  Pure uint32 arithmetic so
    the Pallas kernel computes bit-identical values to this oracle.
    """
    x = (jnp.asarray(seed).astype(jnp.uint32)
         + jnp.asarray(lane).astype(jnp.uint32) * jnp.uint32(_MIX1)
         + jnp.asarray(level).astype(jnp.uint32) * jnp.uint32(_MIX2)
         + a_iota.astype(jnp.uint32) * jnp.uint32(_MIX3))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_AVA1)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(_AVA2)
    x = x ^ (x >> 16)
    # top 24 bits -> f32 in [0, 1): exact, no rounding surprises.  The
    # value is below 2**24, so the int32 hop is exact; it is there because
    # Mosaic has no uint32 -> float32 cast.
    return ((x >> 8).astype(jnp.int32).astype(jnp.float32)
            * jnp.float32(NOISE_SCALE / (1 << 24)))


def mcts_select_ref(visit, value, vloss, prior, legal, children, expanded,
                    terminal, player, seed, *, c_uct, vl_weight, prior_w=None,
                    use_puct: bool = False, lanes: int, max_depth: int,
                    expand_threshold: int):
    """All ``lanes`` sequential descents of one iteration, single game.

    Inputs: ``visit/value/vloss/player/expanded/terminal`` ``f32[N]``
    (masks as 0/1), ``prior/legal`` ``f32[N, A]``, ``children``
    ``i32[N, A]``, ``seed`` a uint32 scalar; ``c_uct`` / ``vl_weight`` /
    ``prior_w`` traced scalars.

    Returns ``(paths i32[L, D], depth i32[L], leaf i32[L], act i32[L],
    can_expand bool[L], vloss f32[N])`` where ``D = max_depth``; paths are
    node ids padded with ``UNVISITED`` and ``vloss`` carries every lane's
    in-flight increments (cleared by the backup, as in the unfused path).
    """
    a = prior.shape[-1]
    a_iota = jnp.arange(a, dtype=jnp.uint32)

    def lane(vl, l):
        path0 = jnp.full((max_depth,), UNVISITED, jnp.int32).at[0].set(0)

        def cond(c):
            _, depth, _, _, _, stop = c
            return (~stop) & (depth < max_depth - 1)

        def body(c):
            node, depth, _, path, level, _ = c
            kids = children[node]
            has_child = (kids != UNVISITED).astype(jnp.float32)
            cidx = jnp.maximum(kids, 0)
            parent_n = visit[node] + vl[node]
            scores = uct_scores_ref(
                visit[cidx][None], value[cidx][None], vl[cidx][None],
                prior[node][None], legal[node][None], has_child[None],
                parent_n[None], player[node][None],
                c_uct=c_uct, vl_weight=vl_weight, prior_w=prior_w,
                use_puct=use_puct)[0]
            scores = scores + tie_break_noise(seed, l, level, a_iota)
            act = jnp.argmax(scores).astype(jnp.int32)
            child = kids[act]
            nxt = jnp.where(child == UNVISITED, node, child)
            safe = jnp.maximum(child, 0)
            stop = (child == UNVISITED) | (terminal[safe] > 0) \
                | ~(expanded[safe] > 0)
            depth = depth + jnp.where(child == UNVISITED, 0, 1)
            path = path.at[depth].set(nxt)
            return nxt, depth, act, path, level + 1, stop

        init = (jnp.int32(0), jnp.int32(0), jnp.int32(a - 1), path0,
                jnp.int32(0), jnp.bool_(False))
        node, depth, act, path, _, _ = jax.lax.while_loop(cond, body, init)

        can_expand = (children[node, act] == UNVISITED) \
            & ~(terminal[node] > 0) \
            & (visit[node] + vl[node] >= expand_threshold) \
            & (expanded[node] > 0)

        valid = path != UNVISITED
        vl = vl.at[jnp.maximum(path, 0)].add(jnp.where(valid, 1.0, 0.0))
        return vl, (path, depth, node, act, can_expand)

    vl, (paths, depth, leaf, act, can_exp) = jax.lax.scan(
        lane, vloss, jnp.arange(lanes, dtype=jnp.int32))
    return paths, depth, leaf, act, can_exp, vl


def mcts_backup_ref(visit, value, paths, val_sum, playouts: float):
    """Exact scatter-add backup for one game's iteration.

    ``paths i32[L, D]`` (``UNVISITED`` pad), ``val_sum f32[L]`` (summed
    black-perspective returns per lane); every valid path entry gains
    ``playouts`` visits and its lane's ``val_sum``.  Identical arithmetic
    to the unfused ``MCTS._simulate`` backup.
    """
    d = paths.shape[-1]
    flat = paths.reshape(-1)
    ok = flat != UNVISITED
    safe = jnp.maximum(flat, 0)
    w = jnp.where(ok, 1.0, 0.0)
    vrep = jnp.repeat(val_sum, d)
    visit = visit.at[safe].add(w * playouts)
    value = value.at[safe].add(jnp.where(ok, vrep, 0.0))
    return visit, value
