"""Pallas kernels for the fused MCTS superstep: select-all-lanes + backup.

Hardware adaptation (DESIGN.md §2): the paper's finding is that past 32
threads FUEGO is gated by cache/memory behaviour *inside* the per-thread
search loop, not by parallelism.  The modern analogue: the unfused lane
scan re-reads the tree slabs (``visit/value/vloss/prior/legal/children``)
from HBM at every level of every lane and materialises per-level score
rows back to HBM.  Here one grid step owns one game's entire arena in
VMEM — the slabs are loaded once, all ``lanes`` sequential descents run
against the resident copies (each seeing the previous lanes' virtual
losses), and only the compact selection outputs (paths, leaves, updated
``vloss``) leave the kernel.

Pointer-chasing becomes linear algebra: the per-level child-statistics
gather (``visit[children[node]]``, the FUEGO hot read) is a one-hot
``[1, N] x [N, A]`` matmul on the MXU — the idiom the tree arena was
shaped for — and every scalar read from an ``[N]`` slab is a masked
reduction, so the kernel never needs an unaligned lane-axis dynamic
slice.  Dynamic *row* slices (``prior[node]``) use ``pl.ds`` on the
sublane axis, the well-supported case.  Per-lane outputs accumulate in loop-carried
vectors and are stored once, avoiding dynamic stores entirely.

Grid/tiling: ``grid=(G,)`` over games; per-game blocks ``(1, 1, N)``
over ``[G, 1, N]`` vectors and ``(1, N, A)`` over the slabs, with ``A``
padded to a 128-lane multiple by ``ops.py``.  A block's last two dims
must be multiples of (8, 128) or equal the array's, so a per-game row
gets its own unit axis.  Inside, every vector is a 2-D ``[1, width]``
row: Mosaic cannot reshape a 1-D vector into a column.
The descent loop is a masked ``fori_loop`` with static bound
``max_depth - 1`` (iterations after the lane stops are no-ops), the
Mosaic-safe form of the oracle's ``while_loop``.

Traced-vs-static: ``c_uct`` / ``vl_weight`` / ``prior_w`` / ``seed``
ride in as per-game ``(1, 1, 1)`` blocks (values never recompile);
``lanes`` / ``max_depth`` / ``expand_threshold`` / ``use_puct`` and the
``prior_w``-presence program selector are static, mirroring
``kernels/uct_select`` exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mcts_step.ref import UNVISITED, tie_break_noise
from repro.kernels.uct_select.ref import uct_scores_rows

LANE = 128   # action-axis padding multiple (shared with uct_select)
# Scoped VMEM a kernel may claim on one core.  The select kernel keeps one
# game's whole prior / legal / children slabs resident, double-buffered
# across the game grid: 24 MiB at N=8192, A=128, beyond the 16 MiB
# default on v5e (128 MiB physical).
VMEM_LIMIT_BYTES = 100 * 1024 * 1024


def _compiler_params():
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _at(row, idx, iota):
    """``row[0, idx]`` as a masked reduction (no lane-axis dynamic slice)."""
    return jnp.sum(jnp.where(iota == idx, row, jnp.zeros_like(row)))


def _col(x):
    """A scalar as a ``[1, 1]`` value, the per-row column of one row."""
    return jnp.full((1, 1), x)


def _select_kernel(visit_ref, value_ref, vloss_ref, prior_ref, legal_ref,
                   children_ref, expanded_ref, terminal_ref, player_ref,
                   seed_ref, cuct_ref, vlw_ref, pw_ref,
                   paths_ref, depth_ref, leaf_ref, act_ref, canexp_ref,
                   vloss_out_ref, *, lanes: int, max_depth: int,
                   expand_threshold: int, use_puct: bool, blend: bool):
    # per-row knobs are [1, 1] columns of the one scored row
    n = visit_ref.shape[2]
    a = prior_ref.shape[2]
    visit = visit_ref[0]
    value = value_ref[0]
    expanded = expanded_ref[0]
    terminal = terminal_ref[0]
    player = player_ref[0]
    seed = seed_ref[0]
    c_uct = cuct_ref[0]
    vl_weight = vlw_ref[0]
    prior_w = pw_ref[0] if blend else None

    iota_n = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    iota_a = jax.lax.broadcasted_iota(jnp.int32, (1, a), 1)
    iota_d = jax.lax.broadcasted_iota(jnp.int32, (1, max_depth), 1)
    iota_l = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    iota_ld = jax.lax.broadcasted_iota(jnp.int32, (lanes, max_depth), 0)
    iota_na = jax.lax.broadcasted_iota(jnp.int32, (n, a), 0)
    a_iota = iota_a.astype(jnp.uint32)
    path0 = jnp.where(iota_d == 0, jnp.int32(0), jnp.int32(UNVISITED))

    def gather(row, oh):
        return jnp.dot(row, oh, preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)

    def lane_body(lane, carry):
        vl, paths_m, depth_v, leaf_v, act_v, canexp_v = carry

        def level_body(level, c):
            node, depth, act, stop, path, path_mask = c
            run = ~stop & (depth < max_depth - 1)
            kids = children_ref[0, pl.ds(node, 1), :]            # [1, A] i32
            # child-statistics gather as a one-hot MXU pass
            oh = (iota_na == kids).astype(jnp.float32)           # [N, A]
            cvisit = gather(visit, oh)
            cvalue = gather(value, oh)
            cvloss = gather(vl, oh)
            has_child = (kids != UNVISITED).astype(jnp.float32)
            prior_row = prior_ref[0, pl.ds(node, 1), :]
            legal_row = legal_ref[0, pl.ds(node, 1), :]
            scores = uct_scores_rows(
                cvisit, cvalue, cvloss, prior_row, legal_row, has_child,
                _col(_at(visit + vl, node, iota_n)),
                _col(_at(player, node, iota_n)),
                c_uct, vl_weight, prior_w, use_puct=use_puct)
            scores = scores + tie_break_noise(seed, lane, level, a_iota)
            act_new = jnp.argmax(scores, axis=1)[0].astype(jnp.int32)
            child = jnp.sum(jnp.where(iota_a == act_new, kids, 0))
            nxt = jnp.where(child == UNVISITED, node, child)
            safe = jnp.maximum(child, 0)
            stop_new = (child == UNVISITED) \
                | (_at(terminal, safe, iota_n) > 0) \
                | ~(_at(expanded, safe, iota_n) > 0)
            depth_new = depth + jnp.where(child == UNVISITED, 0, 1)
            path_new = jnp.where(iota_d == depth_new, nxt, path)
            mask_new = path_mask + jnp.where(
                (iota_n == child) & (child != UNVISITED), 1.0, 0.0)
            return (jnp.where(run, nxt, node),
                    jnp.where(run, depth_new, depth),
                    jnp.where(run, act_new, act),
                    jnp.where(run, stop_new, stop),
                    jnp.where(run, path_new, path),
                    jnp.where(run, mask_new, path_mask))

        root_mask = jnp.where(iota_n == 0, 1.0, 0.0)
        init = (jnp.int32(0), jnp.int32(0), jnp.int32(a - 1),
                jnp.bool_(False), path0, root_mask)
        node, depth, act, _, path, path_mask = jax.lax.fori_loop(
            0, max_depth - 1, level_body, init)

        kids = children_ref[0, pl.ds(node, 1), :]
        child_at = jnp.sum(jnp.where(iota_a == act, kids, 0))
        can_exp = (child_at == UNVISITED) \
            & ~(_at(terminal, node, iota_n) > 0) \
            & (_at(visit + vl, node, iota_n) >= expand_threshold) \
            & (_at(expanded, node, iota_n) > 0)

        here = iota_l == lane
        return (vl + path_mask,
                jnp.where(iota_ld == lane, path, paths_m),
                jnp.where(here, depth, depth_v),
                jnp.where(here, node, leaf_v),
                jnp.where(here, act, act_v),
                jnp.where(here, can_exp.astype(jnp.int32), canexp_v))

    zl = jnp.zeros((1, lanes), jnp.int32)
    init = (vloss_ref[0],
            jnp.full((lanes, max_depth), UNVISITED, jnp.int32),
            zl, zl, zl, zl)
    vl, paths_m, depth_v, leaf_v, act_v, canexp_v = jax.lax.fori_loop(
        0, lanes, lane_body, init)
    paths_ref[0] = paths_m
    depth_ref[0] = depth_v
    leaf_ref[0] = leaf_v
    act_ref[0] = act_v
    canexp_ref[0] = canexp_v
    vloss_out_ref[0] = vl


def _backup_kernel(paths_ref, valsum_ref, visit_in_ref, value_in_ref,
                   visit_ref, value_ref, *, lanes: int, playouts: float):
    n = visit_in_ref.shape[2]
    d = paths_ref.shape[2]
    iota_dn = jax.lax.broadcasted_iota(jnp.int32, (d, n), 1)
    iota_l = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    valsum = valsum_ref[0]

    def lane_body(lane, c):
        visit, value = c
        col = jnp.transpose(paths_ref[0, pl.ds(lane, 1), :])    # [D, 1] i32
        vs = _at(valsum, lane, iota_l)
        oh = ((iota_dn == col) & (col != UNVISITED)
              ).astype(jnp.float32)                              # [D, N]
        counts = jnp.sum(oh, axis=0, keepdims=True)              # [1, N]
        return visit + counts * playouts, value + counts * vs

    visit, value = jax.lax.fori_loop(
        0, lanes, lane_body, (visit_in_ref[0], value_in_ref[0]))
    visit_ref[0] = visit
    value_ref[0] = value


def _row(width: int) -> pl.BlockSpec:
    """One game's ``[1, width]`` slice of a ``[G, 1, width]`` operand.

    The unit middle axis makes the block's last two dims equal the
    array's, which is what the TPU's 8x128 tiling rule asks of a block
    narrower than a tile.
    """
    return pl.BlockSpec((1, 1, width), lambda i: (i, 0, 0))


def _rows(x):
    """``[G, ...]`` -> ``[G, 1, ...]`` (the layout :func:`_row` blocks)."""
    return x[:, None]


def mcts_select_pallas(visit, value, vloss, prior, legal, children, expanded,
                       terminal, player, seed, c_uct, vl_weight, prior_w=None,
                       *, lanes: int, max_depth: int, expand_threshold: int,
                       use_puct: bool, interpret: bool = False):
    """Batched fused selection: slabs ``[G, N]`` / ``[G, N, A_pad]``.

    Per-game traced scalars (``seed`` u32, ``c_uct`` / ``vl_weight`` /
    ``prior_w`` f32) arrive as ``[G]`` arrays; ``prior_w=None`` selects
    the non-blended program (static choice, as in ``uct_select``).
    """
    g, n = visit.shape
    a = prior.shape[-1]
    assert a % LANE == 0, a
    slab = pl.BlockSpec((1, n, a), lambda i: (i, 0, 0))
    pvec = pl.BlockSpec((1, lanes, max_depth), lambda i: (i, 0, 0))
    vec, col, lvec = _row(n), _row(1), _row(lanes)
    blend = prior_w is not None
    scalars = [seed[:, None], c_uct[:, None], vl_weight[:, None],
               prior_w[:, None] if blend else jnp.zeros((g, 1), jnp.float32)]
    lanes_out = jax.ShapeDtypeStruct((g, 1, lanes), jnp.int32)
    paths, depth, leaf, act, can_exp, vl = pl.pallas_call(
        functools.partial(_select_kernel, lanes=lanes, max_depth=max_depth,
                          expand_threshold=expand_threshold,
                          use_puct=use_puct, blend=blend),
        out_shape=(
            jax.ShapeDtypeStruct((g, lanes, max_depth), jnp.int32),  # paths
            lanes_out, lanes_out, lanes_out, lanes_out,  # depth/leaf/act/exp
            jax.ShapeDtypeStruct((g, 1, n), jnp.float32),            # vloss
        ),
        grid=(g,),
        in_specs=[vec, vec, vec, slab, slab, slab, vec, vec, vec,
                  col, col, col, col],
        out_specs=(pvec, lvec, lvec, lvec, lvec, vec),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="mcts_select",
    )(*map(_rows, (visit, value, vloss)), prior, legal, children,
      *map(_rows, (expanded, terminal, player)), *map(_rows, scalars))
    return paths, depth[:, 0], leaf[:, 0], act[:, 0], can_exp[:, 0], vl[:, 0]


def mcts_backup_pallas(visit, value, paths, val_sum, *, playouts: float,
                       interpret: bool = False):
    """Batched fused backup: ``paths [G, L, D]``, ``val_sum [G, L]``."""
    g, n = visit.shape
    _, lanes, d = paths.shape
    vec = _row(n)
    pvec = pl.BlockSpec((1, lanes, d), lambda i: (i, 0, 0))
    visit, value = pl.pallas_call(
        functools.partial(_backup_kernel, lanes=lanes, playouts=playouts),
        out_shape=(jax.ShapeDtypeStruct((g, 1, n), jnp.float32),
                   jax.ShapeDtypeStruct((g, 1, n), jnp.float32)),
        grid=(g,),
        in_specs=[pvec, _row(lanes), vec, vec],
        out_specs=(vec, vec),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="mcts_backup",
    )(paths, *map(_rows, (val_sum, visit, value)))
    return visit[:, 0], value[:, 0]
