"""Pallas kernel: batched UCT/PUCT edge scoring under virtual loss.

Hardware adaptation (DESIGN.md §2): FUEGO's selection walks pointers and does
scalar math per child — exactly what the paper found the Phi's in-order cores
to be slow at.  On TPU the per-node child statistics are already a dense
``[batch_of_nodes, actions]`` tile, so one VPU pass computes every child's
exploitation + exploration score; the transcendentals (log/sqrt) vectorise
over the 8x128 VREG lanes.

Tiling: one grid step owns a ``(ROWS, A_pad)`` tile of each [B, A] statistic
(A padded to a lane multiple of 128 by ``ops.py``).  Per-row scalars
(parent_n, player, and the *traced* search knobs c_uct / vl_weight) ride
along as ``(ROWS, 1)`` tiles and broadcast over the action lanes — so one
compiled kernel scores edges for any mix of per-row search configurations
(the tournament-multiplexing contract; only ``use_puct`` stays a Python
constant).  For the 9x9 Go action space (A=82 -> 128) and ROWS=8 that is
8 tiles x <= 4 KiB — tiny, letting many node-batches pipeline.

The evaluation lane (PR 7) adds a third per-row column, ``prior_w``: the
blended kernel computes *both* the UCT score (over the uniform prior
recomputed from the legal tile) and the PUCT score (over the stored
neural prior) in the same VPU pass and mixes them per row, so the guided
vs unguided choice is data, not a compiled branch — one kernel serves any
mix of blend weights, and ``prior_w = 0`` reproduces the UCT program's
arithmetic bit for bit (ref.py documents why).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.uct_select.ref import BIG, FPU

ROWS = 8
LANE = 128


def _uct_kernel(visit_ref, value_ref, vloss_ref, prior_ref, legal_ref,
                hasc_ref, parent_ref, player_ref, cuct_ref, vlw_ref,
                out_ref, *, use_puct: bool):
    n = visit_ref[...]
    v = value_ref[...]
    vl = vloss_ref[...]
    prior = prior_ref[...]
    legal = legal_ref[...]
    has_child = hasc_ref[...]
    parent_n = parent_ref[...]          # (ROWS, 1)
    player = player_ref[...]            # (ROWS, 1)
    c_uct = cuct_ref[...]               # (ROWS, 1) traced per-row knob
    vl_weight = vlw_ref[...]            # (ROWS, 1) traced per-row knob

    n_eff = jnp.maximum(n + vl, 1.0)
    q = (player * v - vl * vl_weight) / n_eff
    if use_puct:
        root_term = jnp.sqrt(parent_n)
        u = c_uct * prior * root_term / (1.0 + n + vl)
        score = jnp.where(has_child != 0, q + u, c_uct * prior * root_term)
    else:
        pn = jnp.maximum(parent_n, 2.0)
        u = c_uct * jnp.sqrt(jnp.log(pn) / n_eff)
        score = jnp.where(has_child != 0, q + u, FPU + prior)
    out_ref[...] = jnp.where(legal != 0, score, -BIG)


def _uct_blend_kernel(visit_ref, value_ref, vloss_ref, prior_ref, legal_ref,
                      hasc_ref, nleg_ref, parent_ref, player_ref, cuct_ref,
                      vlw_ref, pw_ref, out_ref):
    n = visit_ref[...]
    v = value_ref[...]
    vl = vloss_ref[...]
    prior = prior_ref[...]
    legal = legal_ref[...]
    has_child = hasc_ref[...]
    n_legal = nleg_ref[...]             # (ROWS, 1) precomputed legal count
    parent_n = parent_ref[...]          # (ROWS, 1)
    player = player_ref[...]            # (ROWS, 1)
    c_uct = cuct_ref[...]               # (ROWS, 1) traced per-row knob
    vl_weight = vlw_ref[...]            # (ROWS, 1) traced per-row knob
    w = pw_ref[...]                     # (ROWS, 1) traced prior blend

    n_eff = jnp.maximum(n + vl, 1.0)
    q = (player * v - vl * vl_weight) / n_eff
    # UCT half over the uniform prior recomputed from the legal tile: the
    # per-row legal count is prefolded host-side (ops.py) so the padded
    # action lanes cannot perturb the reduction
    uniform = legal / jnp.maximum(n_legal, 1.0)
    pn = jnp.maximum(parent_n, 2.0)
    u_uct = c_uct * jnp.sqrt(jnp.log(pn) / n_eff)
    s_uct = jnp.where(has_child != 0, q + u_uct, FPU + uniform)
    # PUCT half over the stored (evaluation-lane) prior
    root_term = jnp.sqrt(parent_n)
    u_puct = c_uct * prior * root_term / (1.0 + n + vl)
    s_puct = jnp.where(has_child != 0, q + u_puct,
                       c_uct * prior * root_term)
    score = (1.0 - w) * s_uct + w * s_puct
    out_ref[...] = jnp.where(legal != 0, score, -BIG)


def uct_scores_pallas(child_visit, child_value, child_vloss, prior, legal,
                      has_child, parent_n, player, c_uct, vl_weight,
                      prior_w=None, n_legal=None, *,
                      use_puct: bool, interpret: bool = False):
    """Inputs [B, A_pad] (f32; masks as f32 0/1); per-row [B, 1] columns.

    ``parent_n`` / ``player`` / ``c_uct`` / ``vl_weight`` are the per-row
    columns — the last two are traced search knobs, not constants.  With
    ``prior_w`` (and its companion ``n_legal`` legal-count column) the
    blended kernel runs instead and ``use_puct`` is ignored.
    """
    b, a = child_visit.shape
    assert b % ROWS == 0 and a % LANE == 0, (b, a)
    tile = pl.BlockSpec((ROWS, a), lambda i: (i, 0))
    col = pl.BlockSpec((ROWS, 1), lambda i: (i, 0))
    if prior_w is not None:
        assert n_legal is not None
        return pl.pallas_call(
            _uct_blend_kernel,
            out_shape=jax.ShapeDtypeStruct((b, a), jnp.float32),
            grid=(b // ROWS,),
            in_specs=[tile, tile, tile, tile, tile, tile,
                      col, col, col, col, col, col],
            out_specs=tile,
            interpret=interpret,
            name="uct_scores_blend",
        )(child_visit, child_value, child_vloss, prior, legal, has_child,
          n_legal, parent_n, player, c_uct, vl_weight, prior_w)
    return pl.pallas_call(
        functools.partial(_uct_kernel, use_puct=use_puct),
        out_shape=jax.ShapeDtypeStruct((b, a), jnp.float32),
        grid=(b // ROWS,),
        in_specs=[tile, tile, tile, tile, tile, tile, col, col, col, col],
        out_specs=tile,
        interpret=interpret,
        name="uct_scores",
    )(child_visit, child_value, child_vloss, prior, legal, has_child,
      parent_n, player, c_uct, vl_weight)
