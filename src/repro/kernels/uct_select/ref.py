"""Pure-jnp oracle for UCT/PUCT edge scoring under virtual loss.

This is the arithmetic every selection step of every lane performs at every
tree level — the paper's hottest loop (FUEGO spends its selection time here;
its low integer/scalar throughput on the Phi is one of the paper's findings).

``c_uct`` and ``vl_weight`` are *traced* operands — a Python float or a
per-row ``[B]`` array — never compile-time constants, so one compiled
program scores edges for any mix of search configurations (the per-slot
tournament multiplexing contract; see docs/ARCHITECTURE.md).  A scalar is
broadcast over the batch, which performs bit-identical arithmetic to the
historical static-constant path.

Semantics (matches ``repro.core.mcts.MCTS._edge_scores`` exactly):
  q    = (player * value - vloss * vl_weight) / max(n + vloss, 1)
  uct  : u = c * sqrt(log(max(parent_n, 2)) / max(n + vloss, 1))
         score = has_child ? q + u : FPU + prior
  puct : u = c * prior * sqrt(parent_n) / (1 + n + vloss)
         score = has_child ? q + u : c * prior * sqrt(parent_n)
  illegal edges score -BIG.

``prior_w`` (the evaluation-lane blend, PR 7) replaces the *static*
``use_puct`` branch with a traced per-row weight ``w``::

  score = (1 - w) * uct_score(uniform(legal)) + w * puct_score(prior)

The UCT half recomputes the uniform prior from the legal mask rather than
reading the stored (neural) prior, so ``w = 0`` is bit-identical to the
static UCT path over a uniform-prior tree whatever the evaluation lane
scattered into ``prior`` — both halves are always computed and the blend
is pure traced arithmetic, which is what lets one compiled dispatch serve
guided (``w > 0``) and unguided (``w = 0``) slots in the same pool.
"""
import jax.numpy as jnp

BIG = 1e9
FPU = 10.0


def per_row(x, b: int) -> jnp.ndarray:
    """Broadcast a scalar-or-``[B]`` traced knob to a ``[B, 1]`` column."""
    return jnp.broadcast_to(jnp.asarray(x, jnp.float32), (b,))[:, None]


def uct_scores_ref(child_visit, child_value, child_vloss, prior, legal,
                   has_child, parent_n, player, *, c_uct, vl_weight,
                   use_puct: bool, prior_w=None):
    """All inputs [B, A] except parent_n, player [B]; returns scores [B, A].

    ``c_uct`` / ``vl_weight`` are traced: float or [B] (broadcast per row).
    ``prior_w`` (float or [B], also traced) switches to the blended
    UCT/PUCT scoring described in the module docstring; ``use_puct`` is
    ignored when it is given.
    """
    b = child_visit.shape[0]
    return uct_scores_rows(
        child_visit, child_value, child_vloss, prior, legal, has_child,
        parent_n[:, None], player[:, None], per_row(c_uct, b),
        per_row(vl_weight, b),
        None if prior_w is None else per_row(prior_w, b), use_puct=use_puct)


def uct_scores_rows(child_visit, child_value, child_vloss, prior, legal,
                    has_child, parent_n, player, c, vlw, w, *,
                    use_puct: bool):
    """:func:`uct_scores_ref` with every per-row value as a ``[B, 1]`` column.

    The fused select kernel scores one ``[1, A]`` row at a time and calls
    this form directly: Mosaic cannot reshape a 1-D vector into a column.
    ``w`` is the ``prior_w`` column or ``None``.
    """
    n_eff = jnp.maximum(child_visit + child_vloss, 1.0)
    q = (player * child_value - child_vloss * vlw) / n_eff
    if w is not None:
        m = legal.astype(jnp.float32)
        uniform = m / jnp.maximum(m.sum(-1, keepdims=True), 1.0)
        pn = jnp.maximum(parent_n, 2.0)
        u_uct = c * jnp.sqrt(jnp.log(pn) / n_eff)
        s_uct = jnp.where(has_child, q + u_uct, FPU + uniform)
        root_term = jnp.sqrt(parent_n)
        u_puct = c * prior * root_term / (1.0 + child_visit + child_vloss)
        s_puct = jnp.where(has_child, q + u_puct, c * prior * root_term)
        score = (1.0 - w) * s_uct + w * s_puct
    elif use_puct:
        root_term = jnp.sqrt(parent_n)
        u = c * prior * root_term / (1.0 + child_visit + child_vloss)
        score = jnp.where(has_child, q + u, c * prior * root_term)
    else:
        pn = jnp.maximum(parent_n, 2.0)
        u = c * jnp.sqrt(jnp.log(pn) / n_eff)
        score = jnp.where(has_child, q + u, FPU + prior)
    return jnp.where(legal, score, -BIG)
