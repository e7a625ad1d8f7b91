"""Parallel MCTS: tree / root / leaf parallelism with virtual loss.

This is the paper's algorithm (FUEGO-style tree parallelisation with virtual
loss and lock-free backups, Chaslot et al. 2008 / Enzenberger & Müller 2010)
reformulated for a SIMD machine:

* A "thread" is a **lane**.  One search *iteration* selects ``lanes`` leaves
  from the shared tree, runs all their playouts as a single ``vmap`` batch,
  and backs all results up with exact ``scatter-add``.
* Virtual loss is applied **sequentially within an iteration** via
  ``lax.scan`` over lanes: lane *i* selects under the statistics plus the
  in-flight virtual losses of lanes *< i*, exactly the decorrelation the Phi
  threads got from seeing each other's in-flight descents.  Lanes also see
  nodes expanded by earlier lanes of the same iteration.
* Backups clear the virtual loss (FUEGO removes it at backup time).

With a fixed *time* budget the paper's "2× threads" player performs 2× the
playouts per move at the price of staler selection statistics — the search
overhead the self-play experiments measure.  Here: iterations are the time
analogue and ``lanes`` the thread count, so ``sims/move = iterations x lanes``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.config import MCTSConfig
from repro.core import tree as tree_lib
from repro.core.tree import Tree, UNVISITED
from repro.go.board import GoEngine, GoState

BIG = 1e9
FPU = 10.0  # first-play urgency: unvisited edges are searched eagerly


class SearchOutput(NamedTuple):
    """Raw output of one move search (batched over games by search_batch)."""
    tree: Tree
    action: jax.Array          # chosen move (argmax root visits)
    root_visits: jax.Array     # f32[A] visit distribution at the root
    root_values: jax.Array     # f32[A] mean black-perspective values


class SearchParams(NamedTuple):
    """Traced per-search UCT knobs (the tournament-multiplexing contract).

    Passed to :meth:`MCTS.search_batch` as ``f32[G]`` arrays (one value per
    game; inside a search the scalar broadcasts over every lane and tree
    level), or left ``None`` to use this player's static ``MCTSConfig``
    values.  All fields are *traced*: changing them never recompiles, and
    passing arrays equal to the config constants is bit-identical to
    ``params=None`` (pinned in tests/test_multiplex.py).

    ``prior_w`` is the evaluation-lane UCT<->PUCT blend weight (PR 7).
    Its *presence* selects the blended scoring program (one compiled
    dispatch then serves guided ``w > 0`` and unguided ``w = 0`` slots);
    its *values* are traced.  ``None`` keeps the seed's static scoring
    path — except under an ``evaluator``, where it defaults to the
    config's ``prior_weight``.  ``w = 0`` rows are bit-identical to the
    static no-eval search (tests/test_evaluator.py pins this).

    ``komi`` (PR 10) multiplexes scoring the same way: ``None`` keeps the
    engine's static komi (the historical program, bit for bit); an
    ``f32[G]`` array threads a traced per-game komi into every playout
    outcome, so one compiled dispatch serves every komi bucket.  Like
    ``prior_w``, presence selects the program (a pytree-structure
    change); values never recompile, and an array equal to the engine
    constant is value-bit-identical to ``None`` (half-integer komis are
    exact in f32, and komi never touches the RNG stream).
    """
    c_uct: jax.Array           # f32[G] exploration constant
    vl_weight: jax.Array       # f32[G] virtual-loss weight in the Q term
    prior_w: Optional[jax.Array] = None  # f32[G] eval-lane prior blend
    komi: Optional[jax.Array] = None     # f32[G] traced per-game komi


class MCTS:
    """Search driver bound to an engine + config (methods jit/vmap-safe).

    Public API (everything else is private; the pre-service five-method
    surface — ``search`` / ``search_root_parallel`` / ``best_move`` /
    ``jit_best_move`` — was removed once every caller routed through
    ``search_batch`` or the SearchService/GoService dispatchers):

    ==================  ======================================================
    ``search_batch``    one full move search per game over a leading game
                        axis, with a traced per-game ``sims`` budget and
                        traced per-game ``SearchParams`` (c_uct, vl_weight,
                        prior_w)
    ``init_tree_batch`` batch of per-game tree arenas under this player's
                        engine / capacity / priors
    ==================  ======================================================

    Recompile contract: the config fixes the compiled search *shape*
    (lanes, iteration bound, tree capacity, board); ``sims`` and
    ``SearchParams`` are data.  One MCTS player therefore serves
    arbitrarily many (c_uct, virtual_loss, sims, prior_weight)
    configurations with a single trace — the SearchService multiplexing
    contract (docs/ARCHITECTURE.md).

    Evaluation lane (PR 7): pass ``evaluator=`` (an
    :class:`repro.core.evaluator.EvalService`) to run every iteration's
    selected leaves through a jitted policy/value net.  Roots and leaves
    then carry net priors, edge scoring blends UCT with PUCT under the
    traced ``prior_w`` weight, and net values mix into playout returns.
    The evaluator's params are baked into the compiled search as
    constants — rebuild the player (and any service above it) after a
    training step updates them.
    """

    def __init__(self, engine: GoEngine, cfg: MCTSConfig,
                 prior_fn=None, value_fn=None, use_puct: bool = False,
                 max_depth: int = 64, evaluator=None, fused: bool = False):
        self.engine = engine
        self.cfg = cfg
        self.fused = fused            # route search_batch through mcts_step
        self.evaluator = evaluator    # optional EvalService (core/evaluator.py)
        if evaluator is not None:
            if value_fn is not None:
                raise ValueError(
                    "evaluator and value_fn are mutually exclusive: the "
                    "evaluator's value head already mixes into playout "
                    "returns (weight = value_weight * prior_w)")
            if prior_fn is None:
                prior_fn = evaluator.prior_fn
            # children get a uniform prior at allocation; the batched
            # leaf evaluation of the same iteration overwrites it (the
            # scatter in _simulate) — per-node prior_fn calls inside the
            # sequential lane scan would serialise the net
            self._expand_prior_fn = None
        else:
            self._expand_prior_fn = prior_fn
        self.prior_fn = prior_fn      # optional policy hook: state, legal -> prior
        self.value_fn = value_fn      # optional value hook replacing playouts
        self.use_puct = use_puct
        self.max_depth = max_depth
        if cfg.parallelism == "tree":
            div = cfg.lanes * max(1, cfg.leaf_playouts)
        elif cfg.parallelism == "leaf":
            div = max(1, cfg.leaf_playouts)
        else:  # root: each tree gets the full iteration budget / root_trees
            div = (max(1, cfg.root_trees)
                   * cfg.lanes * max(1, cfg.leaf_playouts))
        self._sims_divisor = div      # sims -> iterations conversion
        self.iterations = max(1, cfg.sims_per_move // div)

    # ------------------------------------------------------------------ select

    def _edge_scores(self, t: Tree, node, player, rng,
                     params: Optional[SearchParams] = None) -> jax.Array:
        """UCT/PUCT score for every action at ``node`` under virtual loss.

        Routed through ``kernels.uct_select.ops`` — the Pallas kernel on
        TPU, its oracle elsewhere — so search and kernel share one call
        site (see kernels/uct_select/kernel.py).  ``params`` carries the
        traced per-search (c_uct, vl_weight) scalars; ``None`` uses the
        static config values (bit-identical when the values agree).
        """
        from repro.kernels.uct_select.ops import uct_scores
        c, vlw, pw = self._resolve_params(params)
        kids = t.children[node]
        has_child = kids != UNVISITED
        cidx = jnp.maximum(kids, 0)
        parent_n = t.visit[node] + t.vloss[node]
        score = uct_scores(
            t.visit[cidx][None], t.value[cidx][None], t.vloss[cidx][None],
            t.prior[node][None], t.legal[node][None], has_child[None],
            parent_n[None], player[None],
            c_uct=c, vl_weight=vlw, prior_w=pw,
            use_puct=self.use_puct)[0]
        # random tie-break (the asynchronous-thread nondeterminism analogue)
        return score + jax.random.uniform(rng, score.shape) * 1e-3

    def _resolve_params(self, params: Optional[SearchParams]):
        """The traced (c_uct, vl_weight, prior_w) triple.

        Defaults come from the config; ``prior_w`` resolves to ``None``
        (static scoring program, the seed path) unless an evaluator is
        bound or the caller threads an explicit blend weight.
        """
        if params is None:
            pw = self.cfg.prior_weight if self.evaluator is not None else None
            return self.cfg.c_uct, self.cfg.virtual_loss, pw
        pw = params.prior_w
        if pw is None and self.evaluator is not None:
            pw = self.cfg.prior_weight
        return params.c_uct, params.vl_weight, pw

    def _select_lane(self, t: Tree, rng,
                     params: Optional[SearchParams] = None):
        """Walk root->leaf under UCT+virtual-loss; expand one node.

        Returns (tree, path i32[max_depth] node ids (-1 pad), playout node).
        """
        path0 = jnp.full((self.max_depth,), UNVISITED, jnp.int32).at[0].set(0)

        def cond(c):
            node, depth, _, _, stop = c
            return (~stop) & (depth < self.max_depth - 1)

        def body(c):
            node, depth, path, key, _ = c
            key, sub = jax.random.split(key)
            player = tree_lib.node_state(t, node).to_play.astype(jnp.float32)
            scores = self._edge_scores(t, node, player, sub, params)
            act = jnp.argmax(scores).astype(jnp.int32)
            child = t.children[node, act]
            # descend only through materialised, expandable children
            nxt = jnp.where(child == UNVISITED, node, child)
            stop = (child == UNVISITED) | t.terminal[child] \
                | ~t.expanded[jnp.maximum(child, 0)]
            depth = depth + jnp.where(child == UNVISITED, 0, 1)
            path = path.at[depth].set(nxt)
            # smuggle chosen action out via stop case
            return (jnp.where(stop & (child == UNVISITED), node, nxt),
                    depth, path, key, stop), act

        # hand-rolled while that also yields the last action
        def loop(carry):
            state, act = carry
            state, act = body(state)
            return (state, act)

        state = (jnp.int32(0), jnp.int32(0), path0, rng, jnp.bool_(False))
        act = jnp.int32(self.engine.pass_action)

        def wcond(carry):
            (node, depth, path, key, stop), _ = carry
            return (~stop) & (depth < self.max_depth - 1)

        (state, act) = jax.lax.while_loop(wcond, loop, (state, act))
        node, depth, path, key, stop = state

        # expand if we stopped at an unmaterialised edge of a non-terminal,
        # sufficiently-visited node
        can_expand = (t.children[node, act] == UNVISITED) \
            & ~t.terminal[node] \
            & (t.visit[node] + t.vloss[node] >= self.cfg.expand_threshold) \
            & t.expanded[node]

        def do_expand(t):
            with jax.named_scope("mcts_expand"):
                return tree_lib.allocate(self.engine, t, node, act,
                                         self._expand_prior_fn)

        t, new_idx = jax.lax.cond(
            can_expand, do_expand, lambda t: (t, node), t)
        depth = depth + jnp.where(can_expand & (new_idx != node), 1, 0)
        path = path.at[depth].set(new_idx)

        # apply virtual loss along the path (visible to later lanes)
        valid = path != UNVISITED
        safe = jnp.maximum(path, 0)
        t = t._replace(vloss=t.vloss.at[safe].add(
            jnp.where(valid, 1.0, 0.0)))
        return t, path, new_idx

    # --------------------------------------------------------------- simulate

    def _simulate(self, t: Tree, rng,
                  params: Optional[SearchParams] = None) -> Tree:
        """One iteration: ``lanes`` selects -> batched playouts -> backup.

        The traced ``params`` scalars broadcast over every lane: each of
        the ``lanes`` sequential selects scores edges under the same
        per-search (c_uct, vl_weight) pair.

        Under an ``evaluator`` the iteration also forms the evaluation
        batch: the ``lanes`` selected leaf states go through the policy/
        value net as one fixed-shape ``[L]`` forward (``[G, L]`` after the
        ``search_batch`` vmap — the superstep eval batch), the policy
        head's priors are scattered back over the leaves' prior rows, and
        the value head mixes into the playout returns with traced weight
        ``value_weight * prior_w`` (AlphaGo's lambda; terminal leaves keep
        their exact game result).  ``prior_w = 0`` leaves the returns
        bit-identical to the playout-only path.
        """
        L, P = self.cfg.lanes, max(1, self.cfg.leaf_playouts)
        keys = jax.random.split(rng, L + 1)

        def lane(t, key):
            t, path, leaf = self._select_lane(t, key, params)
            return t, (path, leaf)

        with jax.named_scope("mcts_select"):
            t, (paths, leaves) = jax.lax.scan(lane, t, keys[:L])

        # batched playouts: [L, P]
        pkeys = jax.random.split(keys[L], L * P).reshape(L, P, 2)
        leaf_states = jax.tree.map(lambda x: x[leaves], t.states)
        komi = None if params is None else params.komi
        with jax.named_scope("mcts_playout"):
            if self.value_fn is not None:
                vals = jax.vmap(self.value_fn)(leaf_states)      # [L]
                vals = jnp.repeat(vals[:, None], P, axis=1)
            elif komi is None:
                vals = jax.vmap(
                    lambda st, ks: jax.vmap(
                        lambda k: self.engine.playout_value(st, k))(ks)
                )(leaf_states, pkeys)                             # [L, P]
            else:
                # traced per-search komi (a scalar here: search_batch's
                # vmap peeled the game axis); broadcasts over every
                # lane/playout
                vals = jax.vmap(
                    lambda st, ks: jax.vmap(
                        lambda k: self.engine.playout_value(st, k, komi))(ks)
                )(leaf_states, pkeys)                             # [L, P]
            val_sum = vals.sum(axis=1)                        # black persp.

        prior = t.prior
        if self.evaluator is not None:
            # the superstep eval batch: one net forward over all L leaves
            net_prior, net_val = self.evaluator.policy_value(
                leaf_states, t.legal[leaves])
            _, _, pw = self._resolve_params(params)
            mix = jnp.asarray(pw, jnp.float32) * self.evaluator.value_weight
            # terminal leaves keep the exact game result; elsewhere blend
            # net value (already a sum-equivalent: x P playouts' worth)
            mix = jnp.where(t.terminal[leaves], 0.0, mix)          # [L]
            val_sum = (1.0 - mix) * val_sum + mix * (net_val * P)
            # duplicate leaf indices write identical rows (same state)
            prior = prior.at[leaves].set(net_prior)

        # exact scatter-add backup over all lanes at once
        with jax.named_scope("mcts_backup"):
            flat = paths.reshape(-1)
            ok = flat != UNVISITED
            safe = jnp.maximum(flat, 0)
            w = jnp.where(ok, 1.0, 0.0)
            vrep = jnp.repeat(val_sum, self.max_depth)
            return t._replace(
                visit=t.visit.at[safe].add(w * P),
                value=t.value.at[safe].add(jnp.where(ok, vrep, 0.0)),
                vloss=jnp.zeros_like(t.vloss),   # FUEGO: remove at backup
                prior=prior,
            )

    # ----------------------------------------------------------------- search

    def _iterations_for(self, sims: jax.Array) -> jax.Array:
        """Traced iteration budget for a per-request ``sims`` knob.

        ``sims <= 0`` means "this player's configured budget".  The static
        ``self.iterations`` stays the compiled loop bound; smaller budgets
        mask the tail iterations instead of recompiling (the ServeEngine
        temperature treatment applied to the search loop — changing a
        request's playout budget must not retrace the dispatcher).
        """
        sims = jnp.asarray(sims, jnp.int32)
        it = jnp.clip(sims // self._sims_divisor, 1, self.iterations)
        return jnp.where(sims > 0, it, jnp.int32(self.iterations))

    def _search(self, root: GoState, rng,
                sims: Optional[jax.Array] = None,
                params: Optional[SearchParams] = None) -> SearchOutput:
        """One full move search from ``root`` (single game).

        With ``sims=None`` this is the seed's exact static loop.  With a
        traced ``sims``, iterations ``>= iterations_for(sims)`` become
        no-ops via a select — bit-identical to the static loop whenever
        the requested budget equals the configured one, which the service
        oracle-equivalence tests pin.  ``params`` (traced per-search
        scalars after the search_batch vmap) likewise reproduces the
        ``None`` path bit-for-bit when it carries the config constants.
        """
        t = tree_lib.init_tree(self.engine, root, self.cfg.max_nodes,
                               None if self.prior_fn is None
                               else self.prior_fn(root,
                                                  self.engine.legal_moves(root)))
        keys = jax.random.split(rng, self.iterations)

        if sims is None:
            def it(i, t):
                return self._simulate(t, keys[i], params)
        else:
            iters = self._iterations_for(sims)

            def it(i, t):
                t2 = self._simulate(t, keys[i], params)
                live = i < iters
                # Mask only the search statistics and the allocation
                # cursor: a dead iteration must not move visit/value mass
                # (so the root distribution, chosen action, and reported
                # tree size equal a truncated search's exactly), but its
                # node *writes* are harmless — they land at or beyond the
                # reverted cursor with zero visits, which every live read
                # ignores.  Selecting two [N] arrays and a scalar instead
                # of the whole tree keeps the masked loop's overhead out
                # of the dispatch hot path.
                return t2._replace(
                    visit=jnp.where(live, t2.visit, t.visit),
                    value=jnp.where(live, t2.value, t.value),
                    size=jnp.where(live, t2.size, t.size))

        t = jax.lax.fori_loop(0, self.iterations, it, t)
        visits = tree_lib.root_action_visits(t)
        action = tree_lib.select_action(visits, t.legal[0])
        return SearchOutput(tree=t, action=action, root_visits=visits,
                            root_values=tree_lib.root_action_values(t))

    def search_batch(self, roots: GoState, rngs: jax.Array,
                     sims: Optional[jax.Array] = None,
                     params: Optional[SearchParams] = None) -> SearchOutput:
        """Batched move search: one independent tree per game.

        ``roots`` is a ``GoState`` batched over a leading game axis and
        ``rngs`` is ``u32[G, 2]`` — per-game RNG so any game's search is
        bit-identical to an unbatched search with the same key.  This is
        the hot path of the SearchService dispatcher (core/service.py):
        all G trees advance one full move search as a single vmapped
        program.

        Traced-vs-static contract (what does and does not recompile):

        * **static** — everything baked into this player's ``MCTSConfig``
          shape: ``lanes``, ``max_nodes``, ``sims_per_move`` (the compiled
          loop bound), board size, ``parallelism`` — plus the batch size
          ``G``.  Changing any of these retraces.
        * **traced** — ``sims`` (optional ``i32[G]`` per-game playout
          budget: ``<= 0`` selects the configured ``sims_per_move``;
          positive values are capped by it) and ``params`` (optional
          :class:`SearchParams` of ``f32[G]`` per-game ``c_uct`` /
          ``vl_weight`` / ``prior_w``).  Changing their *values* never
          recompiles, and passing the configured constants is
          bit-identical to ``None``.  The one structural exception is
          ``prior_w``: ``None`` vs array selects the scoring *program*
          (static vs blended — a pytree-structure change, so the two
          programs are separate jit cache entries), while its values —
          any per-game mix of guided/unguided weights — stay traced.

        Players built with ``fused=True`` route through the
        ``kernels/mcts_step`` superstep (:meth:`_search_fused_batch`) —
        a documented search *variant* with deferred expansion;
        ``fused=False`` (the default) is this exact historical program,
        bit for bit (tests/test_mcts_step.py pins both).
        """
        sims = None if sims is None else jnp.asarray(sims, jnp.int32)
        if params is None:
            if self.fused:
                return self._search_fused_batch(roots, rngs, sims)
            if sims is None:
                return jax.vmap(self._search)(roots, rngs)
            return jax.vmap(self._search)(roots, rngs, sims)
        params = SearchParams(jnp.asarray(params.c_uct, jnp.float32),
                              jnp.asarray(params.vl_weight, jnp.float32),
                              None if params.prior_w is None
                              else jnp.asarray(params.prior_w, jnp.float32),
                              None if params.komi is None
                              else jnp.asarray(params.komi, jnp.float32))
        if self.fused:
            return self._search_fused_batch(roots, rngs, sims, params)
        if sims is None:
            return jax.vmap(
                lambda r, k, p: self._search(r, k, None, p))(
                    roots, rngs, params)
        return jax.vmap(self._search)(roots, rngs, sims, params)

    # ---------------------------------------------------- fused superstep

    def _expand_batch(self, t: Tree, paths, depth, leaf, act, can_exp):
        """Grow every game's tree for all lanes at once (deferred expansion).

        The fused kernel selects over a frozen children table, so lanes
        that picked the same ``(leaf, action)`` edge collapse onto one new
        node: the first such lane allocates, the rest share its child.
        Slots come from an exclusive cumsum over the unique expansions;
        lanes whose slot would overflow the arena keep their parent as
        the playout node (the unfused ``allocate`` full-arena behaviour).
        Masked scatters use the out-of-bounds sentinel ``N`` — dropped by
        XLA scatter semantics — instead of a per-lane ``cond``, and the
        engine step runs as **one** vmapped ``[G, L]`` batch where the
        unfused lane scan played ``L`` sequential moves per game.

        Returns ``(tree, extended paths, playout leaves i32[G, L])``.
        """
        g, lanes = leaf.shape
        n = t.visit.shape[1]
        gi = jnp.arange(g)[:, None]
        li = jnp.arange(lanes, dtype=jnp.int32)[None, :]
        same = (leaf[:, :, None] == leaf[:, None, :]) \
            & (act[:, :, None] == act[:, None, :])
        rep = jnp.argmax(same & can_exp[:, None, :], axis=-1).astype(jnp.int32)
        uniq = can_exp & (rep == li)
        u32 = uniq.astype(jnp.int32)
        slots = t.size[:, None] + jnp.cumsum(u32, axis=1) - u32
        alloc = uniq & (slots < n)

        parents = jax.vmap(
            lambda st, i: jax.tree.map(lambda x: x[i], st))(t.states, leaf)
        childs = jax.vmap(jax.vmap(self.engine.play))(parents, act)
        legal = jax.vmap(jax.vmap(self.engine.legal_moves))(childs)
        if self._expand_prior_fn is not None:
            raw = jax.vmap(jax.vmap(self._expand_prior_fn))(childs, legal)
            prior = jax.vmap(jax.vmap(tree_lib.normalize_prior))(raw, legal)
        else:
            prior = jax.vmap(jax.vmap(tree_lib.uniform_prior))(legal)

        oob = jnp.where(alloc, slots, n)
        t = t._replace(
            children=t.children.at[
                gi, jnp.where(alloc, leaf, n), act].set(slots),
            parent=t.parent.at[gi, oob].set(leaf),
            action=t.action.at[gi, oob].set(act),
            legal=t.legal.at[gi, oob].set(legal),
            prior=t.prior.at[gi, oob].set(prior),
            expanded=t.expanded.at[gi, oob].set(~childs.done),
            terminal=t.terminal.at[gi, oob].set(childs.done),
            states=jax.tree.map(lambda buf, v: buf.at[gi, oob].set(v),
                                t.states, childs),
            size=t.size + alloc.sum(axis=1).astype(jnp.int32),
        )

        rep_alloc = jnp.take_along_axis(alloc, rep, axis=1)
        rep_slot = jnp.take_along_axis(slots, rep, axis=1)
        leaves = jnp.where(can_exp & rep_alloc, rep_slot, leaf)
        ext = (leaves != leaf).astype(jnp.int32)
        paths = paths.at[gi, li, depth + ext].set(leaves)
        return t, paths, leaves

    def _simulate_fused(self, t: Tree, keys, c, vlw, pw, komi=None) -> Tree:
        """One fused iteration over every game: kernel select -> batched
        expansion -> playouts/eval -> kernel backup.

        The ``kernels/mcts_step`` counterpart of :meth:`_simulate`:
        selection and backup run as single fused ops over the ``[G, ...]``
        tree slabs (Pallas on TPU, oracle on CPU) instead of a lane scan,
        and expansion/playouts batch over ``[G, L]``.  ``keys`` is
        ``u32[G, 2]``; ``c`` / ``vlw`` / ``pw`` are the resolved traced
        knobs (scalar or ``[G]``).
        """
        from repro.kernels.mcts_step.ops import mcts_backup, mcts_select
        lanes, p = self.cfg.lanes, max(1, self.cfg.leaf_playouts)
        g = t.visit.shape[0]
        gi = jnp.arange(g)[:, None]
        sub = jax.vmap(lambda k: jax.random.split(k, 2))(keys)   # [G, 2, 2]
        seeds = sub[:, 0, 0]                                     # u32[G]
        pkeys = jax.vmap(
            lambda k: jax.random.split(k, lanes * p))(sub[:, 1])
        pkeys = pkeys.reshape(g, lanes, p, 2)

        player = t.states.to_play.astype(jnp.float32)            # [G, N]
        with jax.named_scope("mcts_select"):
            paths, depth, leaf, act, can_exp, vloss = mcts_select(
                t.visit, t.value, t.vloss, t.prior, t.legal, t.children,
                t.expanded, t.terminal, player, seeds,
                c_uct=c, vl_weight=vlw, prior_w=pw,
                lanes=lanes, max_depth=self.max_depth,
                expand_threshold=int(self.cfg.expand_threshold),
                use_puct=self.use_puct)
        t = t._replace(vloss=vloss)
        with jax.named_scope("mcts_expand"):
            t, paths, leaves = self._expand_batch(
                t, paths, depth, leaf, act, can_exp)

        leaf_states = jax.vmap(
            lambda st, i: jax.tree.map(lambda x: x[i], st))(t.states, leaves)
        with jax.named_scope("mcts_playout"):
            if self.value_fn is not None:
                vals = jax.vmap(jax.vmap(self.value_fn))(leaf_states)  # [G, L]
                val_sum = vals * p
            elif komi is None:
                one = lambda st, ks: jax.vmap(                     # noqa: E731
                    lambda k: self.engine.playout_value(st, k))(ks)
                vals = jax.vmap(jax.vmap(one))(leaf_states, pkeys)  # [G, L, P]
                val_sum = vals.sum(axis=-1)
            else:
                km = jnp.broadcast_to(jnp.asarray(komi, jnp.float32), (g,))
                one = lambda st, ks, kv: jax.vmap(                 # noqa: E731
                    lambda k: self.engine.playout_value(st, k, kv))(ks)
                vals = jax.vmap(
                    lambda ls, pk, kv: jax.vmap(
                        lambda st, ks: one(st, ks, kv))(ls, pk)
                )(leaf_states, pkeys, km)                          # [G, L, P]
                val_sum = vals.sum(axis=-1)

        prior = t.prior
        if self.evaluator is not None:
            net_prior, net_val = jax.vmap(self.evaluator.policy_value)(
                leaf_states, t.legal[gi, leaves])
            mix = jnp.broadcast_to(jnp.asarray(pw, jnp.float32), (g,))[:, None]
            mix = mix * self.evaluator.value_weight
            mix = jnp.where(t.terminal[gi, leaves], 0.0, mix)      # [G, L]
            val_sum = (1.0 - mix) * val_sum + mix * (net_val * p)
            prior = prior.at[gi, leaves].set(net_prior)

        with jax.named_scope("mcts_backup"):
            visit, value = mcts_backup(t.visit, t.value, paths, val_sum,
                                       playouts=float(p))
        return t._replace(visit=visit, value=value,
                          vloss=jnp.zeros_like(t.vloss), prior=prior)

    def _search_fused_batch(self, roots: GoState, rngs: jax.Array,
                            sims: Optional[jax.Array] = None,
                            params: Optional[SearchParams] = None
                            ) -> SearchOutput:
        """Batched move search through the fused superstep kernels.

        Same signature/contract as the vmapped :meth:`_search` path of
        :meth:`search_batch` (traced ``sims`` masking, traced ``params``)
        — but a deliberate algorithm *variant*, not a bit-identical
        replacement: lanes see earlier lanes' virtual losses yet not
        their expansions (ref.py documents the deferred-expansion
        semantics), and tie-breaks come from the counter-based hash.
        """
        t = self.init_tree_batch(roots)
        keys = jax.vmap(
            lambda k: jax.random.split(k, self.iterations))(rngs)  # [G, I, 2]
        c, vlw, pw = self._resolve_params(params)
        komi = None if params is None else params.komi
        iters = None if sims is None else jax.vmap(self._iterations_for)(sims)

        def it(i, t):
            t2 = self._simulate_fused(t, keys[:, i], c, vlw, pw, komi)
            if iters is None:
                return t2
            live = (i < iters)[:, None]
            return t2._replace(
                visit=jnp.where(live, t2.visit, t.visit),
                value=jnp.where(live, t2.value, t.value),
                size=jnp.where(live[:, 0], t2.size, t.size))

        t = jax.lax.fori_loop(0, self.iterations, it, t)
        visits = jax.vmap(tree_lib.root_action_visits)(t)
        action = jax.vmap(tree_lib.select_action)(visits, t.legal[:, 0])
        return SearchOutput(tree=t, action=action, root_visits=visits,
                            root_values=jax.vmap(tree_lib.root_action_values)(t))

    def init_tree_batch(self, roots: GoState) -> Tree:
        """Batch of per-game tree arenas under this player's engine/config.

        Applies the player's ``prior_fn`` (when set) to every root, so
        service consumers never touch ``tree_lib`` directly.
        """
        priors = None
        if self.prior_fn is not None:
            legal = jax.vmap(self.engine.legal_moves)(roots)
            priors = jax.vmap(self.prior_fn)(roots, legal)
        return tree_lib.init_tree_batch(self.engine, roots,
                                        self.cfg.max_nodes, priors)

    # ------------------------------------------------------ internal variants

    def _search_root_parallel(self, root: GoState, rng) -> SearchOutput:
        """Root parallelism: ``root_trees`` independent searches, vote merge."""
        R = max(1, self.cfg.root_trees)
        keys = jax.random.split(rng, R)
        res = jax.vmap(lambda k: self._search(root, k))(keys)
        visits = res.root_visits.sum(axis=0)
        values = res.root_values.mean(axis=0)
        action = tree_lib.select_action(visits, self.engine.legal_moves(root))
        tree0 = jax.tree.map(lambda x: x[0], res.tree)
        return SearchOutput(tree=tree0, action=action, root_visits=visits,
                            root_values=values)

    def _best_move(self, root: GoState, rng) -> jax.Array:
        if self.cfg.parallelism == "root":
            return self._search_root_parallel(root, rng).action
        return self._search(root, rng).action

    @functools.partial(jax.jit, static_argnums=0)
    def _jit_best_move(self, root: GoState, rng) -> jax.Array:
        return self._best_move(root, rng)


def make_mcts(engine: GoEngine, cfg: MCTSConfig, **kw) -> MCTS:
    """Build an :class:`MCTS` player, normalising leaf-parallel configs."""
    if cfg.parallelism == "leaf":
        # leaf parallelism: a single selection lane, many playouts per leaf
        cfg = cfg if cfg.lanes == 1 else cfg.__class__(
            **{**cfg.__dict__, "lanes": 1,
               "leaf_playouts": max(cfg.leaf_playouts, cfg.lanes)})
    return MCTS(engine, cfg, **kw)
