"""Plain reference of one unguided tree-parallel MCTS move search.

One game, one tree, written straight from the algorithm the
configurations state (Fuego-style UCT with virtual loss over ``lanes``
parallel descents per iteration, random playouts, area scoring):

* an iteration runs ``lanes`` descents one after another; each descent
  adds a virtual loss to every node on its path, which later descents
  of the same iteration see; the iteration then plays one random playout
  per descent and backs every result up at once, clearing the losses;
* edge score ``q + c * sqrt(log(max(N, 2)) / max(n + vl, 1))`` with
  ``q = (player * W - vl * vl_weight) / max(n + vl, 1)``; an edge with no
  child scores the first-play urgency ``10 + prior`` (uniform prior over
  legal moves), an illegal edge ``-1e9``; ties are broken by adding
  ``U[0, 1) * 1e-3`` drawn per node visit;
* a descent stops at an edge with no child and expands it once the node
  has been visited (a node is created per expansion until the arena of
  ``max_nodes`` is full);
* playouts are uniform over legal moves that do not fill one's own true
  eye, until two passes or the game cap; the result is the sign of the
  area score minus komi, from Black's side;
* the move is the most visited legal root move.

Random numbers follow the key discipline of the search service's RNG
contract, with ``jax.random`` on the host's CPU: ``split(key,
iterations)`` per search, ``split(key, lanes + 1)`` per iteration (the
last key feeds the playouts, ``split(k, lanes)``), and ``key, sub =
split(key)`` per descent level and per playout move.  The Go rules are
:mod:`.gorules`; nothing here imports the program under test.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .gorules import Rules, State

FPU = np.float32(10.0)
BIG = np.float32(1e9)
NOISE = np.float32(1e-3)
UNVISITED = -1
MAX_DEPTH = 64


def _cpu(x):
    return jax.device_put(x, jax.devices("cpu")[0])


@functools.partial(jax.jit, static_argnums=1)
def _split(key, n):
    return jax.random.split(key, n)


@functools.partial(jax.jit, static_argnums=1)
def _descent_noise(key, actions):
    key, sub = jax.random.split(key)
    return key, jax.random.uniform(sub, (actions,))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _playout_gumbel(keys, steps, points):
    """Per playout key: the gumbel draw of every move it may play."""
    def chain(key):
        def body(k, _):
            k, sub = jax.random.split(k)
            return k, jax.random.gumbel(sub, (points,), jnp.float32)
        return jax.lax.scan(body, key, None, length=steps)[1]
    return jax.vmap(chain)(keys)


class SearchResult(NamedTuple):
    action: int
    root_visits: np.ndarray    # f32[A]
    tree_nodes: int


class RefSearch:
    """Searches with one configuration: lanes, iteration bound, capacity."""

    def __init__(self, size: int, lanes: int, max_sims: int, max_nodes: int,
                 c_uct: float = 0.9, vl_weight: float = 1.0,
                 playout_cap: int = 0):
        """``playout_cap > 0`` breaks the rules of the search on purpose
        (the benchmark's control): playouts stop after that many moves and
        score the board as it stands."""
        self.rules = Rules(size)
        self.playout_cap = playout_cap
        self.lanes = lanes
        self.iterations = max(1, max_sims // lanes)
        self.max_nodes = max_nodes
        self.c = np.float32(c_uct)
        self.vlw = np.float32(vl_weight)

    # ------------------------------------------------------------ playouts

    def playout(self, st: State, gumbel: np.ndarray, komi: float) -> float:
        r = self.rules
        t = 0
        cap = self.playout_cap or r.max_moves
        while not st.done and t < cap:
            pts, ids, libs = r.playout_moves(st)
            if pts.any():
                move = int(np.argmax(np.where(pts, gumbel[t], -np.inf)))
            else:
                move = r.pass_action
            st = r.play(st, move, ids, libs)
            t += 1
        return r.result(st, komi)

    # -------------------------------------------------------------- search

    def search(self, root: State, key, sims: int, komi: float
               ) -> SearchResult:
        r, A, N = self.rules, self.rules.num_actions, self.max_nodes
        visit = np.zeros(N, np.float32)
        visit[0] = 1.0
        value = np.zeros(N, np.float32)
        vloss = np.zeros(N, np.float32)
        children = np.full((N, A), UNVISITED, np.int64)
        legal = np.zeros((N, A), bool)
        prior = np.zeros((N, A), np.float32)
        expanded = np.zeros(N, bool)
        terminal = np.zeros(N, bool)
        states = [None] * N

        def install(i, st):
            states[i] = st
            legal[i] = r.legal(st)
            m = legal[i].astype(np.float32)
            prior[i] = m / np.float32(max(m.sum(), 1.0))
            expanded[i] = not st.done
            terminal[i] = st.done

        install(0, root)
        size = 1
        iters = int(np.clip(sims // self.lanes, 1, self.iterations)) \
            if sims > 0 else self.iterations
        ikeys = np.asarray(_split(_cpu(np.asarray(key, np.uint32)),
                                  self.iterations))
        for it in range(iters):
            keys = np.asarray(_split(_cpu(ikeys[it]), self.lanes + 1))
            paths, leaves = [], []
            for lane in range(self.lanes):
                node, depth, lkey = 0, 0, _cpu(keys[lane])
                path = [0]
                act = r.pass_action
                while True:
                    lkey, u = _descent_noise(lkey, A)
                    kids = children[node]
                    has = kids != UNVISITED
                    ci = np.maximum(kids, 0)
                    n, v, vl = visit[ci], value[ci], vloss[ci]
                    player = np.float32(states[node].to_play)
                    parent_n = visit[node] + vloss[node]
                    n_eff = np.maximum(n + vl, np.float32(1.0))
                    q = (player * v - vl * self.vlw) / n_eff
                    pn = np.maximum(parent_n, np.float32(2.0))
                    uu = self.c * np.sqrt(np.log(pn) / n_eff)
                    score = np.where(has, q + uu, FPU + prior[node])
                    score = np.where(legal[node], score, -BIG)
                    score = score + np.asarray(u, np.float32) * NOISE
                    act = int(np.argmax(score))
                    child = int(kids[act])
                    if child == UNVISITED:
                        break
                    depth += 1
                    path.append(child)
                    node = child
                    if terminal[child] or not expanded[child] \
                            or depth >= MAX_DEPTH - 1:
                        break
                leaf = node
                if (children[node, act] == UNVISITED and not terminal[node]
                        and visit[node] + vloss[node] >= 1.0
                        and expanded[node]):
                    if size < N:
                        leaf = size
                        size += 1
                        children[node, act] = leaf
                        install(leaf, r.play(states[node], act))
                        path.append(leaf)
                for p in path:
                    vloss[p] += 1.0
                paths.append(path)
                leaves.append(leaf)
            pkeys = _split(_cpu(keys[self.lanes]), self.lanes)
            steps = r.max_moves
            gum = np.asarray(_playout_gumbel(pkeys, steps, r.n2))
            for lane in range(self.lanes):
                val = np.float32(self.playout(states[leaves[lane]],
                                              gum[lane], komi))
                for p in paths[lane]:
                    visit[p] += np.float32(1.0)
                    value[p] += val
            vloss[:] = 0.0
        kids = children[0]
        visits = np.where(kids == UNVISITED, np.float32(0.0),
                          visit[np.maximum(kids, 0)]).astype(np.float32)
        masked = np.where(legal[0], visits, np.float32(-1.0))
        action = int(np.argmax(masked))
        if masked[action] <= 0:
            action = int(np.argmax(legal[0]))
        return SearchResult(action, visits, size)
