"""Jittable Go engine (the FUEGO substrate).

Fully vectorised, ``jax.jit``/``vmap``-compatible Go rules for an ``n x n``
board: flood-fill connected groups, exact liberty counting, captures, suicide
and simple-ko legality, true-eye detection for the playout policy, and
Tromp–Taylor (Chinese/area) scoring.

Representation
--------------
* ``board``: ``int8[n2]`` flattened, ``+1`` black / ``-1`` white / ``0`` empty.
* All neighbour/diagonal lookups go through precomputed tables padded with a
  sentinel index ``n2`` that maps to an off-board "wall" cell, so gathers never
  need bounds checks (the wall never matches any colour test that matters and
  scatters to it are discarded).
* Moves are ``0..n2-1`` for points and ``n2`` for pass.

The engine object holds only *static* numpy tables; every method is a pure
function of its arguments and can be wrapped in ``jit``/``vmap`` freely.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

EMPTY, BLACK, WHITE = 0, 1, -1
_OFF = 3  # wall cell "colour": matches neither player nor empty
NO_KO = -1


class GoState(NamedTuple):
    board: jax.Array       # int8[n2]
    to_play: jax.Array     # int8 scalar, +1 / -1
    ko: jax.Array          # int32 scalar, simple-ko forbidden point or -1
    pass_count: jax.Array  # int32 scalar
    move_count: jax.Array  # int32 scalar
    done: jax.Array        # bool scalar


def _build_tables(size: int):
    n2 = size * size
    nbr = np.full((n2, 4), n2, dtype=np.int32)
    diag = np.full((n2, 4), n2, dtype=np.int32)
    for r in range(size):
        for c in range(size):
            p = r * size + c
            for k, (dr, dc) in enumerate(((-1, 0), (1, 0), (0, -1), (0, 1))):
                rr, cc = r + dr, c + dc
                if 0 <= rr < size and 0 <= cc < size:
                    nbr[p, k] = rr * size + cc
            for k, (dr, dc) in enumerate(((-1, -1), (-1, 1), (1, -1), (1, 1))):
                rr, cc = r + dr, c + dc
                if 0 <= rr < size and 0 <= cc < size:
                    diag[p, k] = rr * size + cc
    return nbr, diag


class GoEngine:
    """Static-size Go rules engine; every method is jit/vmap-safe."""

    def __init__(self, size: int = 9, komi: float = 6.0):
        self.size = int(size)
        self.komi = float(komi)
        self.n2 = self.size * self.size
        self.num_actions = self.n2 + 1          # + pass
        self.pass_action = self.n2
        self.max_moves = 2 * self.n2            # hard game-length cap
        nbr, diag = _build_tables(self.size)
        self.nbr = jnp.asarray(nbr)             # int32[n2, 4], n2 = wall
        self.diag = jnp.asarray(diag)
        # number of on-board neighbours/diagonals per point
        self.nbr_valid = jnp.asarray((nbr < self.n2), dtype=jnp.int32)
        self.diag_valid = jnp.asarray((diag < self.n2), dtype=jnp.int32)
        # Static trip count for the min-label component fixpoint.  Hook +
        # one pointer-jump converges in O(log n2) rounds; stress-tested over
        # random boards plus adversarial serpentine/spiral/comb families
        # (worst observed: 10/16/19/27 rounds at sizes 5/9/13/19 vs bounds
        # 21/27/30/33 from this formula).
        self.label_rounds = 3 * max(1, (self.n2 - 1).bit_length()) + 6

    # -- state ----------------------------------------------------------------

    def init_state(self) -> GoState:
        return GoState(
            board=jnp.zeros((self.n2,), jnp.int8),
            to_play=jnp.int8(BLACK),
            ko=jnp.int32(NO_KO),
            pass_count=jnp.int32(0),
            move_count=jnp.int32(0),
            done=jnp.bool_(False),
        )

    def _pad(self, cells: jax.Array, wall_value) -> jax.Array:
        """Append the wall cell so sentinel gathers are safe."""
        return jnp.concatenate(
            [cells, jnp.full((1,), wall_value, cells.dtype)])

    # -- groups & liberties -----------------------------------------------------

    @jax.named_scope("go_flood_fill")
    def _min_label_components(self, active: jax.Array,
                              same: jax.Array) -> jax.Array:
        """Min-index connected-component labels over the neighbour graph.

        ``active`` is ``bool[n2]`` (cells that participate); ``same`` is
        ``bool[n2, 4]`` (which neighbour edges connect).  Returns
        ``int32[n2]`` labels: the smallest cell index in each component,
        ``n2`` for inactive cells — the same fixpoint the old data-dependent
        ``while_loop`` reached, but via a *static* ``fori_loop`` trip count
        (hook to the neighbour min, then one pointer jump per round,
        FastSV-style) so the loop is shaped for a Pallas port: fixed rounds,
        fixed-size gathers, no convergence flag.
        """
        n2 = self.n2
        ids0 = jnp.where(active, jnp.arange(n2, dtype=jnp.int32), n2)

        def body(_, ids):
            idp = self._pad(ids, n2)
            cand = jnp.where(same, idp[self.nbr], n2)     # hook: nbr min
            new = jnp.minimum(ids, cand.min(axis=1))
            newp = self._pad(new, n2)
            new = jnp.minimum(new, newp[new])             # pointer jump
            return jnp.where(active, new, n2)

        return jax.lax.fori_loop(0, self.label_rounds, body, ids0)

    @jax.named_scope("go_group_info")
    def group_info(self, board: jax.Array):
        """Connected components + exact per-group liberty counts.

        Returns
        -------
        ids : int32[n2]   root-cell index of each stone's group (n2 for empty)
        libs : int32[n2]  liberties of the group each stone belongs to
                          (0 for empty cells)
        """
        n2 = self.n2
        bp = self._pad(board, _OFF)                       # int8[n2+1]
        stone = board != EMPTY
        same = bp[self.nbr] == board[:, None]             # same colour as self
        ids = self._min_label_components(stone, same)

        # distinct-liberty counting: each empty cell credits each *distinct*
        # adjacent group exactly once.
        idp = self._pad(ids, n2)
        nb_ids = idp[self.nbr]                            # [n2, 4] group of each nbr
        empty = board == EMPTY
        # for empty cell e, neighbour k contributes iff it is a stone-group id
        # (< n2) and differs from all previous neighbour ids of e
        contrib = (nb_ids < n2) & empty[:, None]
        for k in range(1, 4):
            dup = jnp.zeros_like(contrib[:, k])
            for j in range(k):
                dup = dup | (nb_ids[:, k] == nb_ids[:, j])
            contrib = contrib.at[:, k].set(contrib[:, k] & ~dup)
        libs_by_root = jnp.zeros((n2 + 1,), jnp.int32).at[
            nb_ids.reshape(-1)].add(contrib.reshape(-1).astype(jnp.int32))
        libs = jnp.where(stone, libs_by_root[jnp.where(stone, ids, n2)], 0)
        return ids, libs

    # -- legality ---------------------------------------------------------------

    def _legal_points(self, state: GoState, libs: jax.Array) -> jax.Array:
        """Exact point legality from precomputed group liberties."""
        board = state.board
        bp = self._pad(board, _OFF)
        libp = self._pad(libs, 0)
        me = state.to_play
        nb_col = bp[self.nbr]                              # [n2, 4]
        nb_lib = libp[self.nbr]
        empty_nbr = (nb_col == EMPTY).any(axis=1)
        friend_spare = ((nb_col == me) & (nb_lib > 1)).any(axis=1)
        enemy_atari = ((nb_col == -me) & (nb_lib == 1)).any(axis=1)
        playable = (board == EMPTY) & (empty_nbr | friend_spare | enemy_atari)
        ko_mask = jnp.arange(self.n2, dtype=jnp.int32) != state.ko
        return playable & ko_mask & ~state.done

    def legal_moves(self, state: GoState) -> jax.Array:
        """Exact legality mask, ``bool[num_actions]`` (pass always legal)."""
        _, libs = self.group_info(state.board)
        pts = self._legal_points(state, libs)
        return jnp.concatenate([pts, jnp.ones((1,), jnp.bool_)])

    def true_eyes(self, board: jax.Array, color) -> jax.Array:
        """Heuristic true-eye mask for ``color`` (playout move filter)."""
        bp = self._pad(board, _OFF)
        nb = bp[self.nbr]
        # every on-board neighbour is own colour (wall counts as own)
        nbrs_own = ((nb == color) | (nb == _OFF)).all(axis=1)
        dg = bp[self.diag]
        bad_diag = (dg == -color).astype(jnp.int32).sum(axis=1)
        n_valid_diag = self.diag_valid.sum(axis=1)
        # interior: at most 1 hostile diagonal; edge/corner: none
        limit = jnp.where(n_valid_diag == 4, 1, 0)
        return (board == EMPTY) & nbrs_own & (bad_diag <= limit)

    def playout_mask(self, state: GoState) -> jax.Array:
        """Playout policy support: legal and not filling own true eye."""
        legal = self.legal_moves(state)
        eyes = self.true_eyes(state.board, state.to_play)
        pts = legal[: self.n2] & ~eyes
        return jnp.concatenate([pts, jnp.ones((1,), jnp.bool_)])

    # -- playing a move -----------------------------------------------------------

    def play(self, state: GoState, move) -> GoState:
        """Apply a (legal) move; ``move == n2`` is pass."""
        move = jnp.asarray(move, jnp.int32)
        is_pass = (move >= self.n2) | state.done
        me = state.to_play
        pt = jnp.clip(move, 0, self.n2 - 1)

        placed = state.board.at[pt].set(me.astype(jnp.int8))
        board1 = jnp.where(is_pass, state.board, placed)

        _, libs = self.group_info(board1)
        cap = (board1 == -me) & (libs == 0) & ~is_pass
        ncap = cap.sum()
        board2 = jnp.where(cap, jnp.int8(EMPTY), board1)

        # simple ko: single capture by a lone stone that now has exactly the
        # captured point as its only liberty
        bp2 = self._pad(board2, _OFF)
        nb2 = bp2[self.nbr[pt]]
        lone = ~(nb2 == me).any()
        one_lib = (nb2 == EMPTY).sum() == 1
        cap_idx = jnp.argmax(cap).astype(jnp.int32)
        ko_new = jnp.where((ncap == 1) & lone & one_lib, cap_idx,
                           jnp.int32(NO_KO))
        ko_new = jnp.where(is_pass, jnp.int32(NO_KO), ko_new)

        pass_count = jnp.where(is_pass, state.pass_count + 1, 0)
        move_count = state.move_count + jnp.where(state.done, 0, 1)
        done = state.done | (pass_count >= 2) | (move_count >= self.max_moves)
        return GoState(board=board2, to_play=(-me).astype(jnp.int8),
                       ko=ko_new, pass_count=pass_count.astype(jnp.int32),
                       move_count=move_count.astype(jnp.int32), done=done)

    # -- scoring ------------------------------------------------------------------

    def _reach(self, board: jax.Array, color) -> jax.Array:
        """Cells reachable from ``color`` stones through empty cells.

        Reformulated from mask-growth iteration to connected components of
        the *empty* cells: an empty cell is reached iff its empty-region
        contains a cell adjacent to a ``color`` stone.  Same result as the
        old ``while_loop`` growth, but on the static-trip-count label
        fixpoint shared with ``group_info``.
        """
        empty = board == EMPTY
        bp = self._pad(board, _OFF)
        nb_col = bp[self.nbr]                              # [n2, 4]
        same = empty[:, None] & (nb_col == EMPTY)
        ids = self._min_label_components(empty, same)
        adj = empty & (nb_col == color).any(axis=1)        # region seed cells
        seeded = jnp.zeros((self.n2 + 1,), jnp.int32).at[ids].add(
            adj.astype(jnp.int32))
        return (board == color) | (empty & (seeded[ids] > 0))

    def score(self, board: jax.Array) -> jax.Array:
        """Tromp–Taylor area score, black-positive, before komi."""
        rb = self._reach(board, BLACK)
        rw = self._reach(board, WHITE)
        empty = board == EMPTY
        black_pts = (board == BLACK).sum() + (empty & rb & ~rw).sum()
        white_pts = (board == WHITE).sum() + (empty & rw & ~rb).sum()
        return (black_pts - white_pts).astype(jnp.float32)

    def result(self, state: GoState, komi=None) -> jax.Array:
        """+1 black win / -1 white win / 0 draw, komi applied.

        ``komi`` may be a traced per-game value; ``None`` falls back to the
        engine's static komi (the historical program, bit for bit — half-
        integer komis are exact in f32 either way).
        """
        k = self.komi if komi is None else komi
        s = self.score(state.board) - k
        return jnp.sign(s)

    # -- playouts ----------------------------------------------------------------

    def _play_with_info(self, state: GoState, move, ids: jax.Array,
                        libs: jax.Array) -> GoState:
        """Apply a *legal* move reusing the pre-move group analysis.

        §Perf (fuego hillclimb): the placed stone removes exactly one
        liberty (itself) from each adjacent enemy group, so a group is
        captured iff its pre-move liberties were 1 — no post-move flood
        fill needed.  Halves the per-playout-move fixpoint work.
        """
        move = jnp.asarray(move, jnp.int32)
        is_pass = (move >= self.n2) | state.done
        me = state.to_play
        pt = jnp.clip(move, 0, self.n2 - 1)

        placed = state.board.at[pt].set(me.astype(jnp.int8))
        board1 = jnp.where(is_pass, state.board, placed)

        bp = self._pad(state.board, _OFF)
        idp = self._pad(ids, self.n2)
        libp = self._pad(libs, 0)
        nbrs = self.nbr[pt]                                # [4]
        cap_mask = jnp.zeros((self.n2,), jnp.bool_)
        for k in range(4):
            q = nbrs[k]
            hit = (bp[q] == -me) & (libp[q] == 1)
            cap_mask = cap_mask | (hit & (ids == idp[q]))
        cap_mask = cap_mask & ~is_pass
        ncap = cap_mask.sum()
        board2 = jnp.where(cap_mask, jnp.int8(EMPTY), board1)

        bp2 = self._pad(board2, _OFF)
        nb2 = bp2[nbrs]
        lone = ~(nb2 == me).any()
        one_lib = (nb2 == EMPTY).sum() == 1
        cap_idx = jnp.argmax(cap_mask).astype(jnp.int32)
        ko_new = jnp.where((ncap == 1) & lone & one_lib, cap_idx,
                           jnp.int32(NO_KO))
        ko_new = jnp.where(is_pass, jnp.int32(NO_KO), ko_new)

        pass_count = jnp.where(is_pass, state.pass_count + 1, 0)
        move_count = state.move_count + jnp.where(state.done, 0, 1)
        done = state.done | (pass_count >= 2) | (move_count >= self.max_moves)
        return GoState(board=board2, to_play=(-me).astype(jnp.int8),
                       ko=ko_new, pass_count=pass_count.astype(jnp.int32),
                       move_count=move_count.astype(jnp.int32), done=done)

    def playout_step(self, state: GoState, rng: jax.Array) -> GoState:
        """One uniform-random playout move (pass if nothing sensible).

        Fused: one ``group_info`` fixpoint serves both the legality mask
        and the capture bookkeeping of the chosen move.
        """
        ids, libs = self.group_info(state.board)
        pts = self._legal_points(state, libs)
        eyes = self.true_eyes(state.board, state.to_play)
        pts = pts & ~eyes
        n_ok = pts.sum()
        logits = jnp.where(pts, 0.0, -jnp.inf)
        pick = jax.random.categorical(rng, logits)
        move = jnp.where(n_ok > 0, pick, self.pass_action)
        return self._play_with_info(state, move, ids, libs)

    def random_playout(self, state: GoState, rng: jax.Array) -> GoState:
        """Play uniformly random moves until the game ends (bounded)."""

        def cond(carry):
            st, _ = carry
            return ~st.done

        def body(carry):
            st, key = carry
            key, sub = jax.random.split(key)
            return self.playout_step(st, sub), key

        final, _ = jax.lax.while_loop(cond, body, (state, rng))
        return final

    def playout_value(self, state: GoState, rng: jax.Array,
                      komi=None) -> jax.Array:
        """Black-perspective playout outcome in ``{-1, 0, +1}``."""
        return self.result(self.random_playout(state, rng), komi)

    # -- convenience ----------------------------------------------------------------

    @functools.partial(jax.jit, static_argnums=0)
    def jit_play(self, state: GoState, move) -> GoState:
        return self.play(state, move)

    @functools.partial(jax.jit, static_argnums=0)
    def jit_legal(self, state: GoState) -> jax.Array:
        return self.legal_moves(state)

    def render(self, board) -> str:
        chars = {EMPTY: ".", BLACK: "X", WHITE: "O"}
        b = np.asarray(board).reshape(self.size, self.size)
        return "\n".join(" ".join(chars[int(v)] for v in row) for row in b)
