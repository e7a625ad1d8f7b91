"""Plain Go rules in numpy: the benchmark's reference for legality,
captures, ko, playout moves and area scoring.

Two forms of the same rules:

* ``bfs_groups`` / ``ref_play`` / ``ref_legal``: the pure-Python BFS
  reference, copied from ``tests/test_go_property.py``.  Slow and plain;
  the tests of this package hold the fast form to it.
* :class:`Rules`: the same rules vectorised with numpy and
  ``scipy.ndimage.label``, fast enough to generate traffic and to play
  the reference search's random playouts.  It implements the semantics
  of a 9x9 tournament Go engine with Chinese (area) scoring, positional
  simple ko, no suicide, a game cap of ``2 * n * n`` moves, and the
  playout policy "uniform over legal moves that do not fill one's own
  true eye".

Nothing here imports the program under test.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np
from scipy import ndimage

EMPTY, BLACK, WHITE = 0, 1, -1
OFF = 3            # wall cell: matches neither colour nor empty
NO_KO = -1


# ------------------------------------------- BFS reference (copied verbatim)


def _nbrs(p, size):
    r, c = divmod(p, size)
    out = []
    if r > 0:
        out.append(p - size)
    if r < size - 1:
        out.append(p + size)
    if c > 0:
        out.append(p - 1)
    if c < size - 1:
        out.append(p + 1)
    return out


def bfs_groups(board, size):
    """(ids, libs): min-index group roots + exact per-group liberties."""
    n2 = size * size
    ids = np.full(n2, n2, np.int32)
    libs = np.zeros(n2, np.int32)
    seen = set()
    for p in range(n2):
        if board[p] == 0 or p in seen:
            continue
        comp, q = [p], [p]
        seen.add(p)
        while q:
            u = q.pop()
            for v in _nbrs(u, size):
                if board[v] == board[p] and v not in seen:
                    seen.add(v)
                    comp.append(v)
                    q.append(v)
        lib = {v for u in comp for v in _nbrs(u, size) if board[v] == 0}
        for u in comp:
            ids[u] = min(comp)
            libs[u] = len(lib)
    return ids, libs


def ref_play(board, size, p, me):
    """Place ``me`` at empty ``p``; resolve captures.  Returns the new
    board, or None if the move is suicide."""
    b = board.copy()
    b[p] = me
    _, libs = bfs_groups(b, size)
    captured = (b == -me) & (libs == 0)
    b[captured] = 0
    _, libs = bfs_groups(b, size)
    if libs[p] == 0:
        return None
    return b


def ref_legal(board, size, me, ko):
    """Semantic legality: empty, not the ko point, and not suicide."""
    n2 = size * size
    out = np.zeros(n2 + 1, bool)
    out[n2] = True                                    # pass
    for p in range(n2):
        if board[p] != 0 or p == ko:
            continue
        out[p] = ref_play(board, size, p, me) is not None
    return out


# ------------------------------------------------------ vectorised rules


class State(NamedTuple):
    board: np.ndarray      # int8[n2]
    to_play: int           # +1 black, -1 white
    ko: int                # forbidden point or NO_KO
    pass_count: int
    move_count: int
    done: bool


class Rules:
    """Go on an ``n x n`` board; moves ``0..n2-1`` are points, ``n2`` pass."""

    def __init__(self, size: int):
        self.size = size
        self.n2 = n2 = size * size
        self.pass_action = n2
        self.num_actions = n2 + 1
        self.max_moves = 2 * n2
        nbr = np.full((n2, 4), n2, np.int64)
        diag = np.full((n2, 4), n2, np.int64)
        for r in range(size):
            for c in range(size):
                p = r * size + c
                for k, (dr, dc) in enumerate(((-1, 0), (1, 0), (0, -1),
                                              (0, 1))):
                    if 0 <= r + dr < size and 0 <= c + dc < size:
                        nbr[p, k] = (r + dr) * size + c + dc
                for k, (dr, dc) in enumerate(((-1, -1), (-1, 1), (1, -1),
                                              (1, 1))):
                    if 0 <= r + dr < size and 0 <= c + dc < size:
                        diag[p, k] = (r + dr) * size + c + dc
        self.nbr, self.diag = nbr, diag
        self.eye_limit = np.where((diag < n2).sum(1) == 4, 1, 0)
        self.points = np.arange(n2)
        # 4-connectivity within each plane of a [2, n, n] stack (black,
        # white), none across: one labelling pass finds both colours' groups
        self.planes = np.zeros((3, 3, 3), bool)
        self.planes[1] = ndimage.generate_binary_structure(2, 1)

    def initial(self) -> State:
        return State(np.zeros(self.n2, np.int8), BLACK, NO_KO, 0, 0, False)

    def _pad(self, cells, wall):
        return np.concatenate([cells, np.asarray([wall], cells.dtype)])

    def groups(self, board):
        """(ids, libs): a group id per stone (0 on empty points) and the
        liberties of each point's group (0 on empty points)."""
        grid = board.reshape(self.size, self.size)
        lab, n = ndimage.label(np.stack([grid == BLACK, grid == WHITE]),
                               structure=self.planes)
        ids = (lab[0] + lab[1]).reshape(-1)
        idp = np.concatenate([ids, [0]])
        empty = np.flatnonzero(board == EMPTY)
        adj = np.zeros((n + 1, self.n2), bool)
        for k in range(4):
            adj[idp[self.nbr[empty, k]], empty] = True
        adj[0] = False
        libs_g = adj.sum(1)
        return ids, np.where(ids > 0, libs_g[ids], 0)

    def legal_points(self, st: State, libs) -> np.ndarray:
        bp = self._pad(st.board, OFF)
        libp = np.concatenate([libs, [0]])
        nb_col, nb_lib = bp[self.nbr], libp[self.nbr]
        me = st.to_play
        ok = (st.board == EMPTY) & (
            (nb_col == EMPTY).any(1)
            | ((nb_col == me) & (nb_lib > 1)).any(1)
            | ((nb_col == -me) & (nb_lib == 1)).any(1))
        ok &= self.points != st.ko
        return ok & (not st.done)

    def legal(self, st: State) -> np.ndarray:
        """bool[n2 + 1]: legal moves, pass always legal."""
        _, libs = self.groups(st.board)
        return np.concatenate([self.legal_points(st, libs), [True]])

    def true_eyes(self, board, color) -> np.ndarray:
        bp = self._pad(board, OFF)
        nb = bp[self.nbr]
        own = ((nb == color) | (nb == OFF)).all(1)
        bad = (bp[self.diag] == -color).sum(1)
        return (board == EMPTY) & own & (bad <= self.eye_limit)

    def play(self, st: State, move: int, ids=None, libs=None) -> State:
        """Apply a legal move (``n2`` passes); captures, ko, game end."""
        if ids is None:
            ids, libs = self.groups(st.board)
        me = st.to_play
        is_pass = move >= self.n2 or st.done
        board = st.board.copy()
        ko = NO_KO
        if not is_pass:
            board[move] = me
            cap = np.zeros(self.n2, bool)
            for q in self.nbr[move]:
                if q < self.n2 and st.board[q] == -me and libs[q] == 1:
                    cap |= ids == ids[q]
            ncap = int(cap.sum())
            board[cap] = EMPTY
            nb2 = self._pad(board, OFF)[self.nbr[move]]
            if ncap == 1 and not (nb2 == me).any() \
                    and int((nb2 == EMPTY).sum()) == 1:
                ko = int(np.argmax(cap))
        pass_count = st.pass_count + 1 if is_pass else 0
        move_count = st.move_count + (0 if st.done else 1)
        done = st.done or pass_count >= 2 or move_count >= self.max_moves
        return State(board, -me, ko, pass_count, move_count, done)

    def playout_moves(self, st: State):
        """(legal-and-not-own-eye mask over points, ids, libs)."""
        ids, libs = self.groups(st.board)
        pts = self.legal_points(st, libs) & ~self.true_eyes(st.board,
                                                            st.to_play)
        return pts, ids, libs

    def area(self, board) -> float:
        """Area score, black minus white, before komi."""
        grid = board.reshape(self.size, self.size)
        regions, n = ndimage.label(grid == EMPTY, structure=self.planes[1])
        regions = regions.reshape(-1)
        bp = self._pad(board, OFF)
        touch_b = np.zeros(n + 1, bool)
        touch_w = np.zeros(n + 1, bool)
        empty = board == EMPTY
        nbc = bp[self.nbr]
        touch_b[regions[empty & (nbc == BLACK).any(1)]] = True
        touch_w[regions[empty & (nbc == WHITE).any(1)]] = True
        rb, rw = touch_b[regions] & empty, touch_w[regions] & empty
        black = int((board == BLACK).sum() + (rb & ~rw).sum())
        white = int((board == WHITE).sum() + (rw & ~rb).sum())
        return float(black - white)

    def result(self, st: State, komi: float) -> float:
        """+1 black wins, -1 white wins, 0 a draw (float32 arithmetic)."""
        s = np.float32(self.area(st.board)) - np.float32(komi)
        return float(np.sign(s))

    def same(self, a: State, b: State) -> bool:
        return (np.array_equal(a.board, b.board) and a.to_play == b.to_play
                and a.ko == b.ko and a.pass_count == b.pass_count
                and a.move_count == b.move_count and a.done == b.done)
