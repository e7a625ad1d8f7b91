"""Property suite for the go/board.py flood-fill invariants.

The PR 8 rewrite replaced the data-dependent ``while_loop`` flood fills
(``group_info``, ``_reach``) with a static-trip-count min-label fixpoint
(``_min_label_components``).  These properties pin the rules against an
independent pure-Python BFS reference so the reshape cannot silently
change them:

* group ids are a partition rooted at the minimum same-colour index;
* per-stone liberty counts equal the BFS reference exactly;
* ``_reach`` / ``score`` agree with BFS reachability;
* capture / suicide / ko legality agrees with a semantic reference
  (place, resolve captures, then test the placed group's liberties);
* adversarial serpentine / comb / long-corridor boards — the topologies
  that maximise label-propagation diameter — still converge within the
  engine's static ``label_rounds`` bound.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.go import GoEngine
from repro.go.board import GoState, NO_KO

try:                                    # property tier (CI installs .[test])
    import hypothesis
    import hypothesis.strategies as st
    from hypothesis import given, settings
    SETTINGS = dict(max_examples=15, deadline=None,
                    suppress_health_check=list(hypothesis.HealthCheck))
except ImportError:                     # seeded-sweep tier still runs
    hypothesis = None


# ----------------------------------------------------- pure-Python reference


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


def bfs_reach(board, size, color):
    """Cells reachable from ``color`` stones through empty cells."""
    mask = board == color
    frontier = list(np.nonzero(mask)[0])
    while frontier:
        u = frontier.pop()
        for v in _nbrs(u, size):
            if board[v] == 0 and not mask[v]:
                mask[v] = True
                frontier.append(v)
    return mask


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


def _state(board, me=1, ko=NO_KO):
    return GoState(board=jnp.asarray(board), to_play=jnp.int8(me),
                   ko=jnp.int32(ko), pass_count=jnp.int32(0),
                   move_count=jnp.int32(0), done=jnp.bool_(False))


# --------------------------------------------- seeded sweep (always runs)


class TestSeededSweepVsBFS:
    """Deterministic random-board sweep against the BFS reference —
    independent of hypothesis so bare containers still pin the rules."""

    @pytest.mark.parametrize("size,boards", [(5, 20), (9, 6)])
    def test_groups_libs_reach_score(self, size, boards):
        rng = np.random.default_rng(size)
        eng = GoEngine(size)
        for _ in range(boards):
            board = rng.choice(np.int8([0, 1, -1]),
                               size=size * size).astype(np.int8)
            ids, libs = map(np.asarray, eng.group_info(jnp.asarray(board)))
            rids, rlibs = bfs_groups(board, size)
            np.testing.assert_array_equal(ids, rids)
            np.testing.assert_array_equal(libs, rlibs)
            rb = bfs_reach(board.copy(), size, 1)
            rw = bfs_reach(board.copy(), size, -1)
            np.testing.assert_array_equal(
                np.asarray(eng._reach(jnp.asarray(board), 1)), rb)
            np.testing.assert_array_equal(
                np.asarray(eng._reach(jnp.asarray(board), -1)), rw)
            empty = board == 0
            want = ((board == 1).sum() + (empty & rb & ~rw).sum()
                    - (board == -1).sum() - (empty & rw & ~rb).sum())
            assert float(eng.score(jnp.asarray(board))) == float(want)

    def test_legality_and_capture_sweep(self):
        rng = np.random.default_rng(7)
        eng = GoEngine(5)
        for _ in range(12):
            board = rng.choice(np.int8([0, 1, -1]), size=25).astype(np.int8)
            me = int(rng.choice([1, -1]))
            ko = int(rng.integers(-1, 25))
            got = np.asarray(eng.legal_moves(_state(board, me, ko)))
            want = ref_legal(board, 5, me, ko)
            np.testing.assert_array_equal(got, want)
            pts = np.nonzero(want[:25])[0]
            if pts.size:
                p = int(rng.choice(pts))
                nxt = eng.play(_state(board, me), jnp.int32(p))
                np.testing.assert_array_equal(np.asarray(nxt.board),
                                              ref_play(board, 5, p, me))

    def test_simple_ko_cycle(self):
        """The canonical ko: recapture is forbidden immediately, allowed
        after a tenuki elsewhere."""
        eng = GoEngine(5)
        #  . X O .
        #  X . . O   <- black plays 6 capturing nothing; build ko shape:
        b = np.zeros(25, np.int8)
        # black: 1, 5, 11, 7; white: 2, 8, 12 -> white 6 is in atari mirror
        for p in (1, 5, 11):
            b[p] = 1
        for p in (2, 8, 12):
            b[p] = -1
        b[6] = -1                     # white stone in the ko mouth
        state = _state(b, me=1)
        nxt = eng.play(state, jnp.int32(7))    # black captures at 7
        assert int(nxt.ko) == 6                # ko point set
        legal = np.asarray(eng.legal_moves(nxt))
        assert not legal[6]                    # immediate recapture illegal
        # after a pass the ko lifts
        lifted = eng.play(nxt, jnp.int32(eng.pass_action))
        assert int(lifted.ko) == NO_KO


# --------------------------------------------------- adversarial topologies


def snake_board(size, fill):
    """Boustrophedon snake of BLACK (path-graph topology, diameter n2) on
    a ``fill`` background — the label-propagation worst case."""
    b = np.full((size, size), fill, np.int8)
    b[::2, :] = 1
    for r in range(1, size, 2):
        b[r, size - 1 if (r // 2) % 2 == 0 else 0] = 1
    return b.reshape(-1)


def comb_board(size):
    """Spine column + every-other-row teeth: one group, many liberties."""
    b = np.zeros((size, size), np.int8)
    b[:, 0] = 1
    b[::2, :] = 1
    return b.reshape(-1)


def corridor_board(size):
    """Empty snake corridor walled by WHITE with a single BLACK seed at
    the far end — worst case for ``_reach`` (one seed, full diameter)."""
    b = np.where(snake_board(size, -1) == 1, 0, -1).astype(np.int8)
    # seed: one black stone on the corridor's tail cell — reach must then
    # propagate the full path length to cover the rest
    b[(size - 1) * size] = 1
    return b


class TestAdversarialConvergence:
    @pytest.mark.parametrize("size", [5, 9, 13])
    @pytest.mark.parametrize("maker", [lambda s: snake_board(s, -1),
                                       lambda s: snake_board(s, 0),
                                       comb_board])
    def test_groups_converge_on_diameter_maximisers(self, size, maker):
        board = maker(size)
        eng = GoEngine(size)
        ids, libs = map(np.asarray, eng.group_info(jnp.asarray(board)))
        rids, rlibs = bfs_groups(board, size)
        np.testing.assert_array_equal(ids, rids)
        np.testing.assert_array_equal(libs, rlibs)

    @pytest.mark.parametrize("size", [5, 9, 13])
    def test_reach_traverses_full_corridor(self, size):
        board = corridor_board(size)
        eng = GoEngine(size)
        got = np.asarray(eng._reach(jnp.asarray(board), 1))
        want = bfs_reach(board.copy(), size, 1)
        np.testing.assert_array_equal(got, want)
        # the corridor really is traversed end to end
        assert got[board == 0].all()


# ------------------------------------------------ hypothesis tier (optional)


if hypothesis is not None:
    @st.composite
    def random_board(draw, size=5):
        cells = draw(st.lists(st.sampled_from([0, 1, -1]),
                              min_size=size * size, max_size=size * size))
        return np.array(cells, np.int8)

    def without_dead_groups(board, size=5):
        """``board`` less its zero-liberty groups, which no game reaches
        (a move removes the enemy groups it leaves without liberties and
        suicide is illegal).  Taking stones away only adds liberties to
        the groups that stay, so one pass leaves none."""
        _, libs = bfs_groups(board, size)
        return np.where((board != 0) & (libs == 0), 0, board).astype(np.int8)

    def reachable_board():
        return random_board().map(without_dead_groups)

    class TestFloodFillProperties:
        @settings(**SETTINGS)
        @given(random_board())
        def test_group_ids_partition_and_libs(self, board):
            """Labels are the BFS partition (min-index roots) and liberty
            counts are exact — for every cell, not just statistically."""
            eng = GoEngine(5)
            ids, libs = map(np.asarray, eng.group_info(jnp.asarray(board)))
            rids, rlibs = bfs_groups(board, 5)
            np.testing.assert_array_equal(ids, rids)
            np.testing.assert_array_equal(libs, rlibs)

        @settings(**SETTINGS)
        @given(random_board(size=9))
        def test_group_info_size9(self, board):
            eng = GoEngine(9)
            ids, libs = map(np.asarray, eng.group_info(jnp.asarray(board)))
            rids, rlibs = bfs_groups(board, 9)
            np.testing.assert_array_equal(ids, rids)
            np.testing.assert_array_equal(libs, rlibs)

        @settings(**SETTINGS)
        @given(random_board())
        def test_reach_and_score(self, board):
            eng = GoEngine(5)
            for color in (1, -1):
                got = np.asarray(eng._reach(jnp.asarray(board), color))
                np.testing.assert_array_equal(
                    got, bfs_reach(board.copy(), 5, color))
            rb = bfs_reach(board.copy(), 5, 1)
            rw = bfs_reach(board.copy(), 5, -1)
            empty = board == 0
            want = ((board == 1).sum() + (empty & rb & ~rw).sum()
                    - (board == -1).sum() - (empty & rw & ~rb).sum())
            assert float(eng.score(jnp.asarray(board))) == float(want)

    class TestLegalityProperties:
        @settings(**SETTINGS)
        @given(reachable_board(), st.sampled_from([1, -1]),
               st.integers(-1, 24))
        def test_capture_suicide_ko_agree(self, board, me, ko):
            """The engine's liberty-precomputed legality formula equals
            the semantic place-capture-check reference on any position
            without a zero-liberty group, any player to move, any ko
            point.  (On a board with one, the reference's capture step
            would also remove groups the move does not touch.)"""
            eng = GoEngine(5)
            got = np.asarray(eng.legal_moves(_state(board, me, ko)))
            np.testing.assert_array_equal(got, ref_legal(board, 5, me, ko))

        @settings(**SETTINGS)
        @given(reachable_board(), st.sampled_from([1, -1]))
        def test_play_resolves_captures_like_reference(self, board, me):
            """Playing any legal point move produces the reference board
            (placement + capture removal)."""
            eng = GoEngine(5)
            state = _state(board, me)
            legal = np.asarray(eng.legal_moves(state))[:25]
            if not legal.any():
                return
            p = int(np.nonzero(legal)[0][0])
            nxt = eng.play(state, jnp.int32(p))
            np.testing.assert_array_equal(np.asarray(nxt.board),
                                          ref_play(board, 5, p, me))
            assert int(nxt.to_play) == -me
