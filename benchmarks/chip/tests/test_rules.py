"""The vectorised numpy rules against the copied BFS reference."""
import numpy as np
import pytest

from benchmarks.chip.arrivals import random_position
from benchmarks.chip.gorules import (NO_KO, Rules, State, bfs_groups,
                                     ref_legal, ref_play)


def boards(size, n, seed):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        yield rng.choice([-1, 0, 0, 1], size * size).astype(np.int8)


@pytest.mark.parametrize("size", [5, 9])
def test_liberties_and_legality_match_bfs(size):
    r = Rules(size)
    for i, b in enumerate(boards(size, 40, size)):
        _, libs = r.groups(b)
        _, want = bfs_groups(b, size)
        assert np.array_equal(libs, want)
        me = 1 if i % 2 else -1
        st = State(b, me, NO_KO, 0, 0, False)
        assert np.array_equal(r.legal(st), ref_legal(b, size, me, NO_KO))


@pytest.mark.parametrize("size", [5, 9])
def test_play_matches_bfs_captures(size):
    """On positions reached by legal play (random boards may hold groups
    with no liberty, which no game reaches)."""
    r = Rules(size)
    rng = np.random.default_rng(size + 1)
    for _ in range(20):
        st = random_position(r, int(rng.integers(size * size // 2,
                                                 2 * size * size)), rng)
        st = st._replace(ko=NO_KO)
        for m in np.flatnonzero(r.legal(st)[:-1]):
            got = r.play(st, int(m)).board
            assert np.array_equal(got, ref_play(st.board, size, int(m),
                                                st.to_play))


def test_simple_ko_and_game_end():
    r = Rules(5)
    # black captures a lone white stone at 7 by playing 12; white may not
    # retake at once at 7
    b = np.zeros(25, np.int8)
    b[[2, 6, 8]] = 1          # black around 7
    b[[7, 11, 13, 17]] = -1   # white stone at 7, white around 12
    st = r.play(State(b, 1, NO_KO, 0, 0, False), 12)
    assert st.board[7] == 0 and st.ko == 7
    assert not r.legal(st)[7]
    st = r.play(r.play(st, r.pass_action), r.pass_action)
    assert st.done and st.pass_count == 2


def test_area_score():
    r = Rules(5)
    b = np.zeros(25, np.int8)
    b[[2, 7, 12, 17, 22]] = 1         # a black wall down column 2
    # black owns its wall and the empty columns 0-1 (10 points); columns
    # 3-4 are empty and touch only black too
    assert r.area(b) == 25.0
    b[[4, 9, 14, 19, 24]] = -1         # white wall down column 4
    assert r.area(b) == (5 + 10) - 5   # column 3 touches both


def test_random_positions_replay_legally():
    r = Rules(9)
    rng = np.random.default_rng(0)
    for n in (0, 1, 30, 60):
        st = random_position(r, n, rng)
        assert st.move_count == n and st.to_play == (1 if n % 2 == 0 else -1)
