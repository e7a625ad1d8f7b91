"""The traffic generator: seeded, and the same work for every seed."""
import numpy as np

from benchmarks.chip.arrivals import game_keys, open_loop

SERVE = {"arrivals": "poisson", "rate_per_s": 2.0, "prefix_moves": [0, 10],
         "sims": [[64, 3], [16, 1]]}


def test_same_seed_same_queries():
    a = open_loop(SERVE, 5, 10.0, seed=2 ** 31 + 5)
    b = open_loop(SERVE, 5, 10.0, seed=2 ** 31 + 5)
    assert [q.due_s for q in a] == [q.due_s for q in b]
    assert all(np.array_equal(x.state.board, y.state.board)
               and np.array_equal(x.key, y.key) for x, y in zip(a, b))


def test_every_seed_the_same_work_in_another_order():
    a = open_loop(SERVE, 5, 10.0, seed=1)
    b = open_loop(SERVE, 5, 10.0, seed=2)
    assert len(a) == len(b) == 20
    assert sorted(q.prefix for q in a) == sorted(q.prefix for q in b)
    assert sorted(q.sims for q in a) == sorted(q.sims for q in b)
    assert sum(q.sims == 16 for q in a) == 5
    gaps = lambda qs: sorted(np.diff([0.0] + [q.due_s for q in qs]))
    assert not np.allclose([q.due_s for q in a], [q.due_s for q in b])
    assert np.allclose(gaps(a)[:5], gaps(b)[:5])


def test_game_keys_seeded():
    assert np.array_equal(game_keys(4, 9), game_keys(4, 9))
    assert game_keys(4, 9).dtype == np.uint32
