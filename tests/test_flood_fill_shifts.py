"""The flood fill's gather-free label round against the gather round.

``GoEngine._min_label_components`` hooks each cell to its neighbour min
by static shifts of the flat board and pointer-jumps by a one-hot
compare.  The gather formulation it replaced (``idp[nbr]`` for the hook,
``newp[new]`` for the jump) is kept here, in the tests only, as the
reference: every round must give the same ``int32`` labels, so captures,
playouts and the search's answers cannot move.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.go import GoEngine
from repro.go.board import _OFF, EMPTY
from test_go_property import comb_board, corridor_board, snake_board


def gather_labels(eng, active, same, rounds=None):
    """The gather round: hook by ``idp[nbr]``, jump by ``newp[new]``."""
    n2 = eng.n2
    ids0 = jnp.where(active, jnp.arange(n2, dtype=jnp.int32), n2)

    def body(_, ids):
        idp = eng._pad(ids, n2)
        cand = jnp.where(same, idp[eng.nbr], n2)
        new = jnp.minimum(ids, cand.min(axis=1))
        newp = eng._pad(new, n2)
        new = jnp.minimum(new, newp[new])
        return jnp.where(active, new, n2)

    return jax.lax.fori_loop(0, rounds or eng.label_rounds, body, ids0)


def stone_input(eng, board):
    """``(active, same)`` as ``group_info`` builds them."""
    board = jnp.asarray(board)
    bp = eng._pad(board, _OFF)
    return board != EMPTY, bp[eng.nbr] == board[:, None]


def empty_input(eng, board):
    """``(active, same)`` as ``_reach`` builds them."""
    board = jnp.asarray(board)
    empty = board == EMPTY
    nb_col = eng._pad(board, _OFF)[eng.nbr]
    return empty, empty[:, None] & (nb_col == EMPTY)


INPUTS = {"stones": stone_input, "empties": empty_input}


def assert_same_labels(eng, active, same):
    """Labels of a batch (leading axis) equal the gather round's."""
    got = jax.jit(jax.vmap(eng._min_label_components))(active, same)
    want = jax.jit(jax.vmap(lambda a, s: gather_labels(eng, a, s)))(
        active, same)
    assert got.dtype == jnp.int32 and got.shape == active.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def batch(eng, kind, boards):
    return jax.vmap(lambda b: INPUTS[kind](eng, b))(
        jnp.asarray(np.stack(boards)))


@pytest.mark.parametrize("size", [5, 9, 13, 19])
@pytest.mark.parametrize("kind", sorted(INPUTS))
def test_random_boards_match_gather(size, kind):
    eng = GoEngine(size)
    rng = np.random.default_rng(size)
    boards = [rng.choice(np.int8([0, 1, -1]), size=eng.n2,
                         p=rng.dirichlet([1, 1, 1])).astype(np.int8)
              for _ in range(16)]
    assert_same_labels(eng, *batch(eng, kind, boards))


@pytest.mark.parametrize("size", [5, 9, 19])
def test_arbitrary_masks_match_gather(size):
    """Any ``active``/``same``, edges off the board included: the round
    must not lean on the callers masking the wall."""
    eng = GoEngine(size)
    rng = np.random.default_rng(100 + size)
    density = rng.random((16, 1))
    active = jnp.asarray(rng.random((16, eng.n2)) < density)
    same = jnp.asarray(rng.random((16, eng.n2, 4)) < density[:, :, None])
    assert_same_labels(eng, active, same)


FAMILIES = {"snake_on_white": lambda s: snake_board(s, -1),
            "snake_on_empty": lambda s: snake_board(s, 0),
            "comb": comb_board, "corridor": corridor_board}


@pytest.mark.parametrize("size", [5, 9, 13])
@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("kind", sorted(INPUTS))
def test_adversarial_families_match_gather(size, family, kind):
    eng = GoEngine(size)
    assert_same_labels(eng, *batch(eng, kind, [FAMILIES[family](size)]))


@pytest.mark.parametrize("kind", sorted(INPUTS))
def test_every_round_matches_gather(kind):
    """Round by round on the 9x9 snake (the longest label paths): the
    labels after each round equal the gather round's."""
    eng = GoEngine(9)
    active, same = INPUTS[kind](eng, snake_board(9, 0))
    with jax.disable_jit():                   # the loops run op by op
        for rounds in range(1, eng.label_rounds + 1):
            eng.label_rounds = rounds         # read when the loop is built
            np.testing.assert_array_equal(
                np.asarray(eng._min_label_components(active, same)),
                np.asarray(gather_labels(eng, active, same, rounds)))


def test_playout_batch_matches_gather():
    """A vmapped ``[64, 81]`` batch, the playouts' shape."""
    eng = GoEngine(9)
    rng = np.random.default_rng(64)
    boards = list(rng.choice(np.int8([0, 1, -1]), size=(64, 81),
                             p=[0.4, 0.3, 0.3]).astype(np.int8))
    for kind in INPUTS:
        active, same = batch(eng, kind, boards)
        assert active.shape == (64, 81)
        assert_same_labels(eng, active, same)


def test_label_round_lowers_without_gather():
    """The lowered flood fill at 9x9 holds no gather; the gather round
    it replaced lowers with four."""
    eng = GoEngine(9)
    args = (jax.ShapeDtypeStruct((81,), jnp.bool_),
            jax.ShapeDtypeStruct((81, 4), jnp.bool_))
    text = jax.jit(eng._min_label_components).lower(*args).as_text()
    assert "gather" not in text
    ref = jax.jit(lambda a, s: gather_labels(eng, a, s)).lower(*args)
    assert ref.as_text().count("gather") == 4
