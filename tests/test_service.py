"""SearchService dispatcher tests (core/service.py): device-side refill
bit-for-bit vs the host queue, mixed-lane ticket fairness, the serve-lane
RNG contract, the traced per-request sims knob, and the tournament
scheduler.  (The streaming-pipeline suite lives in tests/test_pipeline.py;
the PR 2 deprecation-shim tests left with the shims.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import MCTSConfig
from repro.core.arena import Arena
from repro.core.mcts import MCTS
from repro.core.selfplay import double_resources
from repro.core.service import (LANE_ARENA, LANE_SERVE,
                                SearchService)

CFG = MCTSConfig(board_size=5, lanes=2, sims_per_move=8, max_nodes=64)
CAP = 12


@pytest.fixture(scope="module")
def players(engine5):
    return MCTS(engine5, double_resources(CFG)), MCTS(engine5, CFG)


@pytest.fixture(scope="module")
def arena_pair(engine5, players):
    """One compiled (host-refill, device-refill) arena pair, shared."""
    a, b = players
    return (Arena(engine5, a, b, slots=2, max_moves=CAP, refill="host"),
            Arena(engine5, a, b, slots=2, max_moves=CAP, refill="device"))


@pytest.fixture(scope="module")
def svc4(engine5, players):
    """One compiled 4-slot mixed-lane pool, reset() between tests."""
    a, b = players
    return SearchService(engine5, a, b, slots=4, max_moves=CAP)


@pytest.fixture(scope="module")
def jit_search(players):
    """Shared jitted search_batch of the 1x player (2- and 3-arg traces)."""
    return jax.jit(players[1].search_batch)


@pytest.fixture(scope="module")
def mid_state(engine5):
    """A position a few moves into a game (serve-query root)."""
    st = engine5.init_state()
    for mv in (3, 7, 12, 16):
        st = engine5.jit_play(st, jnp.int32(mv))
    return st


class TestDeviceRefill:
    @pytest.mark.slow
    def test_device_matches_host_queue_bit_for_bit(self, arena_pair):
        """The tentpole invariant: the jitted admission (pending counter +
        ring buffer) refills slots exactly like the PR 1 host loop — every
        game's (winner, moves, nodes, colour) is identical."""
        host, device = arena_pair
        games = 5                       # > slots: refill path exercised
        keys = np.asarray(jax.random.split(jax.random.PRNGKey(3), games))
        assert (device.play_games(games, game_keys=keys)
                == host.play_games(games, game_keys=keys))

    def test_seeded_key_chain_matches_host_queue(self, arena_pair):
        """Keyless submissions draw from the same host chain as the PR 1
        loop (slot keys first, then per-game keys in admission order) —
        so the two refill modes play bit-identical games."""
        host, device = arena_pair
        assert (device.play_games(3, seed=11)
                == host.play_games(3, seed=11))

    def test_fewer_host_syncs_than_host_queue(self, arena_pair):
        host, device = arena_pair
        host.play_games(3, seed=0)
        device.play_games(3, seed=0)
        assert device.host_syncs < host.host_syncs


class TestSingleSearchPerStep:
    def test_dispatch_traces_one_search_per_player(self, engine5):
        """Per dispatch step the traced search batches cover each slot
        exactly once — S searched slots for S moves (the PR 1 invariant,
        now inside the service)."""
        a2 = MCTS(engine5, double_resources(CFG))
        b2 = MCTS(engine5, CFG)
        searched = []

        def counting(player, tag):
            orig = player.search_batch

            def wrapped(roots, rngs, sims=None, params=None):
                searched.append((tag, int(rngs.shape[0])))
                return orig(roots, rngs, sims, params)
            player.search_batch = wrapped

        counting(a2, "A")
        counting(b2, "B")
        svc = SearchService(engine5, a2, b2, slots=4, max_moves=CAP)
        svc.dispatch(steps=1)
        assert sorted(searched) == [("A", 2), ("B", 2)]


class TestMixedLanes:
    def test_mixed_pool_runs_all_lanes(self, svc4, mid_state):
        svc4.reset(seed=0, colour_cap=1)
        gk = np.asarray(jax.random.split(jax.random.PRNGKey(9), 2))
        sk = np.asarray(jax.random.split(jax.random.PRNGKey(11), 3))
        gt = [svc4.submit_game(key=gk[i]) for i in range(2)]
        st = [svc4.submit_serve(mid_state, key=sk[i]) for i in range(3)]
        recs = {r.ticket: r for r in svc4.drain()}
        assert sorted(recs) == sorted(gt + st)
        for t in gt:
            assert recs[t].lane == LANE_ARENA
            assert recs[t].winner in (-1.0, 0.0, 1.0)
            assert 0 < recs[t].moves <= CAP
        for t in st:
            assert recs[t].lane == LANE_SERVE
            assert recs[t].moves == 1
        # colour balance across the game lane holds in the mixed pool
        blacks = [recs[t].a_is_black for t in gt]
        assert sorted(blacks) == [False, True]

    def test_serve_key_contract(self, players, svc4, mid_state):
        """A serve result is player A's search_batch with the request key
        — independent of slot placement and batch-mates (bit-for-bit)."""
        a, _ = players
        svc4.reset(seed=0)
        sk = np.asarray(jax.random.split(jax.random.PRNGKey(5), 2))
        svc4.submit_game()              # batch-mates in the pool
        tickets = [svc4.submit_serve(mid_state, key=sk[i], sims=s)
                   for i, s in enumerate((0, 4))]
        recs = {r.ticket: r for r in svc4.drain()}
        roots = jax.tree.map(lambda x: x[None], mid_state)
        want_fn = jax.jit(a.search_batch)
        for i, (t, s) in enumerate(zip(tickets, (0, 4))):
            want = want_fn(roots, jnp.asarray(sk[i])[None],
                           jnp.asarray([s], jnp.int32))
            assert recs[t].action == int(want.action[0])
            np.testing.assert_array_equal(
                recs[t].root_visits, np.asarray(want.root_visits[0]))

    def test_serve_tickets_resolve_fifo(self, engine5, players, mid_state):
        """Under contention (one A-cell per step) serve queries complete
        in submission order."""
        a, _ = players
        svc = SearchService(engine5, a, a, slots=2, max_moves=CAP)
        keys = np.asarray(jax.random.split(jax.random.PRNGKey(2), 5))
        tickets = [svc.submit_serve(mid_state, key=keys[i])
                   for i in range(5)]
        order = [r.ticket for r in svc.drain() if r.lane == LANE_SERVE]
        assert order == tickets

    def test_validation_and_queue_limits(self, engine5, players, mid_state):
        a, b = players
        with pytest.raises(ValueError):
            SearchService(engine5, a, b, slots=3)
        with pytest.raises(ValueError):
            SearchService(engine5, a, b, slots=2, superstep=0)
        svc = SearchService(engine5, a, b, slots=2)
        with pytest.raises(ValueError):
            svc.submit_game(lane=LANE_SERVE)
        svc.reset(serve_capacity=2, game_capacity=2)
        svc.submit_serve(mid_state)
        svc.submit_serve(mid_state)
        with pytest.raises(RuntimeError):
            svc.submit_serve(mid_state)


class TestDonation:
    def test_dispatch_consumes_its_work_half(self, svc4, mid_state):
        """The dispatch donates the pool's work half on every backend, the
        CPU included: the buffers it was handed are gone after the call.
        A reference held across it would fail here as it would on the
        chip.  The result ring is not donated and stays readable."""
        svc4.reset(seed=0, serve_capacity=4)
        svc4.submit_serve(mid_state, key=np.array([1, 2], np.uint32))
        svc4.flush()
        work = svc4._pool._replace(ring=None)
        ring = svc4._pool.ring
        svc4.dispatch()
        leaves = jax.tree.leaves(work)
        assert leaves and all(x.is_deleted() for x in leaves)
        assert not any(x.is_deleted() for x in jax.tree.leaves(ring))
        assert not any(x.is_deleted()
                       for x in jax.tree.leaves(svc4._pool))


class TestSimsKnob:
    def test_full_budget_bit_identical_to_static_loop(self, engine5,
                                                      jit_search):
        """sims=0 and sims>=configured budget both reproduce the static
        loop exactly — the masked tail is a no-op select."""
        roots = jax.tree.map(lambda x: x[None], engine5.init_state())
        key = jax.random.PRNGKey(4)[None]
        base = jit_search(roots, key)
        for sims in (0, CFG.sims_per_move, CFG.sims_per_move * 10):
            res = jit_search(roots, key, jnp.asarray([sims], jnp.int32))
            assert int(res.action[0]) == int(base.action[0])
            np.testing.assert_array_equal(np.asarray(res.root_visits),
                                          np.asarray(base.root_visits))
            np.testing.assert_array_equal(np.asarray(res.tree.visit),
                                          np.asarray(base.tree.visit))

    def test_smaller_budget_masks_iterations(self, engine5, jit_search):
        """The root's visit count pins iterations = sims // lanes, and
        the reported tree size tracks the truncated budget (dead
        iterations allocate nothing visible)."""
        roots = jax.tree.map(lambda x: x[None], engine5.init_state())
        key = jax.random.PRNGKey(4)[None]
        sizes = {}
        for sims, iters in ((4, 2), (8, 4), (2, 1)):
            res = jit_search(roots, key, jnp.asarray([sims], jnp.int32))
            assert float(res.tree.visit[0, 0]) == 1.0 + iters * CFG.lanes
            sizes[sims] = int(res.tree.size[0])
        assert sizes[2] <= sizes[4] <= sizes[8]

    def test_sims_is_traced_not_static(self, engine5, players):
        """Changing the budget must not recompile (the ServeEngine
        temperature treatment applied to the search loop)."""
        _, b = players
        fn = jax.jit(b.search_batch)
        roots = jax.tree.map(lambda x: x[None], engine5.init_state())
        key = jax.random.PRNGKey(0)[None]
        for sims in (2, 4, 8):
            fn(roots, key, jnp.asarray([sims], jnp.int32))
        assert fn._cache_size() == 1


class TestTournament:
    @pytest.mark.slow
    def test_round_robin_through_one_pool(self, engine5):
        from repro.core.tournament import Tournament
        cfgs = [CFG, double_resources(CFG)]
        t = Tournament(engine5, cfgs, names=("1x", "2x"),
                       games_per_pair=3, slots=2, max_moves=CAP, seed=1)
        res = t.round_robin()
        assert res.games == 3
        pair = res.pairs[(0, 1)]
        assert pair.i_wins + pair.j_wins + pair.draws == 3
        assert res.points.sum() == pytest.approx(3.0)
        assert 0.0 <= pair.rate.lo <= pair.rate.rate <= pair.rate.hi <= 1.0
        assert "points" in res.table()
        assert t.host_syncs > 0

    def test_tournament_validation(self, engine5):
        from repro.core.tournament import Tournament
        with pytest.raises(ValueError):
            Tournament(engine5, [CFG])
        with pytest.raises(ValueError):
            Tournament(engine5, [CFG, CFG], names=("only-one",))


class TestGoService:
    @pytest.fixture(scope="class")
    def go_service(self):
        from repro.serving.go_service import GoService
        return GoService(board_size=5, komi=0.5, max_sims=8, lanes=2,
                         slots=4, seed=0)

    def test_best_move_deterministic_and_legal(self, go_service):
        board = np.zeros(25, np.int8)
        board[12] = 1
        key = np.asarray(jax.random.PRNGKey(8))
        m1 = go_service.best_move(board, to_play=-1, key=key)
        m2 = go_service.best_move(board, to_play=-1, key=key)
        assert m1.action == m2.action
        np.testing.assert_array_equal(m1.root_visits, m2.root_visits)
        assert 0 <= m1.action <= 25
        assert m1.is_pass == (m1.action == 25)
        assert (m1.coord is None) == m1.is_pass
        if not m1.is_pass:
            assert m1.action != 12      # occupied point is illegal

    def test_batch_and_tickets(self, go_service):
        boards = [np.zeros(25, np.int8) for _ in range(5)]
        res = go_service.best_move_batch(boards, sims=4)
        assert [r.ticket for r in res] == sorted(r.ticket for r in res)
        with pytest.raises(KeyError):
            go_service.result(10_000)
