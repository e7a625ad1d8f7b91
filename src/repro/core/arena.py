"""Batched self-play arena: a thin client of the SearchService dispatcher.

The seed harness (``selfplay.play_game``) ran **both** players' full MCTS
searches every move and discarded the non-mover's — half the compute wasted
— and vmapped whole games, so one long game stalled its entire batch.  PR 1
restructured the work loop (the Xeon Phi papers' lesson: throughput at
scale comes from the loop shape, not more lanes): G games advance one move
per jitted step, a parity-indexed roll-by-half puts each player's games in
a static half-batch (one search per move), and finished slots refill from
a pending queue.

This PR moves the pending-queue refill *onto the device*
(core/service.py): the arena submits its games to a
:class:`~repro.core.service.SearchService` pool, whose jitted dispatch
admits, searches, and scatters results into a device-resident ring buffer
— the host polls once per ``superstep`` moves instead of syncing every
step.  ``refill="host"`` keeps the PR 1 host-queue loop as the measured
baseline (benchmarks/bench_service.py) and as the bit-for-bit oracle for
the device refill (tests/test_service.py).

RNG is oracle-compatible on both paths: every slot carries its own key
chain and splits ``key -> (key, ka, kb)`` once per step exactly like
``play_game``, so a game seeded with key K plays the identical move
sequence in the arena and in the sequential oracle — the equivalence
tests pin this.
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mcts import MCTS
from repro.core.service import LANE_ARENA, SearchService
from repro.go.board import GoEngine, GoState


class SlotState(NamedTuple):
    """Device-resident arena state, batched over the G slots (host path)."""
    states: GoState     # game states, leading axis G
    keys: jax.Array     # u32[G, 2] per-game RNG chains


class StepRecord(NamedTuple):
    """Per-step observables consumed by the host bookkeeping (host path)."""
    done: jax.Array     # bool[G]  game over after this step
    winner: jax.Array   # f32[G]   engine.result of the post-step state
    action: jax.Array   # i32[G]   move just played
    nodes: jax.Array    # i32[G]   mover's final search-tree size


class GameResult(NamedTuple):
    """One finished game (host-side scalars)."""
    winner: float       # +1 black / -1 white / 0 draw
    moves: int
    tree_nodes: int     # mover's tree size on the final move (Fig. 12)
    a_is_black: bool


class Arena:
    """G-slot arena stepping two MCTS players through concurrent games.

    ``refill="device"`` (default) drives games through the SearchService
    slot pool; ``refill="host"`` runs the PR 1 per-step host-queue loop.
    Both play bit-identical games.

    ``mesh``/``placement``/``rebalance``/``multihop`` shard the backing
    pool over a one-axis device mesh (see core/service.py): games are
    placed onto per-device sub-pools by the host policy, each device
    steps its own slots, and self-play throughput scales past one
    device.  ``pipeline_depth`` streams the drain (that many supersteps
    in flight, host I/O overlapped with device compute) — the result set
    is depth-invariant because games are ticket-keyed.
    """

    def __init__(self, engine: GoEngine, player_a: MCTS, player_b: MCTS,
                 slots: int, max_moves: Optional[int] = None,
                 refill: str = "device", superstep: int = 2,
                 mesh=None, placement: str = "round_robin",
                 rebalance: bool = True, multihop: bool = True,
                 pipeline_depth: int = 1):
        if slots < 2 or slots % 2:
            raise ValueError(f"slots must be even and >= 2, got {slots}")
        if refill not in ("device", "host"):
            raise ValueError(f"refill must be 'device' or 'host', "
                             f"got {refill!r}")
        if mesh is not None and refill == "host":
            raise ValueError("mesh= requires refill='device' (the host-queue"
                             " baseline is single-device by construction)")
        self.engine = engine
        self.player_a = player_a
        self.player_b = player_b
        self.slots = slots
        self.max_moves = max_moves or engine.max_moves
        self.refill = refill
        self.superstep = superstep
        self.mesh = mesh
        self.placement = placement
        self.rebalance = rebalance
        self.multihop = multihop
        self.pipeline_depth = pipeline_depth
        self._service: Optional[SearchService] = None   # built on first use
        self._step = jax.jit(self._step_impl)
        self._refill = jax.jit(self._refill_impl)
        self.host_syncs = 0     # host<->device round-trips of the last run

    @property
    def service(self) -> SearchService:
        """The backing dispatcher (lazy: refill="host" never builds it)."""
        if self._service is None:
            self._service = SearchService(
                self.engine, self.player_a, self.player_b, self.slots,
                max_moves=self.max_moves, superstep=self.superstep,
                mesh=self.mesh, placement=self.placement,
                rebalance=self.rebalance, multihop=self.multihop,
                pipeline_depth=self.pipeline_depth)
        return self._service

    # ----------------------------------------------- host-queue device side
    # The PR 1 step/refill kernels, kept as the host-refill baseline.

    def _step_impl(self, slot: SlotState, parity: jax.Array):
        """Advance every slot one move; one search per slot.

        ``parity`` is the global move parity (0 => Black to move).  The
        roll-by-half gather puts A-to-move slots first; since G = 2h the
        same gather inverts itself after the searches.
        """
        G, h = self.slots, self.slots // 2
        shift = jnp.where(parity % 2 == 0, 0, h)
        idx = (jnp.arange(G, dtype=jnp.int32) + shift) % G   # involution

        st = jax.tree.map(lambda x: x[idx], slot.states)
        k3 = jax.vmap(lambda k: jax.random.split(k, 3))(slot.keys[idx])
        new_keys, ka, kb = k3[:, 0], k3[:, 1], k3[:, 2]

        head = jax.tree.map(lambda x: x[:h], st)
        tail = jax.tree.map(lambda x: x[h:], st)
        res_a = self.player_a.search_batch(head, ka[:h])
        res_b = self.player_b.search_batch(tail, kb[h:])
        actions = jnp.concatenate([res_a.action, res_b.action])
        nodes = jnp.concatenate([res_a.tree.size, res_b.tree.size])

        new_st = jax.vmap(self.engine.play)(st, actions)

        # un-permute with the same involution gather
        new_st = jax.tree.map(lambda x: x[idx], new_st)
        new_keys = new_keys[idx]
        actions = actions[idx]
        nodes = nodes[idx]

        winner = jax.vmap(self.engine.result)(new_st)
        rec = StepRecord(done=new_st.done, winner=winner, action=actions,
                         nodes=nodes)
        return SlotState(states=new_st, keys=new_keys), rec

    def _refill_impl(self, slot: SlotState, mask: jax.Array,
                     fresh_keys: jax.Array) -> SlotState:
        """Reset masked slots to fresh games with the given keys."""
        init = self.engine.init_state()

        def reset_leaf(buf, iv):
            m = mask.reshape((self.slots,) + (1,) * (buf.ndim - 1))
            return jnp.where(m, iv, buf)

        states = jax.tree.map(reset_leaf, slot.states, init)
        keys = jnp.where(mask[:, None], fresh_keys, slot.keys)
        return SlotState(states=states, keys=keys)

    def _initial_slots(self, keys: jax.Array) -> SlotState:
        init = self.engine.init_state()
        states = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (self.slots,) + jnp.shape(x)), init)
        return SlotState(states=states, keys=keys)

    # --------------------------------------------------------------- client

    @staticmethod
    def _check_keys(games: int, game_keys) -> Optional[np.ndarray]:
        if game_keys is None:
            return None
        game_keys = np.asarray(game_keys, np.uint32)
        if game_keys.shape != (games, 2):
            raise ValueError(f"game_keys must be [games, 2], got "
                             f"{game_keys.shape}")
        return game_keys

    def play_games(self, games: int, seed: int = 0,
                   game_keys: Optional[jax.Array] = None,
                   prior_weight=None) -> List[GameResult]:
        """Play ``games`` full games, refilling finished slots from the
        pending queue until the queue drains.

        A game admitted to slot ``s`` when the *next* step has parity ``p``
        must give Black to the player owning that (slot-half, parity) cell
        — that keeps the half-batch dispatch invariant.  Colour balance is
        the paper's (alternating colours, at most ±1 imbalance): admission
        is capped per colour, so a slot whose forced colour is exhausted
        idles one step and admits at the opposite parity instead.

        ``game_keys`` optionally fixes each game's root RNG key (u32[games,
        2], admission order) — used by the oracle-equivalence tests;
        otherwise keys come from a host-side chain of ``seed``.

        ``prior_weight`` (scalar or (a_side, b_side) pair, device-refill
        only) threads the evaluation-lane blend to every game — traced,
        so a guided-vs-unguided match reuses the unmodified pool trace;
        ``None`` means each player's configured default.
        """
        game_keys = self._check_keys(games, game_keys)
        if self.refill == "host":
            if prior_weight is not None:
                raise ValueError(
                    "prior_weight= needs refill='device' (the host-queue "
                    "baseline predates the evaluation lane)")
            return self._play_games_hostqueue(games, seed, game_keys)
        svc = self.service
        svc.reset(seed=seed, colour_cap=(games + 1) // 2,
                  game_capacity=games,
                  ring_capacity=games + self.slots)
        tickets = [svc.submit_game(
            key=None if game_keys is None else game_keys[i],
            lane=LANE_ARENA, prior_weight=prior_weight)
            for i in range(games)]
        recs = {r.ticket: r for r in svc.drain()}
        self.host_syncs = svc.host_syncs
        return [GameResult(winner=recs[t].winner, moves=recs[t].moves,
                           tree_nodes=recs[t].tree_nodes,
                           a_is_black=recs[t].a_is_black) for t in tickets]

    # ----------------------------------------------------- host-queue loop

    def _play_games_hostqueue(self, games: int, seed: int,
                              game_keys: Optional[np.ndarray]
                              ) -> List[GameResult]:
        """The PR 1 loop: per-step host admission + per-step result sync."""
        G, h = self.slots, self.slots // 2
        host_rng = np.random.default_rng(seed)
        self.host_syncs = 0

        def draw_key(i: int) -> np.ndarray:
            if game_keys is not None:
                return game_keys[i]
            return host_rng.integers(0, 2 ** 32, size=(2,), dtype=np.uint32)

        game_id = np.full(G, -1)            # -1: dummy slot (result discarded)
        a_black = np.array([s < h for s in range(G)])
        nmoves = np.zeros(G, np.int64)
        last_nodes = np.zeros(G, np.int64)
        colour_cap = (games + 1) // 2        # per-colour admission budget
        colour_count = {True: 0, False: 0}
        next_game = 0
        keys0 = np.stack([host_rng.integers(0, 2 ** 32, size=(2,),
                                            dtype=np.uint32)
                          for _ in range(G)])
        slot = self._initial_slots(jnp.asarray(keys0))

        results: List[Optional[GameResult]] = [None] * games
        finished = 0
        parity = 0
        while finished < games:
            # admit pending games into empty slots whose forced colour
            # still has budget; a blocked slot waits for the parity flip
            refill_mask = np.zeros(G, bool)
            fresh = np.zeros((G, 2), np.uint32)
            for s in range(G):
                if game_id[s] >= 0 or next_game >= games:
                    continue
                colour = (s < h) == (parity % 2 == 0)
                if colour_count[colour] >= colour_cap:
                    continue
                colour_count[colour] += 1
                game_id[s] = next_game
                a_black[s] = colour
                nmoves[s] = 0
                last_nodes[s] = 0
                fresh[s] = draw_key(next_game)
                refill_mask[s] = True
                next_game += 1
            if refill_mask.any():
                slot = self._refill(slot, jnp.asarray(refill_mask),
                                    jnp.asarray(fresh))
                self.host_syncs += 1
            slot, rec = self._step(slot, jnp.int32(parity))
            parity ^= 1
            done = np.asarray(rec.done)
            winner = np.asarray(rec.winner)
            nodes = np.asarray(rec.nodes)
            self.host_syncs += 1

            for s in range(G):
                if game_id[s] < 0:
                    continue
                nmoves[s] += 1
                last_nodes[s] = int(nodes[s])
                if done[s] or nmoves[s] >= self.max_moves:
                    results[game_id[s]] = GameResult(
                        winner=float(winner[s]), moves=int(nmoves[s]),
                        tree_nodes=int(last_nodes[s]),
                        a_is_black=bool(a_black[s]))
                    finished += 1
                    game_id[s] = -1
        assert all(r is not None for r in results)
        return results  # type: ignore[return-value]
