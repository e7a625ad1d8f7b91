"""Closed-loop self-play through the ``Arena``'s ``SearchService``.

``Arena.play_games`` drains a fixed list of games; a window of fixed
length instead drives the same service and compiled dispatch directly:
one game per slot from the empty board, spare games queued for the
device to refill a slot the step its game ends, and a new spare queued
for each game that ends.  The window is whole dispatches: it ends with
the dispatch running when ``seconds`` expire.

The games are read back after every dispatch (the slots' states), so
that every move played can be checked against the numpy rules and a
seeded sample of the window's searches against the plain reference
search.

A traced run traces the boundary between the first two dispatches of the
window: from ``trace_lead_s`` seconds before the first is due to end
(judged by how long the set-up's dispatch ran on the device) to
``trace_s`` seconds after that, so the span holds the host's poll and
read between them.  A whole dispatch holds more device events than the
profiler keeps.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from .. import arrivals
from ..gorules import Rules, State
from ..refsearch import RefSearch, _cpu, _split
from .common import warm_ring_reads


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int, span):
        self.cfg, self.traffic, self.seed, self.span = cfg, traffic, seed, span
        self.rules = Rules(cfg["board_size"])

    # ----------------------------------------------------------------- set-up

    def setup(self, seconds: float) -> None:
        from repro.config import MCTSConfig
        from repro.core.arena import Arena
        from repro.core.mcts import MCTS
        from repro.go.board import GoEngine
        c = self.cfg
        engine = GoEngine(c["board_size"], c["komi"])
        player = MCTS(engine, MCTSConfig(
            board_size=c["board_size"], komi=c["komi"], lanes=c["lanes"],
            sims_per_move=c["sims_per_move"], max_nodes=c["max_nodes"],
            c_uct=c["c_uct"], virtual_loss=c["virtual_loss"],
            parallelism="tree"))
        arena = Arena(engine, player, player, slots=c["slots"],
                      superstep=c["superstep"])
        self.svc = svc = arena.service
        S = svc.slots
        self.queued = S + int(self.traffic["spare_games"])
        self.keys = list(arrivals.game_keys(4 * self.queued, self.seed))
        svc.reset(seed=self.seed % 2 ** 31, game_capacity=2 * self.queued,
                  ring_capacity=4 * self.queued)
        self.next_game = 0
        for _ in range(self.queued):
            self._submit_game()
        svc.flush()
        init = self.rules.initial()
        self.history = [[init] * S]      # slot states after each dispatch
        self.step = 0
        self.first_timed = None          # first dispatch of the window
        self._issue()                    # compiles, then plays 2 moves
        self._finish()
        warm_ring_reads(svc, svc.slots * svc.superstep)

    def _submit_game(self) -> None:
        self.svc.submit_game(key=self.keys[self.next_game])
        self.next_game += 1

    def _issue(self) -> None:
        with self.span("service.dispatch"):
            self.svc.dispatch()          # returns once the work is queued
        self.issued = time.perf_counter()

    def _finish(self) -> None:
        svc = self.svc
        with self.span("service.poll"):
            done = svc.poll()
        self.device_s = time.perf_counter() - self.issued
        for _ in done:                   # closed loop: one new spare each
            self._submit_game()
        if done:
            svc.flush()
        self.step += svc.superstep
        with self.span("client.read_games"):
            g = jax.device_get(svc._pool.slots.states)
        self.history.append([State(np.asarray(g.board[s], np.int8),
                                   int(g.to_play[s]), int(g.ko[s]),
                                   int(g.pass_count[s]),
                                   int(g.move_count[s]), bool(g.done[s]))
                             for s in range(svc.slots)])

    def counters(self) -> dict:
        occ = np.asarray(self.svc.shard_occupancy(), np.float64)
        return {"host_syncs": self.svc.host_syncs, "steps": self.step,
                "occ_sum": float(np.round(
                    (occ * self.step * self.svc.slots / occ.size).sum()))}

    # ----------------------------------------------------------------- window

    def window(self, seconds: float, tracer) -> dict:
        before = self.counters()
        self.first_timed = len(self.history) - 1
        lead = float(self.traffic["trace_lead_s"])
        walls = []
        t0 = time.perf_counter()
        while True:
            t = time.perf_counter()
            self._issue()
            if tracer.running:           # the next dispatch has started
                time.sleep(max(0.0, tracer.at + tracer.length
                               - (time.perf_counter() - t0)))
                tracer.stop()
            elif tracer.due(t - t0):     # the device is busy until then
                time.sleep(max(0.0, t + self.device_s - lead
                               - time.perf_counter()))
                tracer.start(time.perf_counter() - t0)
            self._finish()
            walls.append(time.perf_counter() - t)
            if time.perf_counter() - t0 >= seconds:
                break
        wall = time.perf_counter() - t0
        after = self.counters()
        searches = after["occ_sum"] - before["occ_sum"]
        return {"wall_s": wall, "dispatches": len(walls),
                "dispatch_s": walls, "steps": after["steps"] - before["steps"],
                "searches": searches,
                "sims": searches * self.cfg["sims_per_move"],
                "host_syncs": after["host_syncs"] - before["host_syncs"],
                "attempted": int(searches), "failed": 0}

    # ------------------------------------------------------------------ check

    def _moves(self, a: State, b: State, n: int):
        """The ``n`` moves that lead from ``a`` to ``b`` under the rules,
        with the state before each, or None if no legal sequence does."""
        r = self.rules
        if n == 0:
            return [] if r.same(a, b) else None
        legal = r.legal(a)
        new = np.flatnonzero((a.board == 0) & (b.board == a.to_play))
        first = [int(p) for p in new if legal[p]] + [r.pass_action]
        rest = [int(m) for m in np.flatnonzero(legal) if m not in first]
        for m in first + rest:           # the stones that appeared first
            tail = self._moves(r.play(a, m), b, n - 1)
            if tail is not None:
                return [(a, m)] + tail
        return None

    def check(self) -> dict:
        """Every move played, against the rules; a seeded sample of the
        searches, against the plain reference search."""
        c, S, k = self.cfg, self.svc.slots, self.svc.superstep
        h = S // 2
        violations = 0
        played = []                      # (slot, step, state before, move)
        init = self.rules.initial()
        for d in range(len(self.history) - 1):
            for s in range(S):
                a, b = self.history[d][s], self.history[d + 1][s]
                n = b.move_count - a.move_count
                if 0 < n <= k and not a.done and (n == k or b.done):
                    moves = self._moves(a, b, n)      # the same game
                elif 1 <= b.move_count <= k and (a.done or n <= 0):
                    # a new game: admitted at one of the dispatch's steps,
                    # it has played from there on
                    moves = self._moves(init, b, b.move_count)
                else:
                    moves = None
                if moves is None:
                    violations += 1
                    continue
                for j, (st, m) in enumerate(moves):
                    played.append((s, d * k + j, st, m, d))
        ref, control = (RefSearch(c["board_size"], c["lanes"],
                                  c["sims_per_move"], c["max_nodes"],
                                  c["c_uct"], c["virtual_loss"],
                                  playout_cap=cap)
                        for cap in (0, c["control_playout_moves"]))
        rng = np.random.default_rng(self.seed + 2)
        # the window's searches in each slot's first game, whose key
        # chain is known
        first = [p for p in played if p[2].move_count == p[1]
                 and p[4] >= self.first_timed]
        n = min(c["ref_samples"], len(first))
        mismatch = 0
        for i in (rng.choice(len(first), n, replace=False) if n else []):
            s, step, st, move, _ = first[i]
            key = _cpu(np.asarray(self.keys[s], np.uint32))
            for _ in range(step + 1):
                key, ka, kb = _split(key, 3)
            shift = 0 if step % 2 == 0 else h
            key = ka if (s - shift) % S < h else kb
            want = ref.search(st, np.asarray(key), 0, c["komi"])
            if c["control"]:             # the control answers instead
                move = control.search(st, np.asarray(key), 0,
                                      c["komi"]).action
            mismatch += want.action != move
        return {"rule_violations": violations,
                "ref_mismatch_share": mismatch / n if n else 1.0}
