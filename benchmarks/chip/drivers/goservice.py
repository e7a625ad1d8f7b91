"""Open-loop best-move queries through ``GoService.submit`` / ``poll``.

One client thread: it sends every query whose due time has come, then
polls the service, which runs one whole dispatch when queries are
outstanding; with none outstanding it sleeps until the next due time.
A query's latency runs from its due time to the poll that returns its
answer.  Queries due in the window are drained after it, for at most
``DRAIN_S`` seconds (not counting the time the profiler takes to write
a trace out); one still unanswered then, or shed, has failed.
"""
from __future__ import annotations

import time

import numpy as np

from .. import arrivals
from ..gorules import NO_KO, Rules, State
from ..refsearch import RefSearch
from .common import warm_ring_reads

DRAIN_S = 60.0
WARM_S = 1200.0       # a first run compiles the dispatch in its set-up


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int, span):
        self.cfg, self.traffic, self.seed, self.span = cfg, traffic, seed, span
        self.rules = Rules(cfg["board_size"])

    # ----------------------------------------------------------------- set-up

    def setup(self, seconds: float) -> None:
        from repro.serving.go_service import GoService
        from repro.compat import make_service_mesh
        c = self.cfg
        chips = c.get("chips", 1)
        mesh = make_service_mesh(chips) if chips > 1 else None
        self.svc = GoService(
            board_size=c["board_size"], komi=c["komi"],
            max_sims=c["max_sims"], lanes=c["lanes"], slots=c["slots"],
            max_nodes=c["max_nodes"], superstep=c["superstep"],
            pipeline_depth=c["pipeline_depth"], placement=c["placement"],
            mesh=mesh, seed=self.seed % 2 ** 31)
        self.queries = arrivals.open_loop(self.traffic, c["board_size"],
                                          seconds, self.seed)
        # warm every shape the window uses: one full pool of queries
        # through the same submit/poll path
        warm = arrivals.open_loop(
            dict(self.traffic, rate_per_s=self.svc.slots),
            c["board_size"], 1.0, self.seed + 1)
        tickets = [self._submit(q) for q in warm[:self.svc.slots]]
        self.svc.flush()
        for t in tickets:
            self.svc.result(t, timeout_s=WARM_S)
        inner = self.svc._buckets[self.svc.default_komi]
        warm_ring_reads(inner, inner.slots * inner.superstep)
        self.answers = {}

    def _submit(self, q) -> int:
        return self.svc.submit(q.state.board, q.state.to_play,
                               komi=self.cfg["komi"], sims=q.sims, key=q.key)

    def counters(self) -> dict:
        s = self.svc
        stats = s.scheduler_stats()
        occ = np.asarray(s.shard_occupancy(), np.float64)
        return {"host_syncs": s.host_syncs,
                "steps": int(stats["steps_issued"]),
                "occ": occ,
                "queue_counts": s.metrics.hists["queue"].counts.copy(),
                "queue_edges": s.metrics.hists["queue"].edges}

    # ----------------------------------------------------------------- window

    def window(self, seconds: float, tracer) -> dict:
        from repro.serving.go_service import OverCapacityError
        svc, qs = self.svc, self.queries
        before = self.counters()
        sent, answered, shed = {}, {}, set()
        t0 = time.perf_counter()
        tracer.arm(0.0)
        i = 0
        closed_at = backlog = None
        while True:
            now = time.perf_counter() - t0
            tracer.tick(now)
            while i < len(qs) and qs[i].due_s <= now:
                with self.span("client.submit"):
                    try:
                        sent[self._submit(qs[i])] = (i, now)
                    except OverCapacityError:
                        shed.add(i)
                i += 1
                now = time.perf_counter() - t0
            if closed_at is None and i == len(qs) and now >= seconds:
                closed_at, backlog = now, svc.outstanding
            if svc.outstanding:
                with self.span("service.poll"):
                    done = svc.poll()
                got = time.perf_counter() - t0
                for t in done:
                    answered[sent[t][0]] = (got, svc.result(t, wait=False))
            elif i < len(qs):
                with self.span("client.idle"):
                    time.sleep(max(0.0, qs[i].due_s - now))
            if i == len(qs) and not svc.outstanding:
                break
            if closed_at is not None \
                    and now - closed_at - tracer.stall_s > DRAIN_S:
                break
        end = time.perf_counter() - t0
        after = self.counters()
        self.answers = answered
        lat = [answered[k][0] - qs[k].due_s for k in sorted(answered)]
        quarter = max(1, len(lat) // 4)
        # a traced run stalls while the profiler writes its trace out:
        # that stall is the tracer's, not the client's lateness
        stall = (0.0, 0.0) if tracer.t1 is None else (
            tracer.t1 - t0, tracer.t1 - t0 + tracer.stall_s)
        late = [s_now - qs[k].due_s
                - max(0.0, min(s_now, stall[1]) - max(qs[k].due_s, stall[0]))
                for k, s_now in sent.values()]
        d_steps = after["steps"] - before["steps"]
        occ_sum = (after["occ"] * after["steps"]
                   - before["occ"] * before["steps"]) * (
                       svc.slots / len(after["occ"]))
        per_shard = (after["occ"] * after["steps"]
                     - before["occ"] * before["steps"]) / max(d_steps, 1)
        return {
            "wall_s": end, "attempted": len(qs),
            "backlog_at_close": backlog, "shed": len(shed),
            # a backlog that grows over the window shows as a later
            # quarter of the queries waiting longer than the first
            "first_quarter_ms": 1e3 * float(np.mean(lat[:quarter] or [0])),
            "last_quarter_ms": 1e3 * float(np.mean(lat[-quarter:] or [0])),
            "failed": len(qs) - len(answered),
            "latency_ms": [1e3 * x for x in lat],
            "late_ms": [1e3 * x for x in late],
            "answered": len(answered),
            "host_syncs": after["host_syncs"] - before["host_syncs"],
            "steps": d_steps,
            "slot_occupancy": float(occ_sum.sum()) / max(
                d_steps * svc.slots, 1),
            "shard_occupancy": [float(x) for x in per_shard],
            "queue_hist": (after["queue_counts"] - before["queue_counts"],
                           after["queue_edges"]),
        }

    # ------------------------------------------------------------------ check

    def check(self) -> dict:
        """Every answer in the window, judged by what it says; and a
        seeded sample of them against the plain reference search."""
        r, c, qs = self.rules, self.cfg, self.queries
        illegal = budget = argmax = 0
        for k, (_, res) in self.answers.items():
            q = qs[k]
            st = State(q.state.board, q.state.to_play, NO_KO, 0, 0, False)
            legal = r.legal(st)
            v = np.asarray(res.root_visits, np.float64)
            a = int(res.action)
            illegal += not (0 <= a < legal.size and legal[a])
            granted = q.sims if 0 < q.sims <= c["max_sims"] \
                else c["max_sims"]
            budget += not (v.shape == legal.shape and np.all(v >= 0)
                           and np.all(v == np.round(v))
                           and v.sum() == granted and not v[~legal].any())
            argmax += not (0 <= a < v.size
                           and v[a] == np.where(legal, v, -1).max())
        ref, control = (RefSearch(c["board_size"], c["lanes"], c["max_sims"],
                                  c["max_nodes"], c["c_uct"],
                                  c["virtual_loss"], playout_cap=cap)
                        for cap in (0, c["control_playout_moves"]))
        # a seeded sample, with the longest search in it: the most
        # simulations, then the emptiest board (the longest playouts)
        rng = np.random.default_rng(self.seed + 2)
        keys = sorted(self.answers)
        n = min(c["ref_samples"], len(keys))
        longest = min(keys, key=lambda k: (-qs[k].sims, qs[k].prefix)) \
            if n else None
        rest = [k for k in keys if k != longest]
        sample = ([longest] + list(rng.choice(rest, n - 1, replace=False))
                  if n else [])
        mismatch = 0
        for k in sample:
            q, res = qs[k], self.answers[k][1]
            st = State(q.state.board, q.state.to_play, NO_KO, 0, 0, False)
            want = ref.search(st, q.key, q.sims, c["komi"])
            if c["control"]:             # the control answers instead
                res = control.search(st, q.key, q.sims, c["komi"])
            mismatch += not (want.action == res.action and np.array_equal(
                want.root_visits, np.asarray(res.root_visits, np.float32)))
        unanswered = len(qs) - len(self.answers)
        return {"unanswered": unanswered, "illegal_moves": illegal,
                "visit_budget_errors": budget, "not_most_visited": argmax,
                "ref_mismatch_share": mismatch / n if n else 1.0}
