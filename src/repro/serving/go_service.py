"""GoService: batched external best-move queries (queue -> ticket -> poll).

The Go-side counterpart of :class:`~repro.serving.engine.ServeEngine`'s
fixed-bucket pattern: requests are admitted into a fixed-capacity
SearchService slot pool so one compiled dispatch serves every query.  The
static bucket axes are ``(board_size, max_sims)``; the per-request
``sims`` budget, the strength knobs ``c_uct`` / ``virtual_loss``, **and
— since PR 10 — the scoring ``komi``** are *traced* (masked search tail;
per-lane scalar broadcast; per-slot komi column), so budgets from 1 to
``max_sims``, arbitrary UCT configurations, and arbitrary komis share
one executable — a caller can dial a query's exploration *and* its komi
per request with zero recompilation.

Two scheduling modes own that pool (``unified=``, default on):

* **unified** — every komi is a *bucket* inside ONE mesh-wide
  SearchService, scheduled by a single
  :class:`~repro.core.scheduler.BucketScheduler` pump/reconcile stream:
  one compiled dispatch and one pipeline serve all buckets, per-bucket
  shard partitions (+ idle-headroom borrowing) keep traffic classes
  apart, and ``pipeline_depth`` may adapt inside a static clamp
  (``max_pipeline_depth``).  Host pump cost no longer scales with
  bucket count.
* **per-bucket** (``unified=False``, the PR 6-9 shape, kept as the
  benchmark baseline) — each komi opens its own SearchService + pipeline
  and :meth:`poll` round-robins them (rotating its start bucket per
  call so no bucket eats every pump's first flush).

A query is a pure function of
``(board, to_play, sims, c_uct, virtual_loss, key)``: the dispatcher
admits serve tickets only into cells searched by the bucket's single
player, and the search consumes the request key directly, so results do
not depend on slot placement or on what else shares the batch
(tests/test_service.py and tests/test_multiplex.py pin this).

SLO discipline (the serving-tier front door contract, used by
:mod:`repro.serving.server`):

* **admission control** — :meth:`submit` rejects with
  :class:`OverCapacityError` when the bucket's queue depth crosses
  ``admission_limit`` (explicit load shedding, never silent loss);
* **deadlines** — ``deadline_ms`` threads a per-request SLO through
  submission: an unmeetable deadline is shed up front
  (:class:`DeadlineExceededError`), a tight one is *downgraded* — its
  traced ``sims`` budget is cut, which is free since budgets are traced
  (no recompile) — and a request that expires while still host-buffered
  is shed at the next :meth:`poll` (``SearchService.shed_expired``).
  Requests already flushed to the device always complete; finishing
  late only bumps the ``deadline_miss`` counter;
* **observability** — every request's queue/dispatch/total latency
  streams into :class:`~repro.serving.metrics.ServingMetrics`
  (p50/p95/p99 histograms + shed/downgrade counters), the payload the
  HTTP ``/metrics`` endpoint and benchmarks/bench_load.py read.

Typical use::

    svc = GoService(board_size=9, komi=6.0, max_sims=256)
    move = svc.best_move(board)                 # one blocking query
    tickets = [svc.submit(b) for b in boards]   # batched: queue ...
    moves = [svc.result(t) for t in tickets]    # ... then poll tickets
"""
from __future__ import annotations

import contextlib
import math
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.config import MCTSConfig
from repro.core.mcts import MCTS
from repro.core.scheduler import BucketScheduler
from repro.core.service import SearchService, pad_slots
from repro.core.streaming import DispatchPipeline
from repro.go.board import BLACK, NO_KO, GoEngine, GoState
from repro.serving.metrics import ServingMetrics


class OverCapacityError(RuntimeError):
    """Request shed at admission: bucket queue depth over the limit."""


class DeadlineExceededError(TimeoutError):
    """Request shed for its deadline: unmeetable at admission, or it
    expired while still host-buffered (never dispatched)."""


class MoveResult(NamedTuple):
    """One answered best-move query."""
    ticket: int
    action: int               # 0..n2-1 point, n2 = pass
    coord: Optional[Tuple[int, int]]   # (row, col), None for pass
    is_pass: bool
    root_visits: np.ndarray   # f32[A] root visit distribution
    sims_granted: int = 0     # playout budget actually dispatched (0 = full)
    downgraded: bool = False  # True when a deadline cut the budget
    latency_s: float = 0.0    # submit -> completion wall time


class DeadlinePolicy:
    """Admit / downgrade / shed decision for one deadline'd query.

    Linear cost model: a query admitted at queue depth ``d`` into a
    ``slots``-wide bucket waits ``waves = ceil((d + 1) / slots)`` search
    waves, each costing ``base_s + sim_cost_s * sims`` — so

        est(sims, depth) = base_s + sim_cost_s * sims * waves.

    :meth:`decide` compares the estimate against the request's remaining
    budget: full ``sims`` fits -> ``admit``; a cut budget of at least
    ``floor_sims`` fits -> ``downgrade`` (free: the budget is a traced
    dispatch input, PR 2); otherwise -> ``shed``.  ``observe`` keeps
    ``sim_cost_s`` calibrated by EWMA over completed requests, so the
    boundary tracks the machine; construct with ``calibrate=False`` for
    a fixed, deterministic policy (the unit tests do).
    """

    def __init__(self, base_s: float = 0.02, sim_cost_s: float = 1e-3,
                 floor_sims: int = 4, slots: int = 8,
                 calibrate: bool = True, ewma: float = 0.2):
        if floor_sims < 1:
            raise ValueError(f"floor_sims must be >= 1, got {floor_sims}")
        self.base_s = float(base_s)
        self.sim_cost_s = float(sim_cost_s)
        self.floor_sims = int(floor_sims)
        self.slots = max(1, int(slots))
        self.calibrate = calibrate
        self.ewma = float(ewma)

    def _waves(self, depth: int) -> int:
        return max(1, math.ceil((depth + 1) / self.slots))

    def estimate_s(self, sims: int, depth: int) -> float:
        """Predicted completion latency at the given queue depth."""
        return self.base_s + self.sim_cost_s * sims * self._waves(depth)

    def decide(self, remaining_s: Optional[float], depth: int,
               full_sims: int) -> Tuple[str, int]:
        """``("admit"|"downgrade"|"shed", granted_sims)`` for one query."""
        if remaining_s is None:
            return "admit", full_sims
        if self.estimate_s(full_sims, depth) <= remaining_s:
            return "admit", full_sims
        per_sim = self.sim_cost_s * self._waves(depth)
        fit = int((remaining_s - self.base_s) / max(per_sim, 1e-12))
        if fit >= self.floor_sims:
            return "downgrade", min(fit, full_sims)
        return "shed", 0

    def observe(self, latency_s: float, sims: int, depth: int) -> None:
        """EWMA-calibrate ``sim_cost_s`` from one completed request."""
        if not self.calibrate or sims < 1:
            return
        per_sim = max(latency_s - self.base_s, 0.0) / (
            sims * self._waves(depth))
        self.sim_cost_s += self.ewma * (per_sim - self.sim_cost_s)

    def observe_censored(self, waited_s: float, sims: int,
                         depth: int) -> None:
        """One-sided calibration from a shed or expired request.

        Learning only from completions biases the cost model optimistic
        under overload: the slowest requests are exactly the ones that
        never complete, so ``sim_cost_s`` drifts down while the machine
        drowns and the policy admits ever more unmeetable work.  A
        request shed after waiting ``waited_s`` is a *censored* sample —
        its true latency would have been at least the wait — so it may
        only pull the estimate **up** (standard censored-EWMA rule: skip
        the sample when the bound is already below the estimate).
        """
        if not self.calibrate or sims < 1:
            return
        per_sim = max(waited_s - self.base_s, 0.0) / (
            sims * self._waves(depth))
        if per_sim > self.sim_cost_s:
            self.sim_cost_s += self.ewma * (per_sim - self.sim_cost_s)


class _Ticket:
    """Host-side lifecycle record of one submitted query."""

    __slots__ = ("komi", "inner", "t_submit", "t_flush", "deadline",
                 "sims_granted", "downgraded", "depth")

    def __init__(self, komi: float, inner: int, t_submit: float,
                 deadline: Optional[float], sims_granted: int,
                 downgraded: bool, depth: int):
        self.komi = komi
        self.inner = inner              # SearchService ticket
        self.t_submit = t_submit
        self.t_flush: Optional[float] = None
        self.deadline = deadline        # absolute monotonic, None = no SLO
        self.sims_granted = sims_granted
        self.downgraded = downgraded
        self.depth = depth              # bucket queue depth at admission


class GoService:
    """Fixed-bucket batched Go move service over SearchService pools.

    ``mesh=`` shards every bucket's slot pool over a one-axis device mesh
    (``placement`` routes queries to shards, core/placement.py); serve
    answers are placement-independent by the dispatcher's RNG contract,
    so sharding only changes throughput, never a move.

    ``unified`` (default) schedules every komi bucket inside ONE shared
    SearchService pool via a
    :class:`~repro.core.scheduler.BucketScheduler`: one compiled
    dispatch, one pump/reconcile stream, per-bucket shard partitions
    with idle-headroom ``borrowing``.  With a single bucket, borrowing
    moot, and a fixed depth this is bit-identical (results *and* host
    syncs) to the per-bucket path; with many buckets it answers the
    same queries with one pump's host cost instead of one per bucket.
    ``unified=False`` keeps the PR 6-9 one-pool-per-komi shape (each
    new komi compiles its own bucket).

    ``pipeline_depth`` streams the serve loop: :meth:`poll` keeps up to
    that many supersteps in flight instead of awaiting each one —
    queued queries, result unpacking, and placement overlap with device
    search.  Answers are unchanged at any depth (the serve RNG contract
    makes them pure functions of the query).  In unified mode the depth
    may *adapt*: ``max_pipeline_depth > pipeline_depth`` (or
    ``adaptive_depth=True``) engages a
    :class:`~repro.core.scheduler.DepthController` that raises the
    window when the device runs ahead of the host and lowers it when
    reconciles block, clamped to the static ``max_pipeline_depth`` —
    depth only changes host read timing, so adaptation never compiles a
    new trace.

    ``admission_limit`` (0 = the bucket queue capacity) bounds each
    bucket's outstanding requests — :meth:`submit` sheds past it — and
    ``deadline_policy`` decides admit/downgrade/shed for deadline'd
    queries (see :class:`DeadlinePolicy`; the default self-calibrates).
    Neither knob touches the device: shedding happens before flush and
    downgrading rides the traced ``sims`` budget, so SLO enforcement
    adds **zero** new jit traces (tests/test_server.py asserts the
    compile count).

    Extra keyword arguments flow to :class:`~repro.core.mcts.MCTS` — in
    particular ``evaluator=EvalService(...)`` puts every bucket on the
    neural evaluation lane, after which the per-query ``prior_weight``
    knob blends UCT toward PUCT per request without a new trace
    (``prior_weight=0`` stays bit-identical to the unguided service).
    """

    def __init__(self, board_size: int = 9, komi: float = 6.0,
                 max_sims: int = 64, lanes: int = 8, slots: int = 8,
                 max_nodes: int = 0, superstep: int = 2, seed: int = 0,
                 queue_capacity: int = 0, mesh=None,
                 placement: str = "round_robin", pipeline_depth: int = 1,
                 admission_limit: int = 0,
                 deadline_policy: Optional[DeadlinePolicy] = None,
                 metrics: Optional[ServingMetrics] = None,
                 unified: bool = True, max_pipeline_depth: int = 0,
                 adaptive_depth: Optional[bool] = None,
                 borrowing: bool = True,
                 **mcts_kw):
        self.board_size = int(board_size)
        self.default_komi = float(komi)
        self.max_sims = int(max_sims)
        self.lanes = int(lanes)
        self.mesh = mesh
        self.placement = placement
        # pad the pool so every mesh shard gets an even share of slots
        self.slots = pad_slots(slots, mesh)
        self.max_nodes = int(max_nodes) or max(256, 4 * max_sims)
        self.superstep = superstep
        self.seed = seed
        self.queue_capacity = queue_capacity or 4 * self.slots
        self.pipeline_depth = int(pipeline_depth)
        self.admission_limit = int(admission_limit) or self.queue_capacity
        self.deadline_policy = deadline_policy or DeadlinePolicy(
            slots=self.slots)
        self.metrics = metrics or ServingMetrics()
        self.unified = bool(unified)
        # static depth clamp; > pipeline_depth gives the adaptive
        # controller headroom to raise the in-flight window
        self.max_pipeline_depth = (int(max_pipeline_depth)
                                   or self.pipeline_depth)
        if adaptive_depth is None:
            adaptive_depth = self.max_pipeline_depth > self.pipeline_depth
        self.adaptive_depth = bool(adaptive_depth)
        self.borrowing = bool(borrowing)
        self.mcts_kw = mcts_kw
        self._buckets: Dict[float, SearchService] = {}
        self._pipes: Dict[float, DispatchPipeline] = {}  # komi -> pipeline
        self._sched: Optional[BucketScheduler] = None
        self._poll_rot = 0        # per-bucket path: rotating pump offset
        self._tickets: Dict[int, _Ticket] = {}
        self._done: Dict[int, MoveResult] = {}
        self._shed_tickets: Dict[int, str] = {}    # ticket -> reason
        self._shed_new: List[int] = []             # shed since last pop_shed
        self._next_ticket = 0
        self._rng = np.random.default_rng(seed)
        self.host_busy_s = 0.0    # submit + poll time not spent blocked
        if self.unified:
            svc = self._make_service(self.default_komi)
            self._buckets[self.default_komi] = svc
            self._sched = BucketScheduler(
                svc, depth=self.pipeline_depth,
                adaptive=self.adaptive_depth,
                max_depth=max(self.max_pipeline_depth, self.pipeline_depth),
                borrowing=self.borrowing)
            self._sched.bucket(self.default_komi)
        else:
            self._bucket(self.default_komi)   # compile the default bucket

    # ---------------------------------------------------------------- bucket

    def _make_service(self, komi: float) -> SearchService:
        """Build + reset one SearchService pool scored at ``komi``."""
        engine = GoEngine(self.board_size, komi=komi)
        cfg = MCTSConfig(board_size=self.board_size, komi=komi,
                         lanes=self.lanes, sims_per_move=self.max_sims,
                         max_nodes=self.max_nodes)
        player = MCTS(engine, cfg, **self.mcts_kw)
        svc = SearchService(engine, player, player, self.slots,
                            superstep=self.superstep, mesh=self.mesh,
                            placement=self.placement,
                            pipeline_depth=self.pipeline_depth)
        svc.reset(seed=self.seed, serve_capacity=self.queue_capacity,
                  game_capacity=2)
        return svc

    def _bucket(self, komi: float) -> SearchService:
        """The pool serving ``komi``: the shared one (unified — the komi
        just registers a scheduler bucket) or the komi's own (legacy)."""
        if self.unified:
            self._sched.bucket(komi)
            return self._buckets[self.default_komi]
        svc = self._buckets.get(komi)
        if svc is None:
            svc = self._make_service(komi)
            self._buckets[komi] = svc
            self._pipes[komi] = DispatchPipeline(svc)
        return svc

    @property
    def host_syncs(self) -> int:
        """Total blocking host<->device round-trips across all buckets."""
        return sum(b.host_syncs for b in self._buckets.values())

    @property
    def host_blocked_s(self) -> float:
        """Total time spent waiting on devices across all buckets."""
        return sum(b.host_blocked_s for b in self._buckets.values())

    @contextlib.contextmanager
    def _host_span(self, name: str, **args):
        """A profiler span ``name`` whose time, less the blocking device
        waits inside it (``host_blocked_s`` growth), adds to
        ``host_busy_s``."""
        t0, blocked0 = time.perf_counter(), self.host_blocked_s
        try:
            with TraceAnnotation(name, **args):
                yield
        finally:
            self.host_busy_s += (time.perf_counter() - t0) \
                - (self.host_blocked_s - blocked0)

    @property
    def outstanding(self) -> int:
        """Submitted but neither answered nor shed, across all buckets."""
        return sum(b.outstanding for b in self._buckets.values())

    def shard_occupancy(self, komi: Optional[float] = None) -> np.ndarray:
        """Per-shard occupancy, aggregated across buckets.

        Unified mode has one pool, so every komi reads the same global
        occupancy.  Per-bucket mode returns the komi's own pool, or —
        with ``komi=None`` — the element-wise mean over all buckets'
        pools (each bucket owns a full ``slots``-wide pool there, so the
        mean is the fleet-level utilisation a capacity planner wants;
        with one bucket it degenerates to that bucket, the historical
        behaviour).
        """
        if self.unified:
            return self._buckets[self.default_komi].shard_occupancy()
        if komi is not None:
            return self._bucket(float(komi)).shard_occupancy()
        occ = [svc.shard_occupancy() for svc in self._buckets.values()]
        return np.mean(occ, axis=0)

    def scheduler_stats(self) -> dict:
        """Scheduler telemetry for ``/metrics``: per-bucket occupancy,
        queue depth, and the in-flight superstep count.

        Unified mode reports the single pipeline (current + max depth,
        adaptive-controller state) plus per-bucket queue depths and
        shard-partition sizes; per-bucket mode reports each bucket's own
        pipeline window.
        """
        if self.unified:
            s = self._sched.stats()
            s["unified"] = True
            s["in_flight_supersteps"] = self._sched.in_flight_supersteps
            s["per_bucket"] = {
                str(k): v for k, v in self._sched.bucket_stats().items()}
            return s
        return {
            "unified": False,
            "buckets": len(self._buckets),
            "per_bucket": {
                str(komi): {
                    "queue_depth": svc.outstanding,
                    "in_flight_supersteps":
                        self._pipes[komi].in_flight_supersteps,
                }
                for komi, svc in self._buckets.items()},
        }

    def _to_state(self, board, to_play: int, engine: GoEngine) -> GoState:
        b = np.asarray(board, np.int8).reshape(-1)
        if b.shape[0] != engine.n2:
            raise ValueError(f"board must have {engine.n2} points for "
                             f"{self.board_size}x{self.board_size}, "
                             f"got {b.shape[0]}")
        return GoState(board=jnp.asarray(b),
                       to_play=jnp.int8(to_play),
                       ko=jnp.int32(NO_KO),
                       pass_count=jnp.int32(0),
                       move_count=jnp.int32(0),
                       done=jnp.bool_(False))

    # ----------------------------------------------------------------- queue

    def submit(self, board, to_play: int = BLACK,
               komi: Optional[float] = None, sims: int = 0,
               key=None, c_uct: Optional[float] = None,
               virtual_loss: Optional[float] = None,
               prior_weight: Optional[float] = None,
               deadline_ms: Optional[float] = None) -> int:
        """Queue one best-move query; returns a ticket for :meth:`result`.

        Traced per-query knobs (no recompilation across values): ``sims``
        caps the playout budget (0 / > max_sims both mean ``max_sims``);
        ``c_uct`` / ``virtual_loss`` override the bucket's UCT constants
        (``None`` keeps the bucket defaults, bit-identical to omitting
        them); ``prior_weight`` sets the eval-lane UCT<->PUCT blend when
        the service was built with ``evaluator=`` (an
        :class:`repro.core.evaluator.EvalService` in ``mcts_kw``) — it is
        silently inert otherwise.  ``komi`` is traced too in unified
        mode (a new value just registers a scheduler bucket — zero
        recompilation); with ``unified=False`` it is static and a new
        value compiles its own pool.  ``key`` fixes the search RNG for
        reproducible answers (default: drawn from the service chain).

        SLO path: admission is queue-depth gated — past
        ``admission_limit`` outstanding requests in the bucket the query
        is shed with :class:`OverCapacityError` (counted
        ``shed_overload``).  ``deadline_ms`` (relative, wall) runs the
        :class:`DeadlinePolicy`: ``admit`` keeps the full budget,
        ``downgrade`` cuts the traced ``sims`` (counted; visible on the
        result), ``shed`` raises :class:`DeadlineExceededError` (counted
        ``shed_deadline``).  With ``deadline_ms=None`` the submission is
        bit-identical to the pre-SLO path.

        The time spent here, less any blocking device wait, counts into
        ``host_busy_s``.
        """
        with self._host_span("serving.submit", ticket=self._next_ticket):
            komi = self.default_komi if komi is None else float(komi)
            svc = self._bucket(komi)
            now = time.monotonic()
            depth = (self._sched.buckets[komi].outstanding if self.unified
                     else svc.outstanding)
            if depth >= self.admission_limit:
                self.metrics.bump("shed_overload")
                raise OverCapacityError(
                    f"bucket komi={komi} over capacity: {depth} outstanding "
                    f">= admission limit {self.admission_limit}")
            full = (int(sims) if 0 < int(sims) <= self.max_sims
                    else self.max_sims)
            deadline = None
            granted, downgraded = full, False
            if deadline_ms is not None:
                remaining = float(deadline_ms) / 1e3
                deadline = now + remaining
                verdict, granted = self.deadline_policy.decide(
                    remaining, depth, full)
                if verdict == "shed":
                    self.metrics.bump("shed_deadline")
                    floor_est = self.deadline_policy.estimate_s(
                        self.deadline_policy.floor_sims, depth)
                    raise DeadlineExceededError(
                        f"deadline {deadline_ms:.0f}ms unmeetable at queue "
                        f"depth {depth} (~{floor_est * 1e3:.0f}ms needed at "
                        "the floor budget)")
                downgraded = verdict == "downgrade"
                if downgraded:
                    self.metrics.bump("downgraded")
            if key is None:
                key = self._rng.integers(0, 2 ** 32, size=(2,),
                                         dtype=np.uint32)
            state = self._to_state(board, to_play, svc.engine)
            if self.unified:
                inner = self._sched.submit_serve(
                    komi, state, key=key, sims=granted, c_uct=c_uct,
                    virtual_loss=virtual_loss, prior_weight=prior_weight,
                    deadline=deadline)
            else:
                inner = svc.submit_serve(
                    state, key=key, sims=granted, c_uct=c_uct,
                    virtual_loss=virtual_loss, prior_weight=prior_weight,
                    deadline=deadline)
            ticket = self._next_ticket
            self._next_ticket += 1
            self._tickets[ticket] = _Ticket(komi, inner, now, deadline,
                                            granted, downgraded, depth)
            self.metrics.bump("submitted")
            return ticket

    def flush(self) -> None:
        """Push every bucket's queued submissions to its device queues."""
        for svc in self._buckets.values():
            svc.flush()
        self._mark_flushed(time.monotonic())

    def _mark_flushed(self, now: float,
                      komi: Optional[float] = None) -> None:
        """Stamp queue-exit time on tickets that just left the host."""
        for t in self._tickets.values():
            if t.t_flush is None and (komi is None or t.komi == komi):
                t.t_flush = now

    def _shed_ticket(self, ticket: int, reason: str) -> None:
        self._shed_tickets[ticket] = reason
        self._shed_new.append(ticket)
        self.metrics.bump("shed_deadline")

    def pop_shed(self) -> Dict[int, str]:
        """Drain tickets shed since the last call (``ticket -> reason``).

        The HTTP front door's pump loop uses this to fail the matching
        waiters; :meth:`result` reports the same tickets by raising
        :class:`DeadlineExceededError`.
        """
        out = {t: self._shed_tickets[t] for t in self._shed_new}
        self._shed_new.clear()
        return out

    def _shed_with_calibration(self, ticket: int, now: float) -> None:
        """Shed one expired ticket; its wait is a censored latency
        sample, so it still calibrates the deadline policy (one-sided)."""
        t = self._tickets[ticket]
        self.deadline_policy.observe_censored(
            now - t.t_submit, t.sims_granted, t.depth)
        self._shed_ticket(ticket, "deadline")

    def _record_done(self, ticket: int, rec, engine: GoEngine) -> None:
        """Unpack one reconcile record into its ticket's MoveResult and
        land the request's stage latencies in the metrics + policy."""
        is_pass = rec.action >= engine.n2
        coord = (None if is_pass else
                 (rec.action // self.board_size,
                  rec.action % self.board_size))
        t = self._tickets[ticket]
        t_done = time.monotonic()
        total = t_done - t.t_submit
        queue = (t.t_flush - t.t_submit
                 if t.t_flush is not None else None)
        dispatch = (t_done - t.t_flush
                    if t.t_flush is not None else None)
        missed = t.deadline is not None and t_done > t.deadline
        self.metrics.observe(queue, dispatch, total,
                             deadline_missed=missed)
        self.deadline_policy.observe(total, t.sims_granted, t.depth)
        self._done[ticket] = MoveResult(
            ticket=ticket, action=rec.action, coord=coord,
            is_pass=is_pass, root_visits=rec.root_visits,
            sims_granted=t.sims_granted, downgraded=t.downgraded,
            latency_s=total)

    def poll(self) -> List[int]:
        """Pump the scheduler (or every bucket's pipeline); returns
        newly done tickets.

        Each call sheds expired host-buffered queries
        (``SearchService.shed_expired`` — they never reach the device,
        and their waits calibrate the deadline policy as censored
        samples), flushes the rest, tops the in-flight window(s) up to
        the pipeline depth, and reconciles the oldest superstep — at
        depth 1 exactly the old flush -> dispatch -> poll superstep;
        deeper windows leave the device running while the host unpacks
        answers.  Unified mode does all of this **once** for every
        bucket (one pump, one reconcile — host cost independent of
        bucket count); per-bucket mode loops the buckets, rotating the
        start offset each call so every bucket periodically gets the
        round's first flush.  Completed requests land their
        queue/dispatch/total latencies in :attr:`metrics` and
        recalibrate the deadline policy.  The call's time, less its
        blocking device waits, counts into ``host_busy_s``.
        """
        with self._host_span("serving.poll"):
            if self.unified:
                return self._poll_unified()
            done = []
            inner_to_ticket = {(t.komi, t.inner): ticket
                               for ticket, t in self._tickets.items()
                               if ticket not in self._done
                               and ticket not in self._shed_tickets}
            items = list(self._buckets.items())
            if len(items) > 1:            # pump fairness: rotate the start
                off = self._poll_rot % len(items)
                self._poll_rot += 1
                items = items[off:] + items[:off]
            for komi, svc in items:
                if svc.outstanding == 0:
                    continue
                now = time.monotonic()
                for inner in svc.shed_expired(now):
                    ticket = inner_to_ticket.pop((komi, inner), None)
                    if ticket is not None:
                        self._shed_with_calibration(ticket, now)
                pipe = self._pipes[komi]
                pipe.pump()
                self._mark_flushed(time.monotonic(), komi=komi)
                for rec in pipe.reconcile():
                    ticket = inner_to_ticket.get((komi, rec.ticket))
                    if ticket is None:
                        continue        # a game lane sharing the bucket
                    self._record_done(ticket, rec, svc.engine)
                    done.append(ticket)
            return done

    def _poll_unified(self) -> List[int]:
        """One scheduler round: shed, pump once, reconcile once —
        every bucket's work moves in a single superstep stream."""
        done: List[int] = []
        svc = self._buckets[self.default_komi]
        if svc.outstanding == 0:
            return done
        inner_to_ticket = {t.inner: ticket
                           for ticket, t in self._tickets.items()
                           if ticket not in self._done
                           and ticket not in self._shed_tickets}
        now = time.monotonic()
        for inner in self._sched.shed_expired(now):
            ticket = inner_to_ticket.pop(inner, None)
            if ticket is not None:
                self._shed_with_calibration(ticket, now)
        self._sched.pump()
        self._mark_flushed(time.monotonic())
        for rec in self._sched.reconcile():
            ticket = inner_to_ticket.get(rec.ticket)
            if ticket is None:
                continue            # a game lane sharing the pool
            self._record_done(ticket, rec, svc.engine)
            done.append(ticket)
        return done

    def result(self, ticket: int, wait: bool = True,
               timeout_s: Optional[float] = None,
               max_polls: int = 10_000) -> Optional[MoveResult]:
        """Fetch a ticket's move; blocks (dispatching) unless ``wait=False``.

        ``timeout_s`` bounds the blocking wait in wall time and raises
        ``TimeoutError`` past it — without a timeout a lost ticket could
        spin the poll loop for ``max_polls`` rounds before the fallback
        ``RuntimeError``, which is the hang the HTTP server must never
        inherit.  A ticket shed for its deadline raises
        :class:`DeadlineExceededError`; an unknown one raises
        ``KeyError``.
        """
        if ticket not in self._tickets:
            raise KeyError(f"unknown ticket {ticket}")
        t0 = time.monotonic()
        polls = 0
        while ticket not in self._done:
            if ticket in self._shed_tickets:
                reason = self._shed_tickets.pop(ticket)
                del self._tickets[ticket]
                raise DeadlineExceededError(
                    f"ticket {ticket} was shed ({reason}) before dispatch")
            if not wait:
                return None
            if timeout_s is not None \
                    and time.monotonic() - t0 > timeout_s:
                raise TimeoutError(
                    f"ticket {ticket} not done within {timeout_s:.3f}s "
                    f"({polls} polls; the bucket may be overloaded — "
                    "raise timeout_s or shed load)")
            if polls >= max_polls:
                raise RuntimeError(f"ticket {ticket} not done after "
                                   f"{polls} polls")
            self.poll()
            polls += 1
        del self._tickets[ticket]
        return self._done.pop(ticket)

    # ------------------------------------------------------------ one-liners

    def best_move(self, board, to_play: int = BLACK,
                  komi: Optional[float] = None, sims: int = 0,
                  key=None, c_uct: Optional[float] = None,
                  virtual_loss: Optional[float] = None,
                  prior_weight: Optional[float] = None,
                  deadline_ms: Optional[float] = None,
                  timeout_s: Optional[float] = None) -> MoveResult:
        """Blocking single query: board in, move out.

        ``sims`` / ``c_uct`` / ``virtual_loss`` / ``prior_weight`` are
        the traced per-query knobs of :meth:`submit` (they never
        recompile the bucket); ``deadline_ms`` engages the SLO path
        (downgrade or shed) and ``timeout_s`` bounds the blocking wait.
        """
        return self.result(self.submit(board, to_play, komi, sims, key,
                                       c_uct=c_uct,
                                       virtual_loss=virtual_loss,
                                       prior_weight=prior_weight,
                                       deadline_ms=deadline_ms),
                           timeout_s=timeout_s)

    def best_move_batch(self, boards, to_play: int = BLACK,
                        komi: Optional[float] = None, sims: int = 0,
                        prior_weight: Optional[float] = None,
                        ) -> List[MoveResult]:
        """Queue a batch of queries, then poll them all (one pool pass)."""
        tickets = [self.submit(b, to_play, komi, sims,
                               prior_weight=prior_weight) for b in boards]
        self.flush()
        return [self.result(t) for t in tickets]
