"""Unified SearchService: one batched search dispatcher for every consumer.

The Xeon Phi papers' scaling lesson is that MCTS throughput is set by how
search work is *scheduled* onto the hardware, and the 2015 follow-up shows
a work-queue dispatch model recovering the scaling that thread-per-search
loses.  The jax_pallas analogue is a single admission-controlled batch:
every workload — arena self-play (core/arena.py), external best-move
queries (serving/go_service.py), tournament pairings (core/tournament.py)
— submits :class:`SearchRequest` tickets into one device-resident slot
pool, and one jitted ``dispatch`` step advances all ``S`` slots together:

* **Admission** (the device-side refill): empty slots pull requests from
  device-resident pending queues (a pending counter per queue, no host
  round-trip).  Full-game requests are colour-capped exactly like the PR 1
  host queue (alternating colours, at most +-1 imbalance), so device
  refill is bit-for-bit the host refill; a game may additionally carry a
  **forced colour** (``submit_game(a_black=...)``) and is then admitted
  only into a matching cell — the colour-targeted admission the league's
  per-pairing +-1 ledger (core/league.py) rides on.  Serve requests are
  admitted only into cells that player A searches on the next step, making
  a query's result independent of slot placement and batch-mates.
* **Search**: the parity-balanced roll-by-half from PR 1 — one
  ``player_a.search_batch`` over half the slots, one ``player_b`` over the
  other, exactly one search per move.  The per-slot ``sims`` budget and
  the per-slot, per-side ``(c_uct, vl_weight)`` UCT knobs are *traced*
  arguments (masked loop tail; per-lane scalar broadcast), so mixed
  budgets **and mixed search configurations** share one compiled program
  — the 2015 follow-up's lesson that task-level parallelism scales only
  when differently-configured searches stay resident without re-setup.
* **Scatter**: finished requests (game over, or a serve query's single
  search) are appended to a device-resident result ring buffer; their
  slots empty and refill on the next step's admission.

The host only (a) flushes submitted requests in fixed-size chunks and
(b) polls the ring buffer — both amortised over ``superstep`` dispatch
steps, cutting the per-step host sync of the PR 1 arena loop to
``~2/superstep`` per move (``host_syncs`` counts them;
benchmarks/bench_service.py proves the reduction).

Streaming (``pipeline_depth > 1``): the host<->device boundary is double
buffered.  Every queue and the result ring are functionally updated by
the jitted dispatch, so each issued superstep leaves behind an immutable
*back buffer* of the ring while the device keeps appending to the fresh
*front* buffers; :meth:`SearchService.dispatch_async` captures that back
buffer as a :class:`RingView` completion handle, and the
:class:`~repro.core.streaming.DispatchPipeline` keeps up to
``pipeline_depth`` supersteps in flight, reconciling each view as it
lands.  Because a view's buffers are never touched by later supersteps,
reconciling superstep ``i`` blocks only until *its* computation finishes
(a raw ``device_get`` on the snapshot — an enqueued gather would queue
behind the whole in-flight window), so host-side result processing,
request packing, and placement overlap with device compute —
``host_blocked_s`` measures exactly the time that overlap removes.
Results complete **out of superstep order** across shards and lanes; the
ordering contract is explicit in the pytree types: every
:class:`SearchResult` is identified by its ``ticket`` (never by arrival
position) and stamps ``finished_step``, the device dispatch step that
completed it.  ``pipeline_depth=1`` *is* the synchronous PR 4 path,
bit for bit (pinned in tests/test_pipeline.py).

RNG contract:

* game lanes: a slot splits ``key -> (key, ka, kb)`` once per step like
  ``selfplay.play_game``, so a game with key K is bit-identical to the
  sequential oracle;
* serve lane: the search uses the request key *directly* — a query
  ``(state, key, sims)`` returns exactly
  ``player_a.search_batch(state[None], key[None], sims[None])``.

Sharding (``mesh=``): the pool splits into ``n_shard`` fully independent
sub-pools — each shard owns ``slots / n_shard`` slots plus its *own*
pending queues, result ring, colour counter, and parity — and the jitted
dispatch runs under ``jax.shard_map`` so every shard steps
on its own device with no per-step collective.  A host-side
:class:`~repro.core.placement.PlacementPolicy` decides which shard admits
each submission (the paper's KMP_AFFINITY axis applied to requests), and
an optional once-per-superstep rebalance ``ppermute``\\ s surplus pending
games around the shard ring so one hot shard doesn't become the paper's
32-thread knee.  With one shard the body degenerates to the exact
single-device program, so ``mesh`` over one device is bit-identical to
``mesh=None`` (pinned in tests/test_sharded_service.py).

Tracing: the host marks its boundaries with ``jax.profiler``
``TraceAnnotation`` spans (``dispatcher.flush`` / ``pack`` / ``issue`` /
``wait_count`` / ``read_rows`` / ``unpack``, ``scheduler.shed``), and the
device program names its phases with ``jax.named_scope``
(``service_admit``, ``service_ring``; under a mesh ``service_rebalance``
and ``service_push``; the search's in core/mcts.py and go/board.py).
With the profiler off a span costs one flag check; the scopes change
only HLO metadata (pinned in tests/test_tracing.py).  ``read_rows_s``
adds up the wall time of every ``dispatcher.read_rows`` span, its
blocking transfer included: the host's per-shard ring reads.
"""
from __future__ import annotations

import functools
import time
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.mcts import MCTS, SearchParams
from repro.core.placement import CLS_GAME, CLS_SERVE, PlacementPolicy
from repro.go.board import GoEngine, GoState

# Request lanes, tagged by origin.
LANE_ARENA = 0        # arena self-play slot (full game)
LANE_SERVE = 1        # external best-move query (single search)
LANE_TOURNAMENT = 2   # tournament pairing slot (full game)

GAME_LANES = (LANE_ARENA, LANE_TOURNAMENT)
LANE_NAMES = {LANE_ARENA: "arena", LANE_SERVE: "serve",
              LANE_TOURNAMENT: "tournament"}


class SearchRequest(NamedTuple):
    """One pending request (device pytree; leading axis = queue/chunk).

    ``sims`` / ``c_uct`` / ``vl`` / ``pw`` are **per-side pairs**: column
    0 configures searches run by player A (the serve-lane player), column
    1 those run by player B.  All four are traced through the dispatch —
    a pool multiplexes arbitrarily many (c_uct, virtual_loss, sims,
    prior_weight) configurations with one compiled program.  ``pw`` is
    the evaluation-lane blend weight; it only takes effect on sides
    whose player carries an evaluator (elsewhere that side's scoring
    keeps the static no-eval program).

    ``komi`` (PR 10) is the request's *scoring* komi — one traced f32
    per request (both sides score the same game), defaulting to the
    engine's static value.  It feeds every playout outcome and the
    game-lane winner, so one compiled dispatch serves every komi bucket
    (the unified multi-bucket scheduler contract, core/scheduler.py).
    """
    state: GoState        # root position (games start from the empty board)
    key: jax.Array        # u32[2] request RNG key
    lane: jax.Array       # i32 origin tag (LANE_*)
    sims: jax.Array       # i32[2] playout budget/side; <=0 = configured one
    c_uct: jax.Array      # f32[2] UCT exploration constant per side
    vl: jax.Array         # f32[2] virtual-loss weight per side
    pw: jax.Array         # f32[2] eval-lane prior blend weight per side
    komi: jax.Array       # f32 scoring komi (traced; engine default)
    colour: jax.Array     # i32 forced colour: 1 A=Black, 0 A=White, -1 free
    ticket: jax.Array     # i32 service-assigned id


class SearchResult(NamedTuple):
    """One completed request, scattered back from the ring (host scalars).

    Ordering contract (the streaming-pipeline invariant): results are
    identified by ``ticket``, **never** by arrival position.  With
    ``pipeline_depth > 1`` completions land out of superstep order —
    shards drain independently and a long game outlives the serve
    queries admitted after it — so the only order guarantees are (a)
    FIFO per shard within one poll and (b) ``finished_step`` is the
    device dispatch step (since reset) that completed the request, a
    total order *within* a shard.  Consumers key results by ticket
    (Arena/Tournament/GoService all do).
    """
    ticket: int
    lane: int
    action: int               # move chosen by the final (serve: only) search
    winner: float             # +1 black / -1 white / 0 draw (game lanes)
    moves: int                # moves played (serve: 1)
    tree_nodes: int           # final search's tree size (Fig. 12 metric)
    a_is_black: bool          # game lanes: colour assignment
    root_visits: np.ndarray   # f32[A] final root visit distribution
    finished_step: int = -1   # dispatch step (since reset) of completion


class _Pending(NamedTuple):
    """Host-buffered submission awaiting flush().

    ``deadline`` is host-only metadata (absolute ``time.monotonic``
    seconds, ``None`` = no SLO): it never reaches the device, so carrying
    it cannot retrace the dispatch — late requests are dropped *before*
    they flush (:meth:`SearchService.shed_expired`), and deadline-driven
    budget cuts ride the already-traced ``sims`` columns.
    """
    state: GoState
    key: np.ndarray
    lane: int
    sims: tuple           # (A-side, B-side) playout budgets
    c_uct: tuple          # (A-side, B-side) exploration constants
    vl: tuple             # (A-side, B-side) virtual-loss weights
    pw: tuple             # (A-side, B-side) eval-lane prior blend weights
    komi: float           # scoring komi (engine default unless overridden)
    ticket: int
    shard: int
    deadline: Optional[float] = None
    colour: int = -1      # forced colour: 1 A=Black, 0 A=White, -1 free


class _Slots(NamedTuple):
    """Device-resident slot pool, batched over the S slots."""
    states: GoState       # current position per slot
    keys: jax.Array       # u32[S,2] per-slot RNG chains
    ticket: jax.Array     # i32[S] active request id, -1 = dummy slot
    lane: jax.Array       # i32[S]
    moves: jax.Array      # i32[S] moves played by the active request
    sims: jax.Array       # i32[S,2] per-request playout budget per side
    c_uct: jax.Array      # f32[S,2] per-request c_uct per side (traced)
    vl: jax.Array         # f32[S,2] per-request vl weight per side (traced)
    pw: jax.Array         # f32[S,2] per-request prior blend per side (traced)
    komi: jax.Array       # f32[S] per-request scoring komi (traced)
    a_black: jax.Array    # bool[S] player A owns Black (game lanes)


class _Queue(NamedTuple):
    """Device-resident circular pending queue (capacity Q)."""
    states: GoState
    keys: jax.Array       # u32[Q,2]
    lane: jax.Array       # i32[Q]
    sims: jax.Array       # i32[Q,2]
    c_uct: jax.Array      # f32[Q,2]
    vl: jax.Array         # f32[Q,2]
    pw: jax.Array         # f32[Q,2]
    komi: jax.Array       # f32[Q]
    colour: jax.Array     # i32[Q] forced colour demand (-1 = free)
    ticket: jax.Array     # i32[Q]
    size: jax.Array       # i32: total ever enqueued
    head: jax.Array       # i32: total ever admitted (next to admit)


class _Ring(NamedTuple):
    """Device-resident circular result buffer (capacity R).

    Functionally updated each dispatch step, so a host-held reference to
    a superstep's ring is an immutable back buffer (see
    :class:`RingView`): rows are ticket-tagged and ``step``-stamped so
    completions stay identifiable however far out of superstep order the
    host reads them.
    """
    ticket: jax.Array     # i32[R]
    lane: jax.Array       # i32[R]
    action: jax.Array     # i32[R]
    winner: jax.Array     # f32[R]
    moves: jax.Array      # i32[R]
    nodes: jax.Array      # i32[R]
    a_black: jax.Array    # bool[R]
    visits: jax.Array     # f32[R,A]
    step: jax.Array       # i32[R] dispatch step that completed the row
    count: jax.Array      # i32: total ever appended


class PoolState(NamedTuple):
    """Everything the jitted dispatch step owns (one shard's worth).

    The jit boundary splits this into a donatable *work* half (``ring``
    replaced by ``None``) and the ring: supersteps may reuse the work
    buffers in place on backends with donation, while every ring the
    host snapshotted stays immutable (the dispatch donates its work half).
    """
    slots: _Slots
    games: _Queue         # full-game requests (arena + tournament lanes)
    serve: _Queue         # single-search queries
    ring: Optional[_Ring]     # None inside the jit's donated work half
    colour_count: jax.Array   # i32[2]; index 1 = games where A owns Black
    colour_cap: jax.Array     # i32 per-colour admission budget
    parity: jax.Array         # i32 global move parity (0 => Black to move)
    occ_sum: jax.Array        # i32 sum over steps of occupied slots
    occ_steps: jax.Array      # i32 dispatch steps run (occupancy denominator)
    eval_sum: jax.Array       # i32 sum over steps of live eval-guided slots
    hop_idx: jax.Array        # i32 rebalance hop-schedule cursor


class RingView(NamedTuple):
    """Completion handle for one issued superstep (a ring back buffer).

    ``dispatch_async`` returns the result ring exactly as the issued
    superstep leaves it; later supersteps append to *fresh* buffers, so
    polling this view blocks only until its own superstep finishes.
    ``epoch`` invalidates views across :meth:`SearchService.reset`.
    """
    ring: _Ring
    steps: int            # dispatch steps this superstep ran
    epoch: int            # service reset() generation that issued it


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def pad_slots(slots: int, mesh=None) -> int:
    """Round ``slots`` up so every mesh shard gets an even share >= 2.

    The helper consumers (Tournament, GoService) use to pick a pool size
    that satisfies the SearchService divisibility check for ``mesh``.
    """
    n = 1 if mesh is None else int(np.prod(mesh.devices.shape))
    per = 2 * n
    return max(per, slots + (-slots) % per)


def _excl_cumsum(mask: jax.Array) -> jax.Array:
    m = mask.astype(jnp.int32)
    return jnp.cumsum(m) - m


def _queue_push(q: _Queue, req: SearchRequest, n: jax.Array) -> _Queue:
    """Append the first ``n`` rows of a fixed-size request chunk."""
    chunk = req.lane.shape[0]
    cap = q.lane.shape[0]
    arange = jnp.arange(chunk, dtype=jnp.int32)
    idx = jnp.where(arange < n, (q.size + arange) % cap, cap)  # cap: dropped

    def put(buf, val):
        return buf.at[idx].set(val, mode="drop")

    return q._replace(
        states=jax.tree.map(put, q.states, req.state),
        keys=put(q.keys, req.key),
        lane=put(q.lane, req.lane),
        sims=put(q.sims, req.sims),
        c_uct=put(q.c_uct, req.c_uct),
        vl=put(q.vl, req.vl),
        pw=put(q.pw, req.pw),
        komi=put(q.komi, req.komi),
        colour=put(q.colour, req.colour),
        ticket=put(q.ticket, req.ticket),
        size=q.size + n,
    )


class SearchService:
    """S-slot batched dispatcher bound to an engine and two MCTS players.

    Player A searches the first half-batch at even parity (and, by the
    admission rule, every serve query); games alternate which player owns
    Black under the colour cap.  All static search shapes (lanes, budget
    bound, tree capacity, board) live in the players — one service, one
    compiled dispatch.

    Traced-vs-static contract: ``slots``, ``superstep``, the mesh shape,
    the players' ``MCTSConfig`` shapes, and whether a player carries an
    evaluator are **static** (changing them retraces); every per-request
    knob — ``sims``, ``c_uct``, ``virtual_loss``, ``prior_weight``, each
    an (A-side, B-side) pair — is **traced**, so one pool multiplexes
    arbitrarily many tournament configurations with exactly one compiled
    dispatch (pinned by the compile-count tests in tests/test_multiplex.py
    and tests/test_evaluator.py).  Submitting the players' configured
    values (the default) is bit-identical to the PR 3 static path, and
    ``prior_weight=0`` slots of a guided pool are bit-identical to an
    unguided pool's.

    ``mesh`` (a one-axis device mesh, see ``compat.make_service_mesh``)
    shards the pool: each of the axis's ``n_shard`` devices owns
    ``slots / n_shard`` slots with private queues and ring; ``placement``
    names the host policy routing submissions to shards (core/placement.py)
    and ``rebalance`` enables the once-per-superstep cross-shard ppermute
    of surplus pending games (``multihop`` doubles the ppermute hop
    distance each superstep — 1, 2, 4, ... — so a ``fill_first`` backlog
    drains in O(log shards) supersteps; ``multihop=False`` keeps the PR 3
    one-hop ring).  Capacities passed to :meth:`reset` are *per shard*.

    ``pipeline_depth`` sets how many supersteps the
    :class:`~repro.core.streaming.DispatchPipeline` keeps in flight when
    draining: ``1`` is the synchronous flush -> dispatch -> poll loop
    (bit-identical to the pre-streaming dispatcher, pinned in
    tests/test_pipeline.py); ``K > 1`` overlaps host flush/poll/placement
    with device supersteps.  The depth never changes the compiled
    program — only when the host reads it.
    """

    def __init__(self, engine: GoEngine, player_a: MCTS, player_b: MCTS,
                 slots: int, max_moves: Optional[int] = None,
                 superstep: int = 4, mesh=None,
                 mesh_axis: Optional[str] = None,
                 placement: str = "round_robin", rebalance: bool = True,
                 multihop: bool = True, pipeline_depth: int = 1):
        if mesh is not None:
            axes = tuple(mesh.axis_names)
            if len(axes) != 1:
                raise ValueError(
                    f"service mesh must have exactly one axis, got {axes}; "
                    "build one with repro.compat.make_service_mesh")
            axis = mesh_axis or axes[0]
            if axis not in axes:
                raise ValueError(f"mesh_axis {axis!r} not in {axes}")
            n_shard = mesh.shape[axis]
        else:
            axis, n_shard = None, 1
        if slots < 2 * n_shard or slots % (2 * n_shard):
            raise ValueError(
                f"slots must be an even multiple of the {n_shard} shard(s) "
                f"(each shard needs an even count >= 2), got {slots}")
        if superstep < 1:
            raise ValueError(f"superstep must be >= 1, got {superstep}")
        if pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {pipeline_depth}")
        self.engine = engine
        self.player_a = player_a
        self.player_b = player_b
        self.slots = slots
        self.max_moves = max_moves or engine.max_moves
        self.superstep = superstep
        self.mesh = mesh
        self.placement = placement
        self.rebalance = rebalance
        self.multihop = multihop
        self.pipeline_depth = int(pipeline_depth)
        self.n_shard = n_shard
        self._axis = axis
        self._shard_slots = slots // n_shard
        # rebalance hop schedule: [1] (PR 3 ring) or doubling 1, 2, 4, ...
        if n_shard > 1 and rebalance:
            if multihop:
                self._hops, h = [], 1
                while h < n_shard:
                    self._hops.append(h)
                    h *= 2
            else:
                self._hops = [1]
        else:
            self._hops = []
        PlacementPolicy(placement, n_shard)      # validate the policy name
        # unified-scheduler hook (core/scheduler.py): maps a request's
        # (komi, class) to the bool[n_shard] shard mask its bucket may
        # occupy; None = every shard (the historical behaviour)
        self._shard_filter = None
        self._chunk = slots               # flush granularity
        self._init_state = engine.init_state()
        self._dispatch = jax.jit(self._dispatch_impl,
                                 donate_argnums=(0,), static_argnums=(2,))
        self._push_games = jax.jit(self._push_games_impl)
        self._push_serve = jax.jit(self._push_serve_impl)
        if mesh is not None:
            self._dispatch_mesh = jax.jit(self._dispatch_mesh_impl,
                                          donate_argnums=(0,),
                                          static_argnums=(2,))
            self._push_games_mesh = jax.jit(functools.partial(
                self._push_mesh_impl, which="games"))
            self._push_serve_mesh = jax.jit(functools.partial(
                self._push_mesh_impl, which="serve"))
        self._epoch = -1
        self.reset()

    # ------------------------------------------------------------- lifecycle

    def reset(self, seed: int = 0, slot_keys: Optional[np.ndarray] = None,
              colour_cap: Optional[int] = None,
              game_capacity: Optional[int] = None,
              serve_capacity: Optional[int] = None,
              ring_capacity: Optional[int] = None) -> None:
        """Re-initialise the pool, queues, ring, and host bookkeeping.

        ``slot_keys`` seeds the per-slot dummy RNG chains (default: drawn
        from ``default_rng(seed)``, the PR 1 host-queue discipline — the
        same generator then feeds keyless submissions, preserving the
        host path's exact key stream).  Capacities are rounded up to
        powers of two so repeat runs reuse the compiled dispatch; under a
        mesh every capacity (and the colour cap) applies *per shard*, and
        shard ``s`` takes the ``s``-th contiguous block of slot keys.
        """
        S = self.slots
        self._rng = np.random.default_rng(seed)
        if slot_keys is None:
            slot_keys = np.stack([
                self._rng.integers(0, 2 ** 32, size=(2,), dtype=np.uint32)
                for _ in range(S)])
        slot_keys = np.asarray(slot_keys, np.uint32)
        if slot_keys.shape != (S, 2):
            raise ValueError(f"slot_keys must be [{S}, 2], "
                             f"got {slot_keys.shape}")
        self.game_capacity = _pow2(max(2, game_capacity or 4 * S))
        self.serve_capacity = _pow2(max(2, serve_capacity or 4 * S))
        self.ring_capacity = _pow2(
            ring_capacity
            or (self.game_capacity + self.serve_capacity + S))
        # the rebalance writes into queue rows the host never fills, so a
        # rebalancing pool doubles the device-side game queue and reserves
        # the first game_capacity rows' worth of space for host pushes
        self._game_qcap = (2 * self.game_capacity
                           if self.n_shard > 1 and self.rebalance
                           else self.game_capacity)
        cap = 2 ** 30 if colour_cap is None else int(colour_cap)

        Sps = self._shard_slots
        pools = [self._fresh_pool(slot_keys[s * Sps:(s + 1) * Sps], cap)
                 for s in range(self.n_shard)]
        if self.mesh is None:
            self._pool = pools[0]
        else:
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *pools)
            self._pool = jax.device_put(
                stacked, NamedSharding(self.mesh, PartitionSpec(self._axis)))

        self._pending_games: List[_Pending] = []
        self._pending_serve: List[_Pending] = []
        self._next_ticket = 0
        self._ring_read = np.zeros(self.n_shard, np.int64)
        self._placement = PlacementPolicy(self.placement, self.n_shard)
        self._assigned = {}           # ticket -> (request class, shard)
        self._submitted = {LANE_ARENA: 0, LANE_SERVE: 0, LANE_TOURNAMENT: 0}
        self._completed = dict(self._submitted)
        self._shed = dict(self._submitted)
        self.host_syncs = 0           # host<->device round-trips (flush+poll)
        self.host_blocked_s = 0.0     # time spent waiting on the device
        self.read_rows_s = 0.0        # time in poll's ring-row reads
        self.last_drain_stats = {}    # DispatchPipeline.stats() of last drain
        self._epoch += 1              # invalidates outstanding RingViews

    def _fresh_pool(self, slot_keys: np.ndarray, colour_cap: int) -> PoolState:
        """One shard's empty PoolState (the whole pool when unsharded)."""
        S = self._shard_slots
        A = self.engine.num_actions
        bc = lambda n: (lambda x: jnp.broadcast_to(x, (n,) + jnp.shape(x)))
        # dummy slots still search every step; give them the players'
        # configured knobs so their (discarded) results stay finite
        cfg_cu, cfg_vl, cfg_pw = self._default_params()
        slots = _Slots(
            states=jax.tree.map(bc(S), self._init_state),
            keys=jnp.asarray(slot_keys),
            ticket=jnp.full((S,), -1, jnp.int32),
            lane=jnp.full((S,), -1, jnp.int32),
            moves=jnp.zeros((S,), jnp.int32),
            sims=jnp.zeros((S, 2), jnp.int32),
            c_uct=jnp.broadcast_to(jnp.asarray(cfg_cu, jnp.float32), (S, 2)),
            vl=jnp.broadcast_to(jnp.asarray(cfg_vl, jnp.float32), (S, 2)),
            pw=jnp.broadcast_to(jnp.asarray(cfg_pw, jnp.float32), (S, 2)),
            komi=jnp.full((S,), self.engine.komi, jnp.float32),
            a_black=jnp.arange(S) < S // 2,
        )

        def queue(n):
            return _Queue(
                states=jax.tree.map(bc(n), self._init_state),
                keys=jnp.zeros((n, 2), jnp.uint32),
                lane=jnp.zeros((n,), jnp.int32),
                sims=jnp.zeros((n, 2), jnp.int32),
                c_uct=jnp.zeros((n, 2), jnp.float32),
                vl=jnp.zeros((n, 2), jnp.float32),
                pw=jnp.zeros((n, 2), jnp.float32),
                komi=jnp.full((n,), self.engine.komi, jnp.float32),
                colour=jnp.full((n,), -1, jnp.int32),
                ticket=jnp.full((n,), -1, jnp.int32),
                size=jnp.int32(0),
                head=jnp.int32(0),
            )

        R = self.ring_capacity
        ring = _Ring(
            ticket=jnp.full((R,), -1, jnp.int32),
            lane=jnp.zeros((R,), jnp.int32),
            action=jnp.zeros((R,), jnp.int32),
            winner=jnp.zeros((R,), jnp.float32),
            moves=jnp.zeros((R,), jnp.int32),
            nodes=jnp.zeros((R,), jnp.int32),
            a_black=jnp.zeros((R,), jnp.bool_),
            visits=jnp.zeros((R, A), jnp.float32),
            step=jnp.zeros((R,), jnp.int32),
            count=jnp.int32(0),
        )
        return PoolState(
            slots=slots, games=queue(self._game_qcap),
            serve=queue(self.serve_capacity), ring=ring,
            colour_count=jnp.zeros((2,), jnp.int32),
            colour_cap=jnp.int32(colour_cap), parity=jnp.int32(0),
            occ_sum=jnp.int32(0), occ_steps=jnp.int32(0),
            eval_sum=jnp.int32(0), hop_idx=jnp.int32(0))

    # ------------------------------------------------------------ submission

    def _allowed_shards(self, komi: float, cls: int):
        """Shard-subset mask for one submission (``None`` = every shard).

        The :class:`~repro.core.scheduler.BucketScheduler` installs
        ``_shard_filter`` to enforce per-bucket partitions with headroom
        borrowing; unset, placement sees all shards — bit-identical to
        the pre-bucket service.
        """
        if self._shard_filter is None:
            return None
        return self._shard_filter(komi, cls)

    def _draw_key(self, key) -> np.ndarray:
        if key is None:
            return self._rng.integers(0, 2 ** 32, size=(2,), dtype=np.uint32)
        return np.asarray(key, np.uint32).reshape(2)

    def _default_params(self):
        """The players' static (c_uct, vl, pw) pairs — per-request defaults.

        The prior-blend default is a player's configured ``prior_weight``
        only when that player carries an evaluator; an unguided player
        defaults to 0 so its slots count (and score) as unguided however
        the config field is set.
        """
        return ((self.player_a.cfg.c_uct, self.player_b.cfg.c_uct),
                (self.player_a.cfg.virtual_loss,
                 self.player_b.cfg.virtual_loss),
                (self.player_a.cfg.prior_weight
                 if self.player_a.evaluator is not None else 0.0,
                 self.player_b.cfg.prior_weight
                 if self.player_b.evaluator is not None else 0.0))

    @staticmethod
    def _pair(value, default, cast):
        """Normalise a per-request knob to an (A-side, B-side) pair."""
        if value is None:
            return (cast(default[0]), cast(default[1]))
        if np.ndim(value) == 0:
            return (cast(value), cast(value))
        a, b = value
        return (cast(a), cast(b))

    def submit_game(self, key=None, lane: int = LANE_ARENA, sims=0,
                    c_uct=None, virtual_loss=None,
                    prior_weight=None, a_black=None, komi=None) -> int:
        """Queue one full self-play game (A vs B); returns its ticket.

        Colour is assigned at admission by the slot-pool cell, capped to
        the +-1 balance by ``colour_cap`` — exactly the PR 1 host queue.
        ``a_black`` overrides that free assignment with a **forced**
        colour (colour-targeted admission): ``True`` admits the game
        only into a cell where player A owns Black, ``False`` only into
        a White cell, and ``None`` keeps the free cell-assigned colour.
        Admission stays strictly FIFO — a forced game whose colour has
        no matching empty cell this step blocks the game queue until the
        parity flips (at most one dispatch step) — and forced colours
        still count against ``colour_cap``, so a submitter forcing more
        games of one colour than the cap allows deadlocks its own queue
        (the league's per-pairing ledger keeps demands inside the cap).

        ``sims`` / ``c_uct`` / ``virtual_loss`` / ``prior_weight``
        configure this game's two searches and are **traced** through
        the dispatch (no recompile across values — the tournament-
        multiplexing contract).  Each accepts a scalar (both sides) or
        an ``(a_side, b_side)`` pair; ``None`` (and ``sims <= 0``) means
        the players' configured values, which is bit-identical to the
        pre-traced path.  ``prior_weight`` is the evaluation-lane blend:
        it only affects sides whose player has an evaluator, and ``0``
        makes that side's search bit-identical to the unguided program.
        ``komi`` overrides the engine's static komi for this game's
        scoring (playout outcomes and the reported winner) — traced, so
        mixed-komi games share the one compiled dispatch.
        """
        if lane not in GAME_LANES:
            raise ValueError(f"game lane must be one of {GAME_LANES}")
        colour = -1 if a_black is None else int(bool(a_black))
        return self._submit(self._pending_games, self._init_state,
                            key, lane, sims, c_uct, virtual_loss,
                            prior_weight, colour=colour, komi=komi)

    def submit_serve(self, state: GoState, key=None, sims=0,
                     c_uct=None, virtual_loss=None, prior_weight=None,
                     deadline: Optional[float] = None, komi=None) -> int:
        """Queue one external best-move query for ``state``; returns its
        ticket.  The single search always runs under player A with the
        request key, so the result is a pure function of
        ``(state, key, sims, c_uct, virtual_loss, prior_weight, komi)``
        — placement- and batch-mate-independent.  ``c_uct`` /
        ``virtual_loss`` / ``prior_weight`` / ``komi`` are traced
        per-query knobs defaulting to player A's config (komi: the
        engine's).

        ``deadline`` (absolute ``time.monotonic`` seconds, ``None`` = no
        SLO) is host-only metadata consumed by :meth:`shed_expired`: a
        query whose deadline passes while it is still host-buffered is
        shed instead of flushed.  It never reaches the device, so it can
        never retrace the dispatch.
        """
        return self._submit(self._pending_serve, state, key,
                            LANE_SERVE, sims, c_uct, virtual_loss,
                            prior_weight, deadline=deadline, komi=komi)

    def _submit(self, pending: List[_Pending], state: GoState, key,
                lane: int, sims, c_uct, virtual_loss, prior_weight=None,
                deadline: Optional[float] = None, colour: int = -1,
                komi=None) -> int:
        cls = CLS_SERVE if lane == LANE_SERVE else CLS_GAME
        cap = (self.serve_capacity if cls == CLS_SERVE
               else self.game_capacity)
        cfg_cu, cfg_vl, cfg_pw = self._default_params()
        sims = self._pair(sims, (0, 0), int)
        cu = self._pair(c_uct, cfg_cu, float)
        vl = self._pair(virtual_loss, cfg_vl, float)
        pw = self._pair(prior_weight, cfg_pw, float)
        km = float(self.engine.komi if komi is None else komi)
        shard = self._placement.choose(cls, cap,
                                       config_key=(sims, cu, vl, pw),
                                       allowed=self._allowed_shards(km, cls))
        if shard is None:
            raise RuntimeError(
                f"{LANE_NAMES[lane]} queue full ({cap} in flight per "
                "shard); poll() results or reset() with a larger capacity")
        ticket = self._next_ticket
        self._next_ticket += 1
        pending.append(_Pending(state=state, key=self._draw_key(key),
                                lane=lane, sims=sims, c_uct=cu, vl=vl,
                                pw=pw, komi=km, ticket=ticket, shard=shard,
                                deadline=deadline, colour=colour))
        self._assigned[ticket] = (cls, shard)
        self._submitted[lane] += 1
        return ticket

    def flush(self) -> None:
        """Push host-buffered submissions into the device queues."""
        with TraceAnnotation("dispatcher.flush"):
            pushed = False
            for pending, push, mpush in (
                    (self._pending_games, self._push_games,
                     getattr(self, "_push_games_mesh", None)),
                    (self._pending_serve, self._push_serve,
                     getattr(self, "_push_serve_mesh", None))):
                while pending:
                    rows = pending[:self._chunk]
                    del pending[:self._chunk]
                    with TraceAnnotation("dispatcher.pack"):
                        req, shards = self._pack(rows)
                    if self.mesh is None:
                        self._pool = push(self._pool, req,
                                          jnp.int32(len(rows)))
                    else:
                        self._pool = mpush(self._pool, req, shards)
                    pushed = True
            if pushed:
                self.host_syncs += 1

    def _pack(self, rows: List[_Pending]):
        pad = self._chunk - len(rows)
        states = [r.state for r in rows] + [self._init_state] * pad
        req = SearchRequest(
            state=jax.tree.map(lambda *xs: jnp.stack(xs), *states),
            key=jnp.asarray(np.stack(
                [r.key for r in rows]
                + [np.zeros(2, np.uint32)] * pad)),
            lane=jnp.asarray([r.lane for r in rows] + [0] * pad, jnp.int32),
            sims=jnp.asarray([r.sims for r in rows] + [(0, 0)] * pad,
                             jnp.int32),
            c_uct=jnp.asarray([r.c_uct for r in rows] + [(0., 0.)] * pad,
                              jnp.float32),
            vl=jnp.asarray([r.vl for r in rows] + [(0., 0.)] * pad,
                           jnp.float32),
            pw=jnp.asarray([r.pw for r in rows] + [(0., 0.)] * pad,
                           jnp.float32),
            komi=jnp.asarray([r.komi for r in rows]
                             + [self.engine.komi] * pad, jnp.float32),
            colour=jnp.asarray([r.colour for r in rows] + [-1] * pad,
                               jnp.int32),
            ticket=jnp.asarray([r.ticket for r in rows] + [-1] * pad,
                               jnp.int32),
        )
        shards = jnp.asarray([r.shard for r in rows] + [-1] * pad,
                             jnp.int32)
        return req, shards

    # ----------------------------------------------------------- device side

    def _push_games_impl(self, pool: PoolState, req: SearchRequest,
                         n: jax.Array) -> PoolState:
        return pool._replace(games=_queue_push(pool.games, req, n))

    def _push_serve_impl(self, pool: PoolState, req: SearchRequest,
                         n: jax.Array) -> PoolState:
        return pool._replace(serve=_queue_push(pool.serve, req, n))

    def _dispatch_impl(self, work: PoolState, ring: _Ring, steps: int):
        """``steps`` supersteps over one shard's pool.

        The jit boundary splits the pool into the donatable *work* half
        (``work.ring is None``) and the result ring: work buffers may be
        reused in place across calls (``donate_argnums=(0,)``), while every
        ring is a fresh output so host-held :class:`RingView` snapshots
        stay valid however many supersteps run after them.
        """
        def one(_, p):
            return self._advance(self._admit(p))

        pool = jax.lax.fori_loop(0, steps, one, work._replace(ring=ring))
        return pool._replace(ring=None), pool.ring

    def _dispatch_mesh_impl(self, work: PoolState, ring: _Ring, steps: int):
        """The sharded dispatch: every device steps its own sub-pool.

        Each shard's PoolState rides the mesh axis (leading axis of every
        leaf); the body peels it off and runs the *same* per-shard program
        as the single-device dispatch, so one shard is bit-identical to
        ``mesh=None``.  The rebalance (the only cross-shard traffic) runs
        once per dispatch call, before the superstep's moves.
        """
        spec = PartitionSpec(self._axis)

        def body(w, r):
            local = jax.tree.map(lambda x: x[0], w._replace(ring=r))
            if self._hops:
                local = self._rebalance_impl(local)
            out_w, out_r = self._dispatch_impl(
                local._replace(ring=None), local.ring, steps)
            out = jax.tree.map(lambda x: x[None],
                               out_w._replace(ring=out_r))
            return out._replace(ring=None), out.ring

        return shard_map(body, mesh=self.mesh, in_specs=(spec, spec),
                         out_specs=(spec, spec), check_vma=False)(work, ring)

    def _push_mesh_impl(self, pool: PoolState, req: SearchRequest,
                        shards: jax.Array, *, which: str) -> PoolState:
        """Broadcast one flush chunk; each shard keeps its own rows.

        The chunk is replicated to every device; a shard stably compacts
        the rows placed on it to the front and appends only those, so
        per-shard FIFO order is submission order (and with one shard the
        result is bit-identical to the unsharded push).
        """
        spec = PartitionSpec(self._axis)

        @jax.named_scope("service_push")
        def body(p, req, shards):
            local = jax.tree.map(lambda x: x[0], p)
            me = lax.axis_index(self._axis)
            mine = (shards == me) & (req.ticket >= 0)
            order = jnp.argsort(jnp.where(mine, 0, 1), stable=True)
            req_s = jax.tree.map(lambda x: x[order], req)
            q = _queue_push(getattr(local, which), req_s,
                            mine.sum().astype(jnp.int32))
            local = local._replace(**{which: q})
            return jax.tree.map(lambda x: x[None], local)

        return shard_map(
            body, mesh=self.mesh,
            in_specs=(spec, PartitionSpec(), PartitionSpec()),
            out_specs=spec, check_vma=False)(pool, req, shards)

    @jax.named_scope("service_rebalance")
    def _rebalance_impl(self, pool: PoolState) -> PoolState:
        """Rebalance surplus pending games along the shard ring.

        Runs inside the shard_map body, once per dispatch call.  The hop
        distance follows the schedule in ``self._hops``: ``[1]`` is the
        PR 3 one-hop ring; with ``multihop`` the distance doubles every
        superstep (1, 2, 4, ...), so a ``fill_first`` backlog reaches
        every shard in O(log shards) supersteps instead of one ring
        position per superstep — the 2015 follow-up's work-distribution
        fix applied to the donation topology.  Each schedule entry is a
        *static* permutation (``ppermute`` needs one), selected by the
        traced ``hop_idx`` cursor via ``lax.switch``.
        """
        hops = self._hops
        if len(hops) == 1:
            out = self._rebalance_hop(pool, hops[0])
        else:
            out = lax.switch(
                pool.hop_idx % len(hops),
                [functools.partial(self._rebalance_hop, hop=h)
                 for h in hops],
                pool)
        return out._replace(hop_idx=pool.hop_idx + 1)

    def _rebalance_hop(self, pool: PoolState, hop: int) -> PoolState:
        """One donation round at a fixed hop distance.

        Shard ``i`` donates up to ``slots/n_shard`` of its most recent
        pending games to shard ``i+hop`` when its backlog exceeds that
        shard's — two scalar ``ppermute``\\ s (backlog + headroom) decide
        the count, one chunk ``ppermute`` moves the requests.  Donations
        are capped by the receiver's rebalance headroom (queue rows
        beyond the host's ``game_capacity`` share), so a host flush can
        never overflow a queue the rebalance topped up.
        """
        n = self.n_shard
        gq = pool.games
        Qg = gq.lane.shape[0]
        K = self._shard_slots
        from_next = [((i + hop) % n, i) for i in range(n)]
        to_next = [(i, (i + hop) % n) for i in range(n)]

        backlog = gq.size - gq.head
        headroom = (Qg - self.game_capacity) - backlog
        nxt_backlog = lax.ppermute(backlog, self._axis, from_next)
        nxt_headroom = lax.ppermute(headroom, self._axis, from_next)
        d = jnp.clip((backlog - nxt_backlog) // 2, 0, K)
        d = jnp.minimum(d, jnp.maximum(nxt_headroom, 0))

        # pop the d most recently queued requests (rows size-d .. size-1)
        idx = (gq.size - d + jnp.arange(K, dtype=jnp.int32)) % Qg
        chunk = SearchRequest(
            state=jax.tree.map(lambda x: x[idx], gq.states),
            key=gq.keys[idx], lane=gq.lane[idx], sims=gq.sims[idx],
            c_uct=gq.c_uct[idx], vl=gq.vl[idx], pw=gq.pw[idx],
            komi=gq.komi[idx], colour=gq.colour[idx],
            ticket=gq.ticket[idx])
        got = jax.tree.map(lambda x: lax.ppermute(x, self._axis, to_next),
                           chunk)
        got_n = lax.ppermute(d, self._axis, to_next)
        games = _queue_push(gq._replace(size=gq.size - d), got, got_n)
        return pool._replace(games=games)

    @jax.named_scope("service_admit")
    def _admit(self, pool: PoolState) -> PoolState:
        """Device-side refill: fill empty slots from the pending queues.

        Bit-for-bit the PR 1 host admission loop: slots are scanned in
        index order; a game's forced colour is its (slot-half, parity)
        cell, capped per colour; serve queries go first, only into cells
        player A searches next step.
        """
        sl, gq, sq = pool.slots, pool.games, pool.serve
        S = sl.ticket.shape[0]
        h = S // 2
        Qg, Qs = gq.lane.shape[0], sq.lane.shape[0]
        empty = sl.ticket < 0
        cellA = (jnp.arange(S) < h) == (pool.parity % 2 == 0)

        # serve lane: FIFO into A-searched cells
        elig_s = empty & cellA
        rank_s = _excl_cumsum(elig_s)
        adm_s = elig_s & (rank_s < (sq.size - sq.head))
        pos_s = (sq.head + rank_s) % Qs

        # game lanes: colour-capped FIFO over the remaining empties,
        # honouring per-request forced colours (colour-targeted
        # admission).  A sequential greedy walks the queue in FIFO
        # order: entry k takes the first remaining eligible cell whose
        # colour matches its demand (a free demand takes any cell), and
        # an unmatchable entry blocks the rest of the queue — strict
        # FIFO, never reordering.  With no forced colours this is the
        # rank mapping (entry k -> the k-th eligible cell) exactly, so
        # free pools admit bit-identically to the pre-colour dispatch.
        empty_g = empty & ~adm_s
        budget = pool.colour_cap - pool.colour_count          # i32[2]
        rank_c = jnp.where(cellA, _excl_cumsum(empty_g & cellA),
                           _excl_cumsum(empty_g & ~cellA))
        elig_g = empty_g & (rank_c < budget[cellA.astype(jnp.int32)])
        backlog_g = gq.size - gq.head

        def admit_one(k, carry):
            taken, assign, blocked = carry
            demand = gq.colour[(gq.head + k) % Qg]
            cand = elig_g & ~taken & ((demand < 0)
                                      | (cellA == (demand > 0)))
            cell = jnp.argmax(cand)
            want = (k < backlog_g) & ~blocked
            take = want & cand.any()
            taken = taken.at[cell].set(taken[cell] | take)
            assign = assign.at[cell].set(jnp.where(take, k, assign[cell]))
            return taken, assign, blocked | (want & ~take)

        _, assign, _ = lax.fori_loop(
            0, S, admit_one,
            (jnp.zeros((S,), jnp.bool_), jnp.full((S,), -1, jnp.int32),
             jnp.bool_(False)))
        adm_g = assign >= 0
        pos_g = (gq.head + jnp.maximum(assign, 0)) % Qg

        def sel(mask, new, old):
            m = mask.reshape((S,) + (1,) * (old.ndim - 1))
            return jnp.where(m, new, old)

        def merge(cur, sbuf, gbuf):
            return sel(adm_s, sbuf[pos_s], sel(adm_g, gbuf[pos_g], cur))

        refilled = adm_s | adm_g
        slots = _Slots(
            states=jax.tree.map(merge, sl.states, sq.states, gq.states),
            keys=merge(sl.keys, sq.keys, gq.keys),
            ticket=merge(sl.ticket, sq.ticket, gq.ticket),
            lane=merge(sl.lane, sq.lane, gq.lane),
            moves=jnp.where(refilled, 0, sl.moves),
            sims=merge(sl.sims, sq.sims, gq.sims),
            c_uct=merge(sl.c_uct, sq.c_uct, gq.c_uct),
            vl=merge(sl.vl, sq.vl, gq.vl),
            pw=merge(sl.pw, sq.pw, gq.pw),
            komi=merge(sl.komi, sq.komi, gq.komi),
            a_black=jnp.where(adm_s, True,
                              jnp.where(adm_g, cellA, sl.a_black)),
        )
        colour_count = pool.colour_count + jnp.stack([
            (adm_g & ~cellA).sum(), (adm_g & cellA).sum()])
        return pool._replace(
            slots=slots,
            games=gq._replace(head=gq.head + adm_g.sum()),
            serve=sq._replace(head=sq.head + adm_s.sum()),
            colour_count=colour_count.astype(jnp.int32))

    def _advance(self, pool: PoolState) -> PoolState:
        """One move in every slot: the parity-balanced half-batch search.

        After the involution gather the head half is always the slots
        player A moves in, so A's search reads the requests' side-0
        (sims, c_uct, vl, pw) columns and B's the side-1 columns — the
        traced per-slot knobs that let one compiled dispatch host mixed
        configs.  A side's ``pw`` column reaches its search only when
        that player carries an evaluator (a static Python check: the
        guided and unguided players compile different scoring programs,
        but within a guided player the blend weight — and so any
        guided/unguided slot mix — is pure data).
        """
        sl = pool.slots
        S = sl.ticket.shape[0]
        h = S // 2
        shift = jnp.where(pool.parity % 2 == 0, 0, h)
        idx = (jnp.arange(S, dtype=jnp.int32) + shift) % S    # involution

        st = jax.tree.map(lambda x: x[idx], sl.states)
        keys_p = sl.keys[idx]
        k3 = jax.vmap(lambda k: jax.random.split(k, 3))(keys_p)
        new_keys, ka, kb = k3[:, 0], k3[:, 1], k3[:, 2]
        sims_p = sl.sims[idx]
        cu_p = sl.c_uct[idx]
        vl_p = sl.vl[idx]
        pw_p = sl.pw[idx]
        km_p = sl.komi[idx]
        is_serve = (sl.lane == LANE_SERVE) & (sl.ticket >= 0)
        # serve contract: the query key drives its (single) search directly
        ka = jnp.where(is_serve[idx][:, None], keys_p, ka)

        a_eval = self.player_a.evaluator is not None
        b_eval = self.player_b.evaluator is not None
        head = jax.tree.map(lambda x: x[:h], st)
        tail = jax.tree.map(lambda x: x[h:], st)
        res_a = self.player_a.search_batch(
            head, ka[:h], sims_p[:h, 0],
            params=SearchParams(cu_p[:h, 0], vl_p[:h, 0],
                                pw_p[:h, 0] if a_eval else None,
                                km_p[:h]))
        res_b = self.player_b.search_batch(
            tail, kb[h:], sims_p[h:, 1],
            params=SearchParams(cu_p[h:, 1], vl_p[h:, 1],
                                pw_p[h:, 1] if b_eval else None,
                                km_p[h:]))
        actions = jnp.concatenate([res_a.action, res_b.action])
        nodes = jnp.concatenate([res_a.tree.size, res_b.tree.size])
        visits = jnp.concatenate([res_a.root_visits, res_b.root_visits])

        new_st = jax.vmap(self.engine.play)(st, actions)

        # un-permute with the same involution gather
        new_st = jax.tree.map(lambda x: x[idx], new_st)
        new_keys = new_keys[idx]
        actions = actions[idx]
        nodes = nodes[idx]
        visits = visits[idx]

        live = sl.ticket >= 0
        moves_new = sl.moves + jnp.where(live, 1, 0)
        game_done = live & ~is_serve & (new_st.done
                                        | (moves_new >= self.max_moves))
        finished = is_serve | game_done
        winner = jax.vmap(self.engine.result)(new_st, sl.komi)

        # eval-batch occupancy: live slots whose *searching* side this
        # step was guided (pw > 0 under a player with an evaluator) —
        # the useful fraction of the superstep's net-forward rows
        live_p = live[idx]
        guided_a = (live_p[:h] & (pw_p[:h, 0] > 0)) if a_eval \
            else jnp.zeros((h,), jnp.bool_)
        guided_b = (live_p[h:] & (pw_p[h:, 1] > 0)) if b_eval \
            else jnp.zeros((h,), jnp.bool_)

        ring = self._append_ring(pool.ring, finished, sl, actions, winner,
                                 moves_new, nodes, visits, pool.occ_steps)
        slots = _Slots(
            states=new_st, keys=new_keys,
            ticket=jnp.where(finished, -1, sl.ticket),
            lane=sl.lane, moves=moves_new, sims=sl.sims,
            c_uct=sl.c_uct, vl=sl.vl, pw=sl.pw, komi=sl.komi,
            a_black=sl.a_black)
        return pool._replace(slots=slots, ring=ring,
                             parity=pool.parity + 1,
                             occ_sum=pool.occ_sum + live.sum(),
                             occ_steps=pool.occ_steps + 1,
                             eval_sum=(pool.eval_sum + guided_a.sum()
                                       + guided_b.sum()))

    @jax.named_scope("service_ring")
    def _append_ring(self, ring: _Ring, finished, sl: _Slots, actions,
                     winner, moves, nodes, visits, step) -> _Ring:
        R = ring.ticket.shape[0]
        off = ring.count + _excl_cumsum(finished)
        widx = jnp.where(finished, off % R, R)                 # R: dropped

        def put(buf, val):
            return buf.at[widx].set(val, mode="drop")

        return ring._replace(
            ticket=put(ring.ticket, sl.ticket),
            lane=put(ring.lane, sl.lane),
            action=put(ring.action, actions),
            winner=put(ring.winner, winner),
            moves=put(ring.moves, moves),
            nodes=put(ring.nodes, nodes),
            a_black=put(ring.a_black, sl.a_black),
            visits=put(ring.visits, visits),
            step=put(ring.step, jnp.full_like(sl.ticket, step)),
            count=ring.count + finished.sum(),
        )

    # --------------------------------------------------------------- polling

    def _get(self, x):
        """Blocking device fetch, accounted in ``host_blocked_s``."""
        t0 = time.perf_counter()
        out = jax.device_get(x)
        self.host_blocked_s += time.perf_counter() - t0
        return out

    @property
    def epoch(self) -> int:
        """reset() generation counter — stamps and invalidates RingViews."""
        return self._epoch

    def dispatch(self, steps: Optional[int] = None) -> None:
        """Run ``steps`` (default ``superstep``) moves without host sync."""
        fn = self._dispatch if self.mesh is None else self._dispatch_mesh
        steps = int(steps or self.superstep)
        with TraceAnnotation("dispatcher.issue", steps=steps):
            work, ring = fn(self._pool._replace(ring=None), self._pool.ring,
                            steps)
        self._pool = work._replace(ring=ring)

    def dispatch_async(self, steps: Optional[int] = None) -> RingView:
        """Issue one superstep and return its completion handle.

        The dispatch itself never blocks (JAX async dispatch); the
        returned :class:`RingView` snapshots the ring as this superstep
        leaves it, so ``poll(view=...)`` later blocks only until *this*
        superstep's computation lands — the double-buffered read side of
        the streaming pipeline.
        """
        steps = int(steps or self.superstep)
        self.dispatch(steps)
        return RingView(ring=self._pool.ring, steps=steps,
                        epoch=self._epoch)

    _RING_FIELDS = ("ticket", "lane", "action", "winner", "moves", "nodes",
                    "a_black", "visits", "step")

    def poll(self, view: Optional[RingView] = None) -> List[SearchResult]:
        """Drain newly finished requests from the result rings.

        Without ``view`` (the synchronous path) transfers scale with
        *new* results, not ring capacity: one sync reads the append
        counter(s), and only when one moved does a second sync gather
        the unread rows of *every* shard in one ``device_get`` (so an
        idle poll costs one scalar round-trip, no ``[R, A]`` visits
        traffic, and ``host_syncs`` stays an honest count of blocking
        transfers).

        With ``view`` (a :meth:`dispatch_async` handle) the unread rows
        come from that superstep's snapshot via a *raw* transfer of the
        ring buffers, sliced host-side: enqueueing a device gather on
        the snapshot would queue behind every in-flight superstep and
        re-serialise the pipeline, whereas the raw fetch waits only for
        the snapshot's own producer.  Shard rings drain in shard order,
        FIFO within each; across polls only the ticket identifies a
        result (see :class:`SearchResult`).
        """
        if view is not None and view.epoch != self._epoch:
            raise RuntimeError(
                "stale RingView: the service was reset() after this "
                "superstep was issued")
        ring = self._pool.ring if view is None else view.ring
        with TraceAnnotation("dispatcher.wait_count"):
            counts = np.atleast_1d(np.asarray(self._get(ring.count)))
        self.host_syncs += 1
        news = {}
        for s in range(self.n_shard):
            new = int(counts[s]) - int(self._ring_read[s])
            if new <= 0:
                continue        # <0: an out-of-order view, already drained
            if new > self.ring_capacity:
                raise RuntimeError(
                    f"result ring overflowed ({new} unread > capacity "
                    f"{self.ring_capacity}); poll() more often or reset() "
                    "with a larger ring_capacity")
            news[s] = new
        if not news:
            return []
        bufs = tuple(getattr(ring, f) for f in self._RING_FIELDS)
        rows = {}
        t0 = time.perf_counter()
        with TraceAnnotation("dispatcher.read_rows",
                             rows=sum(news.values()), shards=len(news)):
            if view is None:
                gathers = {}
                for s in news:
                    sb = bufs if self.mesh is None \
                        else jax.tree.map(lambda buf: buf[s], bufs)
                    idx = jnp.asarray(
                        [i % self.ring_capacity for i in
                         range(int(self._ring_read[s]), int(counts[s]))])
                    gathers[s] = jax.tree.map(lambda buf: buf[idx], sb)
                rows = self._get(gathers)       # one blocking transfer
            else:
                whole = self._get(bufs)         # raw back-buffer read
                for s in news:
                    sb = whole if self.mesh is None \
                        else tuple(b[s] for b in whole)
                    idx = np.asarray(
                        [i % self.ring_capacity for i in
                         range(int(self._ring_read[s]), int(counts[s]))])
                    rows[s] = tuple(np.asarray(b)[idx] for b in sb)
        self.read_rows_s += time.perf_counter() - t0
        self.host_syncs += 1
        out: List[SearchResult] = []
        with TraceAnnotation("dispatcher.unpack"):
            for s in sorted(rows):
                ticket, lane, action, winner, moves, nodes, a_black, \
                    visits, step = rows[s]
                for j in range(news[s]):
                    rec = SearchResult(
                        ticket=int(ticket[j]), lane=int(lane[j]),
                        action=int(action[j]), winner=float(winner[j]),
                        moves=int(moves[j]), tree_nodes=int(nodes[j]),
                        a_is_black=bool(a_black[j]),
                        root_visits=np.array(visits[j]),
                        finished_step=int(step[j]))
                    self._completed[rec.lane] += 1
                    cls, assigned = self._assigned.pop(rec.ticket)
                    self._placement.release(cls, assigned)
                    out.append(rec)
                self._ring_read[s] = counts[s]
        return out

    def peek_landed(self) -> bool:
        """Non-blocking refresh of the placement occupancy estimate.

        When the newest superstep's ring is already materialised, feed
        the per-(class, shard) completed-but-unpolled counts to the
        placement policy as its *landed* estimate — unpolled ring rows
        are classified by looking their tickets up in the host's
        assignment map — so submissions placed between reconciles see
        estimated in-flight occupancy rather than the stale polled
        truth.  Returns whether the estimate was refreshed.

        Estimates depend on device timing, so in streaming workloads
        placement (and therefore game colouring) may vary run to run —
        the synchronous path stays deterministic (see core/placement.py).
        """
        ring = self._pool.ring
        if not ring.count.is_ready():
            return False
        # outputs of one executable materialise together: count ready
        # means the ticket column is (at worst trivially) ready too
        counts = np.atleast_1d(np.asarray(jax.device_get(ring.count)))
        tickets = np.asarray(jax.device_get(ring.ticket))
        if self.mesh is None:
            tickets = tickets[None]
        landed = np.zeros((2, self.n_shard), np.int64)
        R = self.ring_capacity
        for s in range(self.n_shard):
            # clamp to the last R rows: older unread rows are already
            # lost to wrap-around (poll() will raise overflow for them)
            start = max(int(self._ring_read[s]), int(counts[s]) - R)
            for i in range(start, int(counts[s])):
                assigned = self._assigned.get(int(tickets[s, i % R]))
                if assigned is not None:
                    landed[assigned[0], s] += 1
        self._placement.note_landed(landed)
        return True

    def shard_occupancy(self) -> np.ndarray:
        """Mean fraction of occupied slots per shard since reset().

        A diagnostic read (one device transfer, not counted in
        ``host_syncs``): ``occ_sum / (occ_steps * slots_per_shard)`` —
        the benchmark's per-shard utilisation column, and the sharded
        analogue of the paper's core-utilisation regions.
        """
        occ, steps = jax.device_get((self._pool.occ_sum,
                                     self._pool.occ_steps))
        occ = np.atleast_1d(np.asarray(occ)).astype(np.float64)
        steps = np.atleast_1d(np.asarray(steps)).astype(np.float64)
        return occ / np.maximum(steps * self._shard_slots, 1.0)

    def eval_occupancy(self) -> np.ndarray:
        """Mean fraction of slots doing *guided* search per shard.

        The evaluation-lane analogue of :meth:`shard_occupancy`: of all
        slot-steps since reset(), the fraction whose searching side was
        live and eval-guided (``prior_weight > 0`` under a player with
        an evaluator).  Because every slot's search contributes a fixed
        ``lanes``-row stripe to the superstep's net-forward batch, this
        is exactly the useful fraction of eval-batch rows — the
        benchmark's occupancy column (benchmarks/bench_eval.py gates on
        it staying >= 0.5 at the default pool size).
        """
        ev, steps = jax.device_get((self._pool.eval_sum,
                                    self._pool.occ_steps))
        ev = np.atleast_1d(np.asarray(ev)).astype(np.float64)
        steps = np.atleast_1d(np.asarray(steps)).astype(np.float64)
        return ev / np.maximum(steps * self._shard_slots, 1.0)

    def shed_expired(self, now: Optional[float] = None) -> List[int]:
        """Drop expired host-pending serve requests before they flush.

        The load-shedding half of the serving tier's deadline contract:
        a query whose ``deadline`` (set at :meth:`submit_serve`) has
        passed is removed from the host buffer, its placement slot is
        released, and its ticket is returned — it never reaches the
        device, so a shed request costs zero device work.  Requests
        already flushed to the device queues are past the point of no
        return and always complete (the front door records those as
        deadline *misses*, not sheds).  Shed tickets count into the
        accounting identity ``submitted == completed + in_flight +
        shed`` (see :meth:`accounting`); tests/test_server.py pins the
        pool staying consistent across the shed path.
        """
        now = time.monotonic() if now is None else now
        shed: List[int] = []
        keep: List[_Pending] = []
        with TraceAnnotation("scheduler.shed"):
            for p in self._pending_serve:
                if p.deadline is not None and now >= p.deadline:
                    cls, shard = self._assigned.pop(p.ticket)
                    self._placement.release(cls, shard)
                    self._shed[p.lane] += 1
                    shed.append(p.ticket)
                else:
                    keep.append(p)
            self._pending_serve[:] = keep
        return shed

    @property
    def shed_total(self) -> int:
        """Requests explicitly shed (never dispatched) since reset()."""
        return sum(self._shed.values())

    @property
    def outstanding(self) -> int:
        """Submitted (including still-pending) but neither completed
        nor shed."""
        return (sum(self._submitted.values())
                - sum(self._completed.values())
                - sum(self._shed.values()))

    def accounting(self) -> tuple:
        """``(submitted, completed, in_flight)`` request totals.

        ``in_flight`` counts tickets between submission and poll (host
        pending + device queued/active + landed-but-unpolled); shed
        requests (see :meth:`shed_expired`) leave ``in_flight``
        immediately, so the full identity is ``submitted == completed +
        in_flight + shed_total`` — the pipeline asserts it at every
        reconcile (tests/test_pipeline.py and tests/test_server.py pin
        it).
        """
        return (sum(self._submitted.values()),
                sum(self._completed.values()),
                len(self._assigned))

    def drain(self, max_steps: Optional[int] = None) -> List[SearchResult]:
        """Flush, then dispatch+poll until every submission completes.

        Runs through a :class:`~repro.core.streaming.DispatchPipeline`
        at this service's ``pipeline_depth``: depth 1 reproduces the
        lock-step flush -> dispatch -> poll loop exactly; deeper
        pipelines keep that many supersteps in flight and overlap the
        host I/O with device compute.  The pipeline's counters land in
        ``last_drain_stats``.
        """
        from repro.core.streaming import DispatchPipeline
        pipe = DispatchPipeline(self)
        out = pipe.run_until_drained(max_steps)
        self.last_drain_stats = pipe.stats()
        return out
