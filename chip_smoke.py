"""Bring-up smoke run: the Go search service, once, on the chip.

    python chip_smoke.py                   # one chip, the fuego9 setting
    python chip_smoke.py --chips 4         # sharded pool over four chips vs one
    python chip_smoke.py --cpu-rehearsal   # tiny shapes on the CPU

Everything runs in this one process, through the entry points a user
calls.  With no option the phases are:

* ``kernels``: ``uct_scores`` (Pallas on the chip) against
  ``uct_scores_ref``, plain and blended, to f32 tolerance; ``mcts_backup``
  against ``mcts_backup_ref``, exactly.
* ``service``: ``GoService`` at ``configs/fuego9.py`` (9x9, komi 6, 8
  lanes, 256 sims, 8192 nodes, unfused search) with 16 slots answers 32
  best-move queries on seeded mid-game positions.
* ``http``: ``GoMoveServer`` over that service answers 4
  ``POST /v1/best_move`` requests, which must equal the service's answers.
* ``arena``: a self-play ``Arena`` plays 4 games capped at 40 moves.

``--chips 4`` runs only ``sharded``: the same 32 queries through
``GoService(mesh=make_service_mesh(4), placement="round_robin")`` and through
the same service with ``mesh=None``; answers must match.

Every phase but ``single`` runs twice.  The second run must compile no new
dispatch and answer exactly as the first.  Each phase prints one JSON line: seconds spent
tracing and compiling, wall seconds of each run, the dispatch's compile
count, ``peak_bytes_in_use`` and the compile-cache directory.  The last line
is ``{"ok": true, "device": {...}}``.  Any failed check or exception exits
non-zero without that line, as does a default device that is not a TPU
(``--cpu-rehearsal`` skips only that check).
"""
from __future__ import annotations

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu logs to /tmp else

import argparse
import asyncio
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compat import make_service_mesh  # noqa: E402
from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.configs import fuego9  # noqa: E402
from repro.core.arena import Arena  # noqa: E402
from repro.core.mcts import MCTS  # noqa: E402
from repro.go.board import NO_KO, GoEngine, GoState  # noqa: E402
from repro.kernels.mcts_step.ops import mcts_backup  # noqa: E402
from repro.kernels.mcts_step.ref import UNVISITED, mcts_backup_ref  # noqa: E402
from repro.kernels.uct_select.ops import uct_scores  # noqa: E402
from repro.kernels.uct_select.ref import uct_scores_ref  # noqa: E402
from repro.launch.serve_go import random_position  # noqa: E402
from repro.serving.go_service import GoService  # noqa: E402
from repro.serving.server import GoMoveServer, http_json  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Setting:
    board: int
    komi: float
    lanes: int
    sims: int
    max_nodes: int
    slots: int = 16
    queries: int = 32
    prefix_moves: int = 20
    http_requests: int = 4
    games: int = 4
    max_moves: int = 40
    max_depth: int = 64        # MCTS default; the backup's path length
    score_rows: int = 64       # uct_scores rows in the kernel check


def fuego9_setting() -> Setting:
    cfg = fuego9.config()
    return Setting(board=cfg.board_size, komi=cfg.komi, lanes=cfg.lanes,
                   sims=cfg.sims_per_move, max_nodes=cfg.max_nodes)


REHEARSAL = Setting(board=5, komi=6.0, lanes=2, sims=8, max_nodes=64,
                    slots=4, queries=4, prefix_moves=4, http_requests=2,
                    games=2, max_moves=10, max_depth=8, score_rows=8)


class SmokeFailure(AssertionError):
    pass


def check(ok, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


class CompileMeter:
    """Seconds JAX spends tracing, lowering and compiling (a persistent
    cache hit counts its retrieval), and the cache's hits and misses."""

    DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration",
                 "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self.DURATIONS:
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


class Phase:
    """Times the two runs of one phase and prints its JSON line."""

    def __init__(self, name: str, meter: CompileMeter, cache_dir: str):
        self.name, self.meter, self.cache_dir = name, meter, cache_dir
        self.runs = []

    def run(self, fn):
        before, t0 = self.meter.snapshot(), time.perf_counter()
        out = fn()
        self._record(before, t0)
        return out

    async def run_async(self, fn):
        before, t0 = self.meter.snapshot(), time.perf_counter()
        out = await fn()
        self._record(before, t0)
        return out

    def _record(self, before, t0):
        wall = time.perf_counter() - t0
        after = self.meter.snapshot()
        self.runs.append({"wall_s": wall,
                          "compile_s": after[0] - before[0],
                          "cache_hits": after[1] - before[1],
                          "cache_misses": after[2] - before[2]})

    def report(self, compiles: dict, **extra) -> None:
        first, repeat = self.runs[0], self.runs[-1]
        line = {"phase": self.name,
                "compile_s": first["compile_s"],
                "first_s": first["wall_s"],
                "warm_s": repeat["wall_s"] if len(self.runs) > 1 else None,
                "repeat_compile_s": repeat["compile_s"]
                if len(self.runs) > 1 else None,
                "cache_hits": sum(r["cache_hits"] for r in self.runs),
                "cache_misses": sum(r["cache_misses"] for r in self.runs),
                "compiles": compiles,
                "peak_bytes_in_use": peak_bytes(),
                "cache_dir": self.cache_dir}
        line.update(extra)
        print(json.dumps(line), flush=True)


def peak_bytes():
    """``peak_bytes_in_use`` of every device, where the backend reports it."""
    out = []
    for d in jax.devices():
        stats = d.memory_stats()
        out.append(None if stats is None else stats.get("peak_bytes_in_use"))
    return out


def repeat_phase(phase: Phase, fn, compiles_of):
    """Run ``fn`` twice; the repeat must compile nothing and answer alike."""
    first = phase.run(fn)
    warm = compiles_of()
    second = phase.run(fn)
    again = compiles_of()
    check(again == warm, f"{phase.name}: the repeat compiled again "
                         f"({warm} -> {again})")
    return first, second, warm


# ------------------------------------------------------------------ kernels


def kernel_phase(s: Setting, phase: Phase, on_tpu: bool) -> None:
    rng = np.random.default_rng(0)
    b, a = s.score_rows, s.board * s.board + 1
    visit = rng.integers(0, 50, (b, a)).astype(np.float32)
    stats = (visit, (rng.normal(size=(b, a)) * visit).astype(np.float32),
             rng.integers(0, 3, (b, a)).astype(np.float32),
             rng.dirichlet(np.ones(a), b).astype(np.float32),
             rng.random((b, a)) < 0.8, rng.random((b, a)) < 0.6,
             rng.integers(1, 4000, b).astype(np.float32),
             rng.choice([-1.0, 1.0], b).astype(np.float32))
    stats = tuple(jnp.asarray(x) for x in stats)
    knobs = dict(c_uct=jnp.asarray(rng.uniform(0.5, 1.5, b), jnp.float32),
                 vl_weight=jnp.float32(1.0))
    blend = jnp.asarray(rng.uniform(0, 1, b), jnp.float32)

    g, n, lanes, d = s.slots, s.max_nodes, s.lanes, s.max_depth
    # integer-valued statistics and returns, as in search: the sums are
    # exact in f32 whatever order the scatter adds in
    tree = (jnp.asarray(rng.integers(0, 100, (g, n)).astype(np.float32)),
            jnp.asarray(rng.integers(-100, 100, (g, n)).astype(np.float32)))
    depth = rng.integers(1, d + 1, (g, lanes))
    paths = rng.integers(0, n, (g, lanes, d)).astype(np.int32)
    paths[:, :, 0] = 0
    paths[np.arange(d)[None, None] >= depth[..., None]] = UNVISITED
    backup_in = tree + (jnp.asarray(paths), jnp.asarray(
        rng.integers(-lanes, lanes + 1, (g, lanes)).astype(np.float32)))

    if on_tpu:     # the dispatchers must reach the Pallas kernels
        for fn, args, kw in ((uct_scores, stats, knobs),
                             (uct_scores, stats, dict(knobs, prior_w=blend)),
                             (mcts_backup, backup_in, {})):
            text = fn.lower(*args, **kw).as_text()
            check("tpu_custom_call" in text,
                  f"{fn.__name__} did not lower to a Pallas kernel")

    def once():
        out = {}
        for name, pw in (("plain", None), ("blend", blend)):
            got = np.asarray(uct_scores(*stats, **knobs, prior_w=pw))
            want = np.asarray(uct_scores_ref(*stats, **knobs, prior_w=pw,
                                             use_puct=False))
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                                       err_msg=f"uct_scores {name}")
            out[name] = got
        got = [np.asarray(x) for x in mcts_backup(*backup_in)]
        want = [np.asarray(x) for x in jax.vmap(
            lambda *x: mcts_backup_ref(*x, playouts=1.0))(*backup_in)]
        for gx, wx, name in zip(got, want, ("visit", "value")):
            check(np.array_equal(gx, wx),
                  f"mcts_backup {name} differs from mcts_backup_ref in "
                  f"{int((gx != wx).sum())} entries")
        out["backup"] = got
        return out

    def compiles():
        return {"uct_scores": uct_scores._cache_size(),
                "mcts_backup": mcts_backup._cache_size()}

    first, second, warm = repeat_phase(phase, once, compiles)
    for k in first:
        check(all(np.array_equal(x, y) for x, y in
                  zip(np.atleast_1d(first[k]), np.atleast_1d(second[k]))),
              f"kernels: {k} answered differently twice")
    phase.report(warm, uct_shape=[b, a], backup_shape=[g, n, lanes, d])


# ------------------------------------------------------------------ service


def make_queries(s: Setting, engine: GoEngine, seed: int = 0):
    rng = np.random.default_rng(seed)
    positions = [random_position(engine, rng, s.prefix_moves)
                 for _ in range(s.queries)]
    keys = rng.integers(0, 2 ** 32, (s.queries, 2), dtype=np.uint32)
    return positions, keys


def make_service(s: Setting, mesh=None) -> GoService:
    return GoService(board_size=s.board, komi=s.komi, max_sims=s.sims,
                     lanes=s.lanes, slots=s.slots, max_nodes=s.max_nodes,
                     mesh=mesh, placement="round_robin")


def dispatch_compiles(svc: GoService) -> int:
    pools = svc._buckets.values()
    return sum(p._dispatch._cache_size()
               + (p._dispatch_mesh._cache_size() if p.mesh is not None
                  else 0) for p in pools)


def ask(svc: GoService, positions, keys):
    """Submit every query, then collect: ``[(action, root_visits)]``."""
    tickets = [svc.submit(board, to_play=tp, key=key)
               for (board, tp), key in zip(positions, keys)]
    svc.flush()
    out = []
    for t in tickets:
        res = svc.result(t, timeout_s=900)
        out.append((int(res.action), np.asarray(res.root_visits),
                    int(res.sims_granted)))
    return out


def check_answers(s: Setting, engine: GoEngine, positions, answers,
                  what: str) -> None:
    for i, ((board, tp), (action, visits, sims)) in enumerate(
            zip(positions, answers)):
        state = GoState(board=jnp.asarray(np.asarray(board, np.int8)),
                        to_play=jnp.int8(tp), ko=jnp.int32(NO_KO),
                        pass_count=jnp.int32(0), move_count=jnp.int32(0),
                        done=jnp.bool_(False))
        legal = np.asarray(engine.jit_legal(state))
        check(0 <= action < legal.shape[0] and legal[action],
              f"{what} query {i}: move {action} is illegal")
        check(sims == s.sims, f"{what} query {i}: {sims} sims granted, "
                              f"asked {s.sims}")
        check(float(visits.sum()) == float(sims),
              f"{what} query {i}: root visits sum to {visits.sum()}, "
              f"not the {sims}-sim budget")


def same_answers(a, b) -> bool:
    return len(a) == len(b) and all(
        x[0] == y[0] and np.array_equal(x[1], y[1]) for x, y in zip(a, b))


def service_phase(s, phase, engine, svc, positions, keys, what="service",
                  repeat=True):
    if repeat:
        first, second, warm = repeat_phase(
            phase, lambda: ask(svc, positions, keys),
            lambda: dispatch_compiles(svc))
        check(same_answers(first, second), f"{what}: the same queries with "
                                           f"the same keys answered "
                                           f"differently")
    else:
        first = phase.run(lambda: ask(svc, positions, keys))
        warm = dispatch_compiles(svc)
    check_answers(s, engine, positions, first, what)
    return first, warm


# --------------------------------------------------------------------- http


def http_phase(s, phase, svc, positions, keys, served) -> None:
    bodies = [{"board": np.asarray(board).astype(int).tolist(),
               "to_play": int(tp), "key": [int(k) for k in key]}
              for (board, tp), key in zip(positions[:s.http_requests],
                                          keys[:s.http_requests])]

    async def session():
        server = GoMoveServer(svc)
        port = await server.start()

        async def round_():
            replies = await asyncio.gather(*(
                http_json("127.0.0.1", port, "POST", "/v1/best_move", body,
                          timeout_s=900) for body in bodies))
            for status, reply in replies:
                check(status == 200, f"http: status {status}: {reply}")
            return [(r["action"], np.asarray(r["root_visits"], np.float32))
                    for _, r in replies]

        try:
            outs = []
            for _ in range(2):
                outs.append(await phase.run_async(round_))
                outs.append(dispatch_compiles(svc))
            return outs
        finally:
            await server.stop()

    warm = dispatch_compiles(svc)
    first, c1, second, c2 = asyncio.run(session())
    check(c1 == c2 == warm, f"http: the dispatch compiled again "
                            f"({warm} -> {c1} -> {c2})")
    want = [(a, v) for a, v, _ in served[:s.http_requests]]
    check(same_answers(first, want),
          "http: answers differ from the service's for the same queries")
    check(same_answers(first, second),
          "http: the same requests answered differently")
    phase.report({"dispatch": warm}, requests=len(bodies))


# -------------------------------------------------------------------- arena


def arena_phase(s, phase, engine) -> None:
    cfg = dataclasses.replace(
        fuego9.config(), board_size=s.board, komi=s.komi, lanes=s.lanes,
        sims_per_move=s.sims, max_nodes=s.max_nodes)
    player = MCTS(engine, cfg)
    arena = Arena(engine, player, player, slots=s.games,
                  max_moves=s.max_moves)

    def play():
        return arena.play_games(s.games, seed=0)

    first, second, warm = repeat_phase(
        phase, play, lambda: arena.service._dispatch._cache_size())
    check(first == second, "arena: the same seed played different games")
    for i, g in enumerate(first):
        check(0 < g.moves <= s.max_moves,
              f"arena game {i}: {g.moves} moves, cap {s.max_moves}")
        check(g.winner in (-1.0, 0.0, 1.0), f"arena game {i}: winner "
                                            f"{g.winner}")
    phase.report({"dispatch": warm}, games=len(first),
                 moves=[g.moves for g in first])


# ------------------------------------------------------------------ sharded


def sharded_phase(s, meter, cache_dir, engine, chips: int) -> None:
    devices = jax.devices()
    check(len(devices) >= chips, f"--chips {chips} needs {chips} devices, "
                                 f"found {len(devices)}")
    positions, keys = make_queries(s, engine)
    phase = Phase("single", meter, cache_dir)
    # run once: the one-chip pass costs four chips' time here, and the
    # main path already checks its repeat
    one, warm = service_phase(s, phase, engine, make_service(s), positions,
                              keys, what="single", repeat=False)
    phase.report({"dispatch": warm}, queries=len(positions))
    phase = Phase("sharded", meter, cache_dir)
    sharded = make_service(s, mesh=make_service_mesh(chips))
    many, warm = service_phase(s, phase, engine, sharded, positions, keys,
                               what="sharded")
    check(same_answers(one, many),
          "sharded: answers differ from the one-chip service's")
    mesh_devices = set(sharded.mesh.devices.flat)
    for pool in sharded._buckets.values():
        for leaf in jax.tree.leaves(pool._pool):
            check(set(leaf.sharding.device_set) == mesh_devices,
                  f"sharded: a pool buffer lives on "
                  f"{sorted(d.id for d in leaf.sharding.device_set)}, not "
                  f"on all {chips} devices")
    occupancy = [float(x) for x in sharded.shard_occupancy()]
    check(len(occupancy) == chips and min(occupancy) > 0,
          f"sharded: a shard ran no query (occupancy {occupancy})")
    phase.report({"dispatch": warm}, queries=len(positions),
                 shard_occupancy=occupancy, answers_match=True)


# --------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded service against the "
                         "one-chip service")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny shapes; skip only the platform check")
    args = ap.parse_args(argv)

    cache_dir = use_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke: the default device is {dev.platform!r}, not a "
              "TPU; refusing to run (--cpu-rehearsal runs tiny shapes "
              "there)", file=sys.stderr)
        return 2
    s = REHEARSAL if args.cpu_rehearsal else fuego9_setting()
    meter = CompileMeter()
    engine = GoEngine(s.board, s.komi)
    print(json.dumps({"setting": dataclasses.asdict(s),
                      "device_kind": dev.device_kind,
                      "cache_dir": cache_dir}), flush=True)

    if args.chips > 1:
        sharded_phase(s, meter, cache_dir, engine, args.chips)
    else:
        kernel_phase(s, Phase("kernels", meter, cache_dir),
                     on_tpu=dev.platform == "tpu")
        positions, keys = make_queries(s, engine)
        svc = make_service(s)
        phase = Phase("service", meter, cache_dir)
        served, warm = service_phase(s, phase, engine, svc, positions, keys)
        phase.report({"dispatch": warm}, queries=len(positions))
        http_phase(s, Phase("http", meter, cache_dir), svc, positions, keys,
                   served)
        arena_phase(s, Phase("arena", meter, cache_dir), engine)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
