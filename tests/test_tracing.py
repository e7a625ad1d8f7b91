"""The program's own trace instrumentation, on the CPU.

* Host spans (``jax.profiler.TraceAnnotation``) at the layer boundaries:
  ``dispatcher.*`` in ``core/service.py``, ``scheduler.*`` in
  ``core/streaming.py`` (and the shed in ``core/service.py``),
  ``serving.*`` in ``serving/go_service.py``.  A profiled run writes each
  of them by name.
* ``GoService.host_busy_s``: the time spent in ``submit`` and ``poll``
  less the blocking device waits in them.
* ``SearchService.read_rows_s``: the time in ``poll``'s ring-row reads,
  on polls that read rows only; ``GoService.read_rows_s`` sums it over
  the komi buckets.
* Device scopes (``jax.named_scope``) on the search phases, and on the
  mesh-only rebalance and push, change only HLO metadata: the program
  compiled with them equals the one compiled with them switched off,
  instruction for instruction, once metadata is stripped and the
  instructions are renamed in order.  The mesh programs are compiled in
  a subprocess with four CPU devices.
"""
import contextlib
import json
import os
import re
import subprocess
import sys
import time
import types

import jax
import numpy as np
import pytest
from jax._src import source_info_util

from repro.config import MCTSConfig
from repro.core.mcts import MCTS
from repro.core.service import LANE_ARENA, SearchService
from repro.serving.go_service import GoService

SCOPES = ("mcts_select", "mcts_expand", "mcts_playout", "mcts_backup",
          "go_flood_fill", "go_group_info", "service_admit", "service_ring")
MESH_SCOPES = ("service_rebalance", "service_push")
DISPATCHER = ("dispatcher.flush", "dispatcher.pack", "dispatcher.issue",
              "dispatcher.wait_count", "dispatcher.read_rows",
              "dispatcher.unpack")
CFG = MCTSConfig(board_size=5, lanes=2, sims_per_move=4, max_nodes=32)
# reading an event's stats warns once per call about the binding's type
pytestmark = pytest.mark.filterwarnings(
    "ignore:builtin type event_stats:DeprecationWarning")


def _host_spans(tmp_path, run) -> dict:
    """``{name: [stats of each event]}`` of the host events that ``run``
    wrote while the profiler traced it."""
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        run()
    out = {}
    for f in tmp_path.rglob("*.xplane.pb"):
        for plane in ProfileData.from_file(str(f)).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(dict(e.stats))
    return out


@pytest.fixture(scope="module")
def service(engine5):
    player = MCTS(engine5, CFG)
    return SearchService(engine5, player, player, slots=2, max_moves=6)


@pytest.fixture(scope="module")
def go_service():
    return GoService(board_size=5, komi=0.5, max_sims=4, lanes=2, slots=2,
                     max_nodes=32, seed=0)


def test_drain_writes_dispatcher_and_scheduler_spans(service, tmp_path):
    def run():
        service.reset(seed=1, game_capacity=4, ring_capacity=8)
        for _ in range(3):
            service.submit_game(lane=LANE_ARENA)
        assert len(service.drain()) == 3

    run()                                    # compile outside the trace
    spans = _host_spans(tmp_path, run)
    for name in DISPATCHER + ("scheduler.pump", "scheduler.reconcile"):
        assert name in spans, name
    # a dispatch span carries its step count, a row read its row count
    assert {s.get("steps") for s in spans["dispatcher.issue"]} \
        == {service.superstep}
    assert sum(s["rows"] for s in spans["dispatcher.read_rows"]) == 3
    assert {s["shards"] for s in spans["dispatcher.read_rows"]} == {1}


def test_go_service_writes_serving_spans(go_service, tmp_path):
    board = np.zeros(25, np.int8)

    def run():
        t = go_service.submit(board, key=np.asarray([0, 7], np.uint32))
        while go_service.result(t, wait=False) is None:
            go_service.poll()

    run()
    first = go_service._next_ticket
    spans = _host_spans(tmp_path, run)
    for name in ("serving.submit", "serving.poll", "scheduler.shed",
                 "scheduler.pump", "scheduler.reconcile") + DISPATCHER:
        assert name in spans, name
    assert [s["ticket"] for s in spans["serving.submit"]] == [first]


def test_host_busy_s_excludes_blocked_time(go_service, monkeypatch):
    """Every blocking read is made 20 ms slower: ``host_blocked_s`` takes
    all of it, ``host_busy_s`` none, and the two add up to the time spent
    inside ``submit`` and ``poll``."""
    wait = 0.02
    orig = SearchService._get

    def slow_get(self, x):
        t0 = time.perf_counter()
        time.sleep(wait)
        self.host_blocked_s += time.perf_counter() - t0
        return orig(self, x)

    monkeypatch.setattr(SearchService, "_get", slow_get)
    board = np.zeros(25, np.int8)
    go_service.result(go_service.submit(board))           # warm
    busy0, blocked0 = go_service.host_busy_s, go_service.host_blocked_s
    inside = 0.0
    t0 = time.perf_counter()
    ticket = go_service.submit(board, key=np.asarray([0, 3], np.uint32))
    inside += time.perf_counter() - t0
    polls = 0
    while go_service.result(ticket, wait=False) is None:
        t0 = time.perf_counter()
        go_service.poll()
        inside += time.perf_counter() - t0
        polls += 1
    busy = go_service.host_busy_s - busy0
    blocked = go_service.host_blocked_s - blocked0
    assert polls >= 1 and blocked >= wait * polls        # >= 1 read a poll
    assert 0.0 < busy < inside - blocked + 1e-3
    assert busy + blocked == pytest.approx(inside, abs=0.02)


def test_read_rows_s_counts_polls_that_read_rows(service, engine5):
    service.reset(seed=2, ring_capacity=8)
    assert service.read_rows_s == 0.0
    service.dispatch()
    assert service.poll() == [] and service.read_rows_s == 0.0
    service.submit_serve(engine5.init_state(),
                         key=np.asarray([0, 5], np.uint32))
    service.flush()
    service.dispatch()
    assert len(service.poll()) == 1
    read = service.read_rows_s
    assert read > 0.0
    service.dispatch()
    assert service.poll() == [] and service.read_rows_s == read


def test_go_service_read_rows_s_sums_buckets(go_service, monkeypatch):
    go_service.result(go_service.submit(np.zeros(25, np.int8)))
    (inner,) = go_service._buckets.values()
    assert go_service.read_rows_s == inner.read_rows_s > 0.0
    # per-komi pools (``unified=False``) are summed like host_blocked_s
    monkeypatch.setitem(go_service._buckets, 6.5,
                        types.SimpleNamespace(read_rows_s=0.25))
    assert go_service.read_rows_s == inner.read_rows_s + 0.25


@contextlib.contextmanager
def _scopes_off():
    """Trace with the program's own scopes switched off: entering one of
    them leaves the name stack as it was."""
    cls = source_info_util.ExtendNameStackContextManager
    enter = cls.__enter__

    def skip_ours(self):
        if self.name in SCOPES + MESH_SCOPES:
            self.prev = source_info_util._source_info_context.context
            return self.prev.name_stack
        return enter(self)

    cls.__enter__ = skip_ours
    try:
        yield
    finally:
        cls.__enter__ = enter


def _canonical(text: str) -> str:
    """HLO text without ``metadata={...}``, without the source-location
    tables (file, function and frame names) between the module's header
    and its first computation, and with every instruction and computation
    renamed ``%v<n>`` in order of first use: the names carry a counter
    that the scopes' longer op names can shift."""
    head, *rest = text.splitlines()
    while rest and not rest[0].startswith(("%", "ENTRY")):
        rest.pop(0)
    body = re.sub(r",? metadata=\{[^}]*\}", "", "\n".join(rest))
    names: dict = {}
    body = re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  body)
    return head + "\n" + body


def _scopes(text: str) -> set:
    """The program's scopes named in an HLO text's op metadata."""
    return {c for n in re.findall(r'op_name="([^"]*)"', text)
            for c in n.split("/")} & set(SCOPES + MESH_SCOPES)


def _dispatch_hlo(engine, fused: bool, scoped: bool) -> str:
    """Optimized HLO of a fresh 2-slot service's dispatch, with the
    search-phase scopes on or switched off."""
    with contextlib.nullcontext() if scoped else _scopes_off():
        player = MCTS(engine, CFG, fused=fused)
        svc = SearchService(engine, player, player, slots=2, max_moves=6)
        svc.reset(seed=0, game_capacity=2, ring_capacity=4)
        pool = svc._pool
        return svc._dispatch.lower(pool._replace(ring=None), pool.ring,
                                   svc.superstep).compile().as_text()


def mesh_programs_hlo(scoped: bool) -> dict:
    """Optimized HLO of a fresh 4-shard service's mesh dispatch and mesh
    push, with the program's scopes on or switched off.  Needs four
    devices."""
    from repro.compat import make_service_mesh
    from repro.go import GoEngine
    with contextlib.nullcontext() if scoped else _scopes_off():
        engine = GoEngine(5, komi=0.5)
        player = MCTS(engine, CFG)
        svc = SearchService(engine, player, player, slots=8, max_moves=6,
                            mesh=make_service_mesh(4))
        svc.reset(seed=0, game_capacity=2, ring_capacity=4)
        pool = svc._pool
        svc.submit_serve(engine.init_state(), key=np.asarray([0, 1]))
        req, shards = svc._pack(svc._pending_serve)
        return {
            "dispatch": svc._dispatch_mesh.lower(
                pool._replace(ring=None), pool.ring,
                svc.superstep).compile().as_text(),
            "push": svc._push_serve_mesh.lower(
                pool, req, shards).compile().as_text()}


def _mesh_hlo_in_subprocess() -> dict:
    """``{scoped: mesh_programs_hlo(scoped)}`` from a subprocess that has
    four CPU devices, compared there: each program's scopes and whether
    its canonical HLO equals the other's."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(here), "src"), here,
         env.get("PYTHONPATH", "")])
    code = """if True:
        import json
        import test_tracing as t
        on, off = t.mesh_programs_hlo(True), t.mesh_programs_hlo(False)
        print(json.dumps({k: {"scoped": sorted(t._scopes(on[k])),
                              "plain": sorted(t._scopes(off[k])),
                              "equal": t._canonical(on[k])
                              == t._canonical(off[k])} for k in on}))
    """
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=here,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("program", ["unfused", "fused", "mesh"])
def test_scopes_change_only_metadata(engine5, program):
    if program == "mesh":
        got = _mesh_hlo_in_subprocess()
        assert set(got["dispatch"]["scoped"]) == set(SCOPES) | {
            "service_rebalance"}
        assert got["push"]["scoped"] == ["service_push"]
        for name, prog in got.items():
            assert prog["plain"] == [], name
            assert prog["equal"], name
        return
    scoped = _dispatch_hlo(engine5, program == "fused", True)
    plain = _dispatch_hlo(engine5, program == "fused", False)
    assert _scopes(scoped) == set(SCOPES)
    assert _scopes(plain) == set()
    assert _canonical(scoped) == _canonical(plain)

