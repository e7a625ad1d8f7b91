"""The program's own trace instrumentation, on the CPU.

* Host spans (``jax.profiler.TraceAnnotation``) at the layer boundaries:
  ``dispatcher.*`` in ``core/service.py``, ``scheduler.*`` in
  ``core/streaming.py`` (and the shed in ``core/service.py``),
  ``serving.*`` in ``serving/go_service.py``.  A profiled run writes each
  of them by name.
* ``GoService.host_busy_s``: the time spent in ``submit`` and ``poll``
  less the blocking device waits in them.
* Device scopes (``jax.named_scope``) on the search phases change only
  HLO metadata: the dispatch program compiled with them equals the one
  compiled with them switched off, instruction for instruction, once
  metadata is stripped and the instructions are renamed in order.
"""
import re
import time

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


def _dispatch_hlo(engine, fused: bool, scoped: bool, monkeypatch) -> str:
    """Optimized HLO of a fresh 2-slot service's dispatch, with the
    search-phase scopes on or switched off."""
    if not scoped:
        enter = source_info_util.ExtendNameStackContextManager.__enter__

        def skip_ours(self):
            if self.name in SCOPES:
                self.prev = source_info_util._source_info_context.context
                return self.prev.name_stack
            return enter(self)

        monkeypatch.setattr(source_info_util.ExtendNameStackContextManager,
                            "__enter__", skip_ours)
    player = MCTS(engine, CFG, fused=fused)
    svc = SearchService(engine, player, player, slots=2, max_moves=6)
    svc.reset(seed=0, game_capacity=2, ring_capacity=4)
    pool = svc._pool
    text = svc._dispatch.lower(pool._replace(ring=None), pool.ring,
                               svc.superstep).compile().as_text()
    monkeypatch.undo()
    return text


@pytest.mark.parametrize("fused", [False, True], ids=["unfused", "fused"])
def test_scopes_change_only_metadata(engine5, fused, monkeypatch):
    scoped = _dispatch_hlo(engine5, fused, True, monkeypatch)
    plain = _dispatch_hlo(engine5, fused, False, monkeypatch)
    def scopes(text):
        return {c for n in re.findall(r'op_name="([^"]*)"', text)
                for c in n.split("/")} & set(SCOPES)

    assert scopes(scoped) == set(SCOPES)
    assert scopes(plain) == set()
    assert _canonical(scoped) == _canonical(plain)

