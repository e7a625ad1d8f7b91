"""Per-phase self time and program-span gap labels (``trace/phases.py``),
on synthetic events and on traces recorded on one TPU v5 lite chip."""
from pathlib import Path

import pytest

from benchmarks.chip.trace import reduce as base
from benchmarks.chip.trace.phases import (SCOPES, label, reduce,
                                          reduce_files, scopes_of,
                                          self_times)
from benchmarks.chip.trace.reduce import DeviceEvents, Event
from benchmarks.chip.trace.xspace import op_paths

DATA = Path(__file__).parent / "data"
UCT = DATA / "uct_scores_v5e.xplane.pb"
SCOPED = DATA / "scoped_dispatch_v5e.xplane.pb.gz"
MS = 1e6


def test_self_time_subtracts_direct_children():
    # a loop [0, 10] holds a body op [1, 4] holding [2, 3], then [5, 9];
    # a later op [12, 13] stands alone
    ops = [Event("loop", 0, 10 * MS), Event("body", 1 * MS, 3 * MS),
           Event("inner", 2 * MS, 1 * MS), Event("b2", 5 * MS, 4 * MS),
           Event("alone", 12 * MS, 1 * MS)]
    own = self_times(ops)
    assert own == pytest.approx([3 * MS, 2 * MS, 1 * MS, 4 * MS, 1 * MS])
    assert sum(own) == pytest.approx(11 * MS)      # the union of the ops


def test_self_time_with_a_child_at_its_parent_start():
    ops = [Event("child", 0, 2 * MS), Event("parent", 0, 5 * MS)]
    assert self_times(ops) == pytest.approx([2 * MS, 3 * MS])


def test_scope_matching():
    path = ("jit(_dispatch_impl)/while/body/closed_call/vmap()/while/body/"
            "mcts_playout/vmap(vmap())/while/body/go_group_info/"
            "go_flood_fill/gather:")
    assert scopes_of(path) == {"mcts_playout", "go_group_info",
                               "go_flood_fill"}
    assert scopes_of("jit(f)/vmap(mcts_playout)/add:") == {"mcts_playout"}
    assert scopes_of("jit(f)/vmap(vmap(mcts_backup))/scatter:") \
        == {"mcts_backup"}
    # a jitted function of the same name is not a scope; nor a prefix
    assert scopes_of("jit(f)/jit(mcts_select)/pallas_call:") == set()
    assert scopes_of("jit(f)/mcts_selected/add:") == set()
    assert scopes_of("") == set()


def test_synthetic_phases_and_gap_labels():
    ops = [Event("%while.1 = s32[] while()", 0, 10 * MS),
           Event("%fusion.2 = s32[81] fusion()", 1 * MS, 6 * MS),
           Event("%fusion.3 = f32[8] fusion()", 7 * MS, 2 * MS),
           Event("%scatter.4 = f32[8] scatter()", 12 * MS, 1 * MS)]
    paths = {"%while.1 = s32[] while()": "jit(d)/mcts_playout/while:",
             "%fusion.2 = s32[81] fusion()":
                 "jit(d)/mcts_playout/vmap(vmap())/go_flood_fill/gather:",
             "%fusion.3 = f32[8] fusion()": "jit(d)/vmap(mcts_select)/add:",
             "%scatter.4 = f32[8] scatter()": "jit(d)/tree/scatter:"}
    dev = {"/device:TPU:0": DeviceEvents([], ops)}
    spans = [Event("service.poll", 9 * MS, 4 * MS),
             Event("serving.poll", 9.5 * MS, 3 * MS),
             Event("dispatcher.wait_count", 10 * MS, 1 * MS),
             Event("dispatcher.read_rows", 11 * MS, 1.2 * MS)]
    out = reduce(dev, spans, {"/device:TPU:0": paths}, window_s=0.02)
    assert out["busy_s"] == pytest.approx(0.011)
    assert out["self_s"] == pytest.approx(out["busy_s"])
    s = out["scopes"]
    assert set(s) == set(SCOPES)
    assert s["mcts_playout"] == pytest.approx(0.008)   # 2 ms loop + 6 ms
    assert s["go_flood_fill"] == pytest.approx(0.006)
    assert s["mcts_select"] == pytest.approx(0.002)
    assert s["mcts_backup"] == 0.0
    assert out["scoped_share"] == pytest.approx(0.010 / 0.011)
    # the gap [10, 12] ms: the wait covers half, the read the other half;
    # both serving.poll and service.poll cover it whole, the inner wins
    assert out["breakdown"]["idle_gaps"] == [
        ["serving.poll", pytest.approx(0.002)]]
    # every key of the plain reduction keeps its value
    plain = base.reduce(dev, spans[:1], window_s=0.02)
    for k, v in plain.items():
        if k != "breakdown":
            assert out[k] == v, k
    assert out["breakdown"]["device_ops"] == plain["breakdown"]["device_ops"]


def test_innermost_label_and_benchmark_spans_alone():
    gap = (10 * MS, 11 * MS)
    outer, inner = Event("service.poll", 0, 20 * MS), \
        Event("dispatcher.wait_count", 9 * MS, 3 * MS)
    assert label(gap, [outer, inner]) == "dispatcher.wait_count"
    assert label(gap, [inner, outer]) == "dispatcher.wait_count"
    # a span that covers more of the gap beats an inner one covering less
    part = Event("dispatcher.unpack", 10.8 * MS, 1 * MS)
    assert label(gap, [outer, part]) == "service.poll"
    assert label((30 * MS, 31 * MS), [outer]) == "none"
    # benchmark spans alone, one after another: the plain labels
    bench = [Event("service.poll", 0, 6.5 * MS),
             Event("client.idle", 6.5 * MS, 3 * MS)]
    for g in [(3 * MS, 5 * MS), (6 * MS, 9 * MS), (7 * MS, 8 * MS)]:
        assert label(g, bench) == base.label(g, bench)


def test_recorded_uct_trace_maps_ops_to_scope_paths():
    paths = op_paths(UCT.read_bytes())["/device:TPU:0"]
    kernel = [v for k, v in paths.items() if k.startswith("%uct_scores.1 ")]
    assert len(kernel) == 1
    assert kernel[0].rsplit(":", 1)[0].endswith("/pallas_call")
    # the plain reduction's keys read the same through this one
    plain = base.reduce_files([UCT], window_s=1.0)
    out = reduce_files([UCT], window_s=1.0)
    for k, v in plain.items():
        assert out[k] == v, k
    assert out["self_s"] == pytest.approx(out["busy_s"])
    assert out["scoped_share"] == 0.0        # no program scope in it


def test_recorded_scoped_dispatch():
    """One tiny ``SearchService`` dispatch (4x4, 2 slots, 2 lanes, 4 sims,
    one step; ``record_scoped_dispatch.py``), traced on one TPU v5 lite
    chip: every search phase has self time, and the self times add up to
    the busy time."""
    out = reduce_files([SCOPED], window_s=1.0)
    assert not out["dropped"] and out["busy_s"] > 0
    assert out["self_s"] == pytest.approx(out["busy_s"], rel=0.02)
    for s in ("mcts_select", "mcts_expand", "mcts_playout", "mcts_backup",
              "go_flood_fill", "go_group_info", "service_admit",
              "service_ring"):
        assert out["scopes"][s] > 0, s
    assert out["scopes"]["go_flood_fill"] < out["scopes"]["go_group_info"]
    assert 0.9 <= out["scoped_share"] <= 1.0
