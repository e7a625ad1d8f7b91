"""The trace reduction, on a recorded v5e trace and on synthetic events."""
from pathlib import Path

import pytest

from benchmarks.chip.trace.reduce import (DeviceEvents, Event, KERNEL_RE,
                                          gaps, merge, reduce, reduce_files)

DATA = Path(__file__).parent / "data" / "uct_scores_v5e.xplane.pb"


def test_recorded_v5e_trace():
    """50 jitted ``uct_scores`` calls of one [1, 82] row, traced on one
    TPU v5 lite chip: 50 program runs, 50 Pallas calls."""
    out = reduce_files([DATA], window_s=1.0)
    assert out["devices"] == 1
    # the window is the span of the device's events, 48.92-60.69 ms after
    # the profiler's start, not the host's
    assert out["window_s"] == pytest.approx(0.060688343 - 0.048921636)
    prog = out["programs"]["jit__lambda"]
    assert prog["runs"] == 50
    k = out["kernels"]["uct_scores"]
    assert k["calls"] == 50 and k["shapes"] == {"f32[8,128]": 50}
    assert not out["dropped"]
    assert 0 < k["seconds"] < prog["seconds"]
    # busy time is the union of the op intervals: inside the programs
    assert 0 < out["busy_s"] <= prog["seconds"] + 1e-9
    assert out["op_s"] >= out["busy_s"] - 1e-9
    names = [n for n, _ in out["breakdown"]["device_ops"]]
    assert "%uct_scores.1 = f32[8,128] custom-call" in names
    assert len(names) == 10


def test_kernel_pattern():
    assert KERNEL_RE.match("%uct_scores.1 = f32[8,128]{1,0} custom-call("
                           "f32[8,128] %pad.12)").group(1) == "uct_scores"
    m = KERNEL_RE.match("%uct_scores = f32[4,8,128]{2,1,0:T(8,128)} "
                        "custom-call(x)")
    assert m.group(2).split("{")[0] == "f32[4,8,128]"
    assert not KERNEL_RE.match("%fusion.3 = f32[8] fusion(x), kind=kLoop")


def test_merge_and_gaps():
    assert merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert gaps([[0, 3], [5, 8]], 0, 10) == [(3, 5), (8, 10)]
    assert gaps([[2, 4]], 0, 3) == [(0, 2)]


def test_synthetic_busy_idle_and_labels():
    ms = 1e6
    ops = [Event("%fusion.1 = f32[] fusion()", 0, 2 * ms),
           Event("%fusion.1 = f32[] fusion()", 1 * ms, 2 * ms),   # overlap
           Event("%k.7 = f32[8] custom-call(x)", 5 * ms, 1 * ms),
           Event("%copy.2 = f32[] copy()", 9 * ms, 1 * ms)]
    mods = [Event("jit__dispatch_impl(123)", 0, 6 * ms),
            Event("jit_other(9)", 9 * ms, 1 * ms)]
    spans = [Event("service.poll", 0, 6.5 * ms),
             Event("client.idle", 6.5 * ms, 3 * ms)]
    out = reduce({"/device:TPU:0": DeviceEvents(mods, ops)}, spans,
                 window_s=0.012)
    assert out["window_s"] == pytest.approx(0.010)     # the events' span
    assert out["busy_s"] == pytest.approx(0.005)       # [0,3] [5,6] [9,10]
    assert out["programs"]["jit__dispatch_impl"] == {"seconds": 0.006,
                                                     "runs": 1}
    assert out["kernels"]["k"] == {"seconds": 0.001, "calls": 1,
                                   "shapes": {"f32[8]": 1}}
    gaps_ = out["breakdown"]["idle_gaps"]
    assert [g[0] for g in gaps_] == ["client.idle", "service.poll"]
    assert [g[1] for g in gaps_] == pytest.approx([0.003, 0.002])
    assert out["breakdown"]["device_ops"][0] == ["%fusion.1 = f32[] fusion",
                                                 0.004]


def test_a_drop_is_flagged_not_hidden():
    """A device that dropped trace buffers keeps the window its events
    span; the drop is flagged for the readers."""
    ms = 1e6
    ops = [Event("%f.1 = f32[] fusion()", 2 * ms, 3 * ms),
           Event("%f.2 = f32[] fusion()", 10 * ms, 8 * ms)]
    dev = DeviceEvents([], ops, dropped=True)
    out = reduce({"/device:TPU:0": dev}, [], window_s=0.05)
    assert out["dropped"] and out["window_s"] == pytest.approx(0.016)
    assert out["busy_s"] == pytest.approx(0.011)       # [2,5] and [10,18]
    assert out["breakdown"]["idle_gaps"] == [["none", pytest.approx(0.005)]]


def test_window_spans_the_device_events():
    """A device records only some time after the profiler starts and
    stops recording before it ends: the spans outside its first and last
    events are not idle time."""
    ms = 1e6
    late = [Event("jit_a(1)", 4 * ms, 2 * ms)]
    early = [Event("jit_b(2)", 3 * ms, 1 * ms)]
    out = reduce({"/device:TPU:0": DeviceEvents(late, []),
                  "/device:TPU:1": DeviceEvents(early, [])}, [],
                 window_s=0.008)
    assert out["window_s"] == pytest.approx(0.003)     # 3 ms to 6 ms
    assert out["busy_s"] == pytest.approx(0.0015)      # mean of 2 ms, 1 ms
    assert reduce({}, [], window_s=0.008)["window_s"] == 0.008


def test_idle_readers_read_nothing_from_a_dropped_trace():
    from benchmarks.chip.harness import HERE, read_metric
    t = {"busy_s": 1.0, "window_s": 4.0, "dropped": False,
         "kernels": {"uct_scores": {"seconds": 0.01, "calls": 1,
                                    "shapes": {}}}}
    for name in ("device.idle_share.selfplay",
                 "kernel.uct_scores.step_share.selfplay"):
        assert read_metric(HERE, name, {"trace": t}) is not None
        assert read_metric(HERE, name,
                           {"trace": dict(t, dropped=True)}) is None
    assert read_metric(HERE, "device.idle_share.selfplay",
                       {"trace": t}) == pytest.approx(0.75)


def test_modules_stand_in_without_ops():
    mods = [Event("jit__dispatch_impl(1)", 1e6, 4e6)]
    two = {"/device:TPU:0": DeviceEvents(mods, []),
           "/device:TPU:1": DeviceEvents([Event("jit_x(2)", 0, 2e6)], [])}
    out = reduce(two, [], window_s=0.01)
    assert out["busy_s"] == pytest.approx(0.003)       # mean of 4 ms, 2 ms
    assert out["kernels"] == {}
