"""Reduction of a profiler trace to the benchmark's device numbers.

The JAX profiler writes one ``.xplane.pb`` per host.  Each TPU is a plane
``/device:TPU:<n>`` with the lines ``XLA Modules`` (one event per program
run) and ``XLA Ops`` (one event per operation run); the host is the plane
``/host:CPU``, whose lines hold the benchmark's own spans
(``jax.profiler.TraceAnnotation``).  All times are nanoseconds on one
clock that starts at the profiler's start.

:func:`events_of` turns a trace into plain lists, and :func:`reduce`
computes, from those lists alone:

* ``window_s``: from the first event a device recorded to the end of the
  last.  A device starts recording some tens of ms after the profiler
  starts and stops some hundreds of ms before the profile ends, so the
  span before and after is not known to be idle; the benchmark starts
  a trace while the device is busy and ends it with a dispatch or inside
  one (see ``harness.Tracer``);
* ``busy_s``: per device, the union of the intervals in which an
  operation ran (program runs stand in where a trace holds no
  operations), averaged over the devices.  A device that dropped trace
  buffers lost every event past some point, the end of the program then
  running included, so ``dropped`` marks its busy time as short, and the
  readers of busy or idle time then read nothing;
* ``programs``: device seconds and runs of each program, by name;
* ``kernels``: device seconds and calls of each named Pallas kernel, and
  its calls by output shape;
* ``breakdown``: the ten operations that took most device time, and the
  ten longest idle gaps, each named by the benchmark span the host was in.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple

# a Pallas call shows as an XLA op ``%<kernel>[.<n>] = <out shape>
# custom-call(...)``; the output shape follows the ``=``
KERNEL_RE = re.compile(
    r"^%([A-Za-z_][A-Za-z0-9_]*?)(?:\.\d+)? = (\S+) custom-call")
DROPPED = "Trace Buffers Dropped"
LAYOUT_RE = re.compile(r"\{[^}]*\}")
SPAN_PREFIXES = ("client.", "service.")


class Event(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float


class DeviceEvents(NamedTuple):
    modules: List[Event]
    ops: List[Event]
    dropped: bool = False        # the device lost events: ops incomplete


def events_of(profile) -> tuple:
    """``({device plane: DeviceEvents}, [host spans])`` of a
    ``jax.profiler.ProfileData``."""
    devices, spans = {}, []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            modules, ops, dropped = [], [], False
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = [Event(e.name, e.start_ns, e.duration_ns)
                               for e in line.events]
                elif line.name == "XLA Ops":
                    ops = [Event(e.name, e.start_ns, e.duration_ns)
                           for e in line.events]
                else:
                    dropped |= any(e.name == DROPPED for e in line.events)
            devices[plane.name] = DeviceEvents(modules, ops, dropped)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans.extend(Event(e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIXES))
    return devices, spans


def merge(intervals: Iterable[tuple]) -> List[list]:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(busy: List[list], lo: float, hi: float) -> List[tuple]:
    """Idle ``(start, end)`` spans of ``[lo, hi]`` between busy ones."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def program_name(module: str) -> str:
    """``jit__dispatch_impl(6748...)`` -> ``jit__dispatch_impl``."""
    return module.split("(")[0]


def op_name(op: str) -> str:
    """``%fusion.17 = s32[81]{0:T(128)} fusion(...), kind=kLoop`` ->
    ``%fusion.17 = s32[81] fusion``: the instruction, its shape and kind."""
    return LAYOUT_RE.sub("", op).split("(")[0].strip()


def label(gap: tuple, spans: List[Event]) -> str:
    """The benchmark span that overlaps an idle gap most, or 'none'."""
    best, name = 0.0, "none"
    for sp in spans:
        o = min(gap[1], sp.start_ns + sp.dur_ns) - max(gap[0], sp.start_ns)
        if o > best:
            best, name = o, sp.name
    return name


def reduce(devices: Dict[str, DeviceEvents], spans: List[Event],
           window_s: float) -> dict:
    """The device numbers of a trace; ``window_s``, the host's span of
    the profile, stands in for a trace with no device event."""
    events = [e for dev in devices.values() for e in dev.ops + dev.modules]
    lo = min((e.start_ns for e in events), default=0.0)
    hi = max((e.start_ns + e.dur_ns for e in events),
             default=lo + window_s * 1e9)
    busy_s, programs = [], defaultdict(lambda: [0.0, 0])
    kernels = defaultdict(lambda: [0.0, 0, defaultdict(int)])
    dropped = False
    ops = defaultdict(float)
    first_busy = None
    for _, dev in sorted(devices.items()):
        dropped |= dev.dropped
        busy = merge((e.start_ns, e.start_ns + e.dur_ns)
                     for e in (dev.ops or dev.modules))
        busy_s.append(sum(e - s for s, e in busy) / 1e9)
        if first_busy is None:
            first_busy = busy
        for e in dev.modules:
            p = programs[program_name(e.name)]
            p[0] += e.dur_ns / 1e9
            p[1] += 1
        for e in dev.ops:
            ops[op_name(e.name)] += e.dur_ns / 1e9
            m = KERNEL_RE.match(e.name)
            if m:
                k = kernels[m.group(1)]
                k[0] += e.dur_ns / 1e9
                k[1] += 1
                k[2][m.group(2).split("{")[0]] += 1
    idle = sorted(gaps(first_busy or [], lo, hi),
                  key=lambda g: g[0] - g[1])[:10]
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    n = max(len(devices), 1)
    return {
        "busy_s": sum(busy_s) / n,
        "window_s": (hi - lo) / 1e9,
        "devices": len(devices),
        "programs": {k: {"seconds": v[0], "runs": v[1]}
                     for k, v in programs.items()},
        "kernels": {k: {"seconds": v[0], "calls": v[1],
                        "shapes": dict(v[2])}
                    for k, v in kernels.items()},
        "dropped": dropped,
        "op_s": sum(ops.values()),
        "breakdown": {
            "device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[label(g, spans), (g[1] - g[0]) / 1e9]
                          for g in idle],
        },
    }


def reduce_files(paths, window_s: float) -> dict:
    """:func:`reduce` over the trace files of one run (one per host)."""
    from jax.profiler import ProfileData
    devices, spans = {}, []
    for p in paths:
        d, s = events_of(ProfileData.from_file(str(p)))
        devices.update(d)
        spans.extend(s)
    out = reduce(devices, spans, window_s)
    out["trace_bytes"] = sum(p.stat().st_size for p in paths)
    return out
