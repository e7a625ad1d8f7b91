"""Per-phase device time and program-span gap labels from a profiler trace.

Extends :mod:`.reduce` with what the program's own instrumentation
writes into a trace:

* ``jax.named_scope`` on the search phases (``core/mcts.py``,
  ``go/board.py``, ``core/service.py``): each XLA op's metadata carries
  its scope path (the ``tf_op`` stat, read by :mod:`.xspace`);
* ``jax.profiler.TraceAnnotation`` spans at the host's layer boundaries
  (``serving.``, ``scheduler.``, ``dispatcher.``).

On a TPU the ``XLA Ops`` line nests events: a ``while`` op's event spans
the ops of its body.  An op's *self* time is its duration less that of
the op events directly inside it, so that self times add up to the busy
time once.  :func:`reduce_files` returns what :func:`.reduce.reduce_files`
returns, with:

* ``self_s``: the sum of the self times (equal to ``busy_s`` when every
  op event nests properly);
* ``scopes``: self seconds under each program scope in :data:`SCOPES`.
  An op counts under a scope when a component of its path is the scope's
  name, or the name wrapped by a transform (``vmap(mcts_playout)``).
  Scopes nest (``go_flood_fill`` runs inside ``mcts_playout``), so their
  seconds overlap;
* ``scoped_share``: the self time under any program scope over
  ``busy_s``;
* ``breakdown.idle_gaps`` labelled by the innermost span, benchmark or
  program, that overlaps each gap most.  With only the benchmark's spans
  in a trace, the labels are :func:`.reduce.label`'s.

Self times and scopes are taken over every device, averaged like
``busy_s``.  A device that dropped trace buffers lost its later events,
so ``dropped`` marks these sums as short too.
"""
from __future__ import annotations

import gzip
import re
from collections import defaultdict
from typing import Dict, Iterable, List

import numpy as np

from . import reduce as base
from .xspace import op_paths

SCOPES = ("mcts_select", "mcts_expand", "mcts_playout", "mcts_backup",
          "go_flood_fill", "go_group_info", "service_admit", "service_ring")
SPAN_PREFIXES = base.SPAN_PREFIXES + ("serving.", "scheduler.",
                                      "dispatcher.")
# a component ``name``, or ``name`` wrapped by transforms: ``vmap(name)``
_WRAPPED = re.compile(r"^(?:[A-Za-z_][\w.]*\()*([A-Za-z_]\w*)\)*$")


def scopes_of(path: str) -> frozenset:
    """The program scopes on an op's ``tf_op`` path
    (``<component>/.../<op>:<type>``).  A ``jit(name)`` component names a
    function, not a scope."""
    found = set()
    for part in path.rsplit(":", 1)[0].split("/"):
        m = _WRAPPED.match(part)
        if m and m.group(1) in SCOPES and not part.startswith("jit("):
            found.add(m.group(1))
    return frozenset(found)


def self_times(ops: List[base.Event]) -> List[float]:
    """Each op's duration less its directly nested ops' (one line's
    events), in the order of ``ops``: one sweep in start order, longer
    first at equal starts, with a stack of the events still open."""
    n = len(ops)
    start = np.fromiter((e.start_ns for e in ops), np.float64, n)
    dur = np.fromiter((e.dur_ns for e in ops), np.float64, n)
    s, d = start.tolist(), dur.tolist()
    own = d[:]
    ends: List[float] = []                  # the open events' ends ...
    opened: List[int] = []                  # ... and indices
    for i in np.lexsort((-dur, start)).tolist():
        t0 = s[i]
        while ends and ends[-1] <= t0:
            ends.pop()
            opened.pop()
        t1 = t0 + d[i]
        if ends:
            own[opened[-1]] -= min(t1, ends[-1]) - t0
        ends.append(t1)
        opened.append(i)
    return own


def phase_times(ops: List[base.Event], paths: Dict[str, str]) -> tuple:
    """``(self seconds, {scope: self seconds}, scoped self seconds)`` of
    one device's ops; ``paths`` maps an op event's name to its
    ``tf_op``."""
    by_name: Dict[str, float] = defaultdict(float)
    for e, own in zip(ops, self_times(ops)):
        by_name[e.name] += own
    per = dict.fromkeys(SCOPES, 0.0)
    scoped = 0.0
    for name, own in by_name.items():
        found = scopes_of(paths.get(name, ""))
        if found:
            scoped += own
            for sc in found:
                per[sc] += own
    return (sum(by_name.values()) / 1e9,
            {k: v / 1e9 for k, v in per.items()}, scoped / 1e9)


def label(gap: tuple, spans: Iterable[base.Event]) -> str:
    """The span that overlaps an idle gap most, the innermost (shortest)
    of those that overlap it equally, or 'none'."""
    best, name = (0.0, 0.0), "none"
    for sp in spans:
        o = min(gap[1], sp.start_ns + sp.dur_ns) - max(gap[0], sp.start_ns)
        if o > 0 and (o, -sp.dur_ns) > best:
            best, name = (o, -sp.dur_ns), sp.name
    return name


def reduce(devices: Dict[str, base.DeviceEvents], spans: List[base.Event],
           paths: Dict[str, Dict[str, str]], window_s: float) -> dict:
    """:func:`.reduce.reduce` over the benchmark's spans, plus the phase
    keys and the gap labels over every span (see the module's doc)."""
    bench = [s for s in spans if s.name.startswith(base.SPAN_PREFIXES)]
    out = base.reduce(devices, bench, window_s)
    n = max(len(devices), 1)
    self_s, scoped = 0.0, 0.0
    scopes = dict.fromkeys(SCOPES, 0.0)
    for name, dev in devices.items():
        total, per, sc = phase_times(dev.ops, paths.get(name, {}))
        self_s += total / n
        scoped += sc / n
        for k, v in per.items():
            scopes[k] += v / n
    out["self_s"] = self_s
    out["scopes"] = scopes
    out["scoped_share"] = scoped / out["busy_s"] if out["busy_s"] > 0 \
        else None
    first = next((dev for _, dev in sorted(devices.items())), None)
    if first is not None:
        events = [e for dev in devices.values() for e in dev.ops + dev.modules]
        lo = min((e.start_ns for e in events), default=0.0)
        hi = max((e.start_ns + e.dur_ns for e in events), default=lo)
        busy = base.merge((e.start_ns, e.start_ns + e.dur_ns)
                          for e in (first.ops or first.modules))
        idle = sorted(base.gaps(busy, lo, hi),
                      key=lambda g: g[0] - g[1])[:10]
        out["breakdown"]["idle_gaps"] = [
            [label(g, spans), (g[1] - g[0]) / 1e9] for g in idle]
    return out


def reduce_files(paths, window_s: float) -> dict:
    """:func:`reduce` over the trace files of one run (one per host),
    each as the profiler wrote it or gzipped."""
    from jax.profiler import ProfileData
    devices, spans, tf_ops = {}, [], {}
    for p in paths:
        data = p.read_bytes()
        if data[:2] == b"\x1f\x8b":
            data = gzip.decompress(data)
        profile = ProfileData.from_serialized_xspace(data)
        d, _ = base.events_of(profile)
        devices.update(d)
        for plane in profile.planes:
            if plane.name == "/host:CPU":
                for line in plane.lines:
                    spans.extend(base.Event(e.name, e.start_ns,
                                            e.duration_ns)
                                 for e in line.events
                                 if e.name.startswith(SPAN_PREFIXES))
        tf_ops.update(op_paths(data))
    out = reduce(devices, spans, tf_ops, window_s)
    out["trace_bytes"] = sum(p.stat().st_size for p in paths)
    return out
