"""Streaming dispatch pipeline: overlap host I/O with device supersteps.

The Xeon Phi papers' knee past 32 threads is a *coordination* failure:
the host barriers on every batch of search work, so compute idles while
requests are marshalled.  The PR 2-4 dispatcher kept exactly that shape —
``flush() -> dispatch() -> poll()`` in strict sequence per superstep.
:class:`DispatchPipeline` breaks the barrier: it keeps up to ``depth``
supersteps in flight (JAX async dispatch makes ``dispatch`` an enqueue,
not a wait), capturing a :class:`~repro.core.service.RingView` back
buffer per superstep, and reconciles the oldest view while the device
runs the younger ones.  Host-side work — unpacking results, placement
bookkeeping, packing and flushing new submissions — happens while the
device computes, which is precisely the host<->device transfer overlap
the Phi offload studies identify as the first-order lever.

Contracts:

* ``depth=1`` *is* the synchronous path: one superstep in flight, its
  view reconciled immediately — bit-identical results, syncs, and
  bookkeeping (pinned in tests/test_pipeline.py);
* results are ticket-tagged and order-independent (see
  ``service.SearchResult``): a drain's result *set* is depth-invariant,
  and for submit-then-drain workloads the result *sequence* is too,
  because the device program never depends on host read timing;
* at every reconcile ``submitted == completed + in_flight + shed`` — the
  pipeline checks the service's accounting (including requests the
  serving tier shed before they flushed) and raises on drift;
* a ``service.reset()`` invalidates the window: stale views are evicted,
  never polled.
"""
from __future__ import annotations

import collections
from typing import List, Optional

from jax.profiler import TraceAnnotation


class DispatchPipeline:
    """Keeps up to ``depth`` supersteps in flight over one SearchService.

    The pipeline owns no device state: it is a host-side window of
    :class:`~repro.core.service.RingView` completion handles plus the
    pump/reconcile policy.  ``pump()`` flushes submissions and tops the
    window up; ``reconcile()`` retires the oldest superstep and returns
    its new results; ``run_until_drained()`` alternates the two until
    every submission completes.  Several pipelines over one service are
    not supported (they would race the ring read cursor) — use one
    pipeline per service, as ``SearchService.drain`` and ``GoService``
    do.
    """

    def __init__(self, service, depth: Optional[int] = None,
                 steps: Optional[int] = None):
        self.service = service
        self.depth = int(service.pipeline_depth if depth is None else depth)
        self.steps = int(service.superstep if steps is None else steps)
        if self.depth < 1:
            raise ValueError(f"depth must be >= 1, got {self.depth}")
        if self.steps < 1:
            raise ValueError(f"steps must be >= 1, got {self.steps}")
        self._window = collections.deque()      # oldest superstep first
        self.reconciles = 0
        self.steps_issued = 0
        self.max_in_flight = 0

    @property
    def in_flight_supersteps(self) -> int:
        """Issued but not yet reconciled supersteps (<= depth)."""
        return len(self._window)

    def pump(self) -> int:
        """Flush submissions and top the in-flight window up to depth.

        Every issue is an async enqueue — the host returns immediately
        holding the superstep's ring back buffer.  While the window is
        deep, also refresh the placement policy's landed-occupancy
        estimate (non-blocking; see ``SearchService.peek_landed``).
        Returns the number of supersteps issued.
        """
        svc = self.service
        with TraceAnnotation("scheduler.pump"):
            self._evict_stale()
            svc.flush()
            issued = 0
            while len(self._window) < self.depth and svc.outstanding > 0:
                self._window.append(svc.dispatch_async(self.steps))
                self.steps_issued += self.steps
                issued += 1
            self.max_in_flight = max(self.max_in_flight, len(self._window))
            if self.depth > 1 and self._window:
                svc.peek_landed()
        return issued

    def reconcile(self, block: bool = True) -> List:
        """Retire the oldest in-flight superstep; return its new results.

        Blocks only until *that* superstep's computation lands (its ring
        view is a back buffer no younger superstep touches).  With
        ``block=False`` returns ``[]`` instead of waiting when the
        oldest superstep has not finished yet.  At depth 1 the view is
        the live ring, so the poll keeps the synchronous path's
        scale-with-new-results gather; deeper windows read the snapshot
        raw to stay off the device queue.  Raises if the service's
        request accounting drifted (``submitted != completed +
        in_flight``).
        """
        self._evict_stale()
        if not self._window:
            return []
        head = self._window[0]
        if not block and not head.ring.count.is_ready():
            return []
        self._window.popleft()
        with TraceAnnotation("scheduler.reconcile"):
            out = self.service.poll(view=head if self.depth > 1 else None)
        self.reconciles += 1
        submitted, completed, in_flight = self.service.accounting()
        shed = self.service.shed_total
        if submitted != completed + in_flight + shed:
            raise RuntimeError(
                f"in-flight accounting drifted at reconcile "
                f"{self.reconciles}: {submitted} submitted != "
                f"{completed} completed + {in_flight} in flight + "
                f"{shed} shed")
        return out

    def _evict_stale(self) -> None:
        """Drop views issued before the service's last reset()."""
        while self._window and self._window[0].epoch != self.service.epoch:
            self._window.popleft()

    def stats(self) -> dict:
        """Counters for benchmarks: depth, in-flight high-water, steps."""
        return {"depth": self.depth, "steps_per_superstep": self.steps,
                "max_in_flight": self.max_in_flight,
                "reconciles": self.reconciles,
                "steps_issued": self.steps_issued}

    def run_until_drained(self, max_steps: Optional[int] = None) -> List:
        """Pump + reconcile until every submission completes.

        The drain loop of the dispatcher: with depth 1 this is exactly
        the synchronous ``flush -> dispatch -> poll`` sequence; deeper
        windows keep the device ``depth`` supersteps ahead of the host.
        ``max_steps`` bounds the issued dispatch steps (default scales
        with the outstanding work) and a stall raises.
        """
        svc = self.service
        svc.flush()
        budget = max_steps or (svc.outstanding * (svc.max_moves + 2)
                               + 2 * svc.slots + 16
                               + self.depth * self.steps)
        out: List = []
        while svc.outstanding > 0:
            if self.steps_issued > budget:
                raise RuntimeError(
                    f"DispatchPipeline stalled: {svc.outstanding} requests "
                    f"still outstanding after {self.steps_issued} steps")
            self.pump()
            out.extend(self.reconcile(block=True))
        self._window.clear()
        return out
