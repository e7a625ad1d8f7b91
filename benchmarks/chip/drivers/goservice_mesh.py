"""Open-loop best-move queries to a ``GoService`` sharded over the
cell's chips (``chips`` > 1 builds the mesh in :mod:`.goservice`).

The submit/poll loop and the check are :mod:`.goservice`'s, and so is
the set-up, but for how far it warms the ring reads (see
:func:`warm_shard_rings`).  The window adds the deltas of three of the
program's host counters, each read only where the program has it (an
older program lacks ``read_rows_s``, and the reader of that metric then
prints nothing):

* ``host_busy_s``: time in ``submit`` and ``poll`` less the blocking
  device waits in them;
* ``read_rows_s``: time in ``poll``'s reads of new result-ring rows, one
  read a shard ring, the blocking transfer included;
* ``supersteps``: supersteps reconciled (``scheduler_stats()``'s
  ``reconciles``).
"""
from __future__ import annotations

from unittest import mock

from . import common, goservice


def warm_shard_rings(svc, most: int) -> None:
    """:func:`.common.warm_ring_reads` up to what one poll can find in a
    shard's ring, in place of the whole pool's ``most``.

    At pipeline depth 1 a poll reads what one superstep finished: at most
    one superstep of the shard's slots a shard (a deeper pipeline reads
    its rows from a raw back-buffer and gathers nothing on the device).
    Warming up to the pool's count on every shard compiles four times the
    gathers at four shards (899 against 231 programs, counted on four CPU
    devices); on four TPU v5e chips a cold set-up with that did not end
    in 540 s, and takes 156.6 s with this.
    """
    common.warm_ring_reads(svc, svc._shard_slots * svc.superstep)


class Driver(goservice.Driver):
    def setup(self, seconds: float) -> None:
        with mock.patch.object(goservice, "warm_ring_reads",
                               warm_shard_rings):
            super().setup(seconds)

    def host_counters(self) -> dict:
        svc = self.svc
        return {"host_busy_s": getattr(svc, "host_busy_s", None),
                "read_rows_s": getattr(svc, "read_rows_s", None),
                "supersteps": svc.scheduler_stats().get("reconciles")}

    def window(self, seconds: float, tracer) -> dict:
        before = self.host_counters()
        out = super().window(seconds, tracer)
        for k, v in self.host_counters().items():
            if v is not None and before[k] is not None:
                out[k] = v - before[k]
        return out
