"""HTTP move-serving launcher: the SLO-aware front door over GoService.

Starts :class:`~repro.serving.server.GoMoveServer` on one persistent
:class:`~repro.serving.go_service.GoService` (per-komi buckets, streaming
dispatch pipelines) and serves until interrupted:

    PYTHONPATH=src python -m repro.launch.serve_http --board 9 \
        --sims 64 --slots 8 --port 8080 --pipeline-depth 2

Then::

    curl -s localhost:8080/healthz
    curl -s -X POST localhost:8080/v1/best_move \
        -d '{"board": [0, 0, ...81 ints...], "deadline_ms": 500}'
    curl -s localhost:8080/metrics

Load-shedding responses are explicit: 503 = over capacity (queue depth
past ``--admission-limit``), 504 = deadline shed.  See
docs/ARCHITECTURE.md "Serving tier" for the request lifecycle and the
deadline -> downgrade -> shed decision table.
"""
from __future__ import annotations

import argparse
import asyncio

from repro.compile_cache import use_compile_cache
from repro.serving.go_service import DeadlinePolicy, GoService
from repro.serving.server import GoMoveServer


def build_service(args: argparse.Namespace) -> GoService:
    """Construct the GoService a parsed CLI asks for."""
    mesh = None
    if args.shards > 1:
        from repro.compat import make_service_mesh
        mesh = make_service_mesh(args.shards)
    policy = DeadlinePolicy(slots=args.slots,
                            floor_sims=args.floor_sims)
    return GoService(board_size=args.board, komi=args.komi,
                     max_sims=args.sims, lanes=args.lanes,
                     slots=args.slots, seed=args.seed, mesh=mesh,
                     placement=args.placement,
                     pipeline_depth=args.pipeline_depth,
                     admission_limit=args.admission_limit,
                     deadline_policy=policy)


async def serve(args: argparse.Namespace) -> None:
    """Start the front door and serve until cancelled."""
    service = build_service(args)
    server = GoMoveServer(service)
    port = await server.start(host=args.host, port=args.port)
    print(f"serving Go moves on http://{args.host}:{port} "
          f"(board {args.board}, komi {args.komi}, max_sims {args.sims}, "
          f"admission limit {service.admission_limit})")
    try:
        await asyncio.Event().wait()          # until Ctrl-C
    finally:
        await server.stop()


def main() -> None:
    """CLI entry point."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080,
                    help="0 picks a free port (printed at startup)")
    ap.add_argument("--board", type=int, default=9)
    ap.add_argument("--komi", type=float, default=6.0)
    ap.add_argument("--sims", type=int, default=64,
                    help="max playout budget per query (bucket size)")
    ap.add_argument("--lanes", type=int, default=8)
    ap.add_argument("--slots", type=int, default=8,
                    help="concurrent queries per dispatch")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shards", type=int, default=1,
                    help="shard the serving pool over this many devices")
    ap.add_argument("--placement", default="round_robin",
                    help="query->shard policy (repro.core.placement)")
    ap.add_argument("--pipeline-depth", type=int, default=1,
                    help="supersteps kept in flight per bucket")
    ap.add_argument("--admission-limit", type=int, default=0,
                    help="shed (503) past this many outstanding requests "
                         "per bucket (0 = the bucket queue capacity)")
    ap.add_argument("--floor-sims", type=int, default=4,
                    help="minimum downgraded playout budget before a "
                         "deadline'd query is shed instead")
    args = ap.parse_args()
    use_compile_cache()
    try:
        asyncio.run(serve(args))
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
