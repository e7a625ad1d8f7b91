"""Go move-serving launcher: batched best-move queries via GoService.

Simulates external traffic: random mid-game positions are queued as serve
tickets and answered through the SearchService dispatcher's slot pool.
``--pipeline-depth K`` streams the serve loop — up to K supersteps stay
in flight while the host queues fresh queries and unpacks answers
(``host blocked`` in the report is the time that overlap removes).
``--eval-config`` serves through the neural evaluation lane
(core/evaluator.py); ``--prior-weight`` then blends UCT toward PUCT per
query without retracing.

    PYTHONPATH=src python -m repro.launch.serve_go --board 5 --sims 32 \
        --queries 8 --prefix-moves 6 --pipeline-depth 4
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.go import GoEngine
from repro.go.board import BLACK
from repro.serving.go_service import GoService


def random_position(engine: GoEngine, rng: np.random.Generator,
                    moves: int) -> tuple[np.ndarray, int]:
    """A plausible mid-game board: ``moves`` uniform legal non-pass moves."""
    import jax.numpy as jnp
    st = engine.init_state()
    for _ in range(moves):
        legal = np.asarray(engine.jit_legal(st))[: engine.n2]
        if not legal.any():
            break
        st = engine.jit_play(st, jnp.int32(rng.choice(np.where(legal)[0])))
    return np.asarray(st.board), int(st.to_play)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--board", type=int, default=9)
    ap.add_argument("--komi", type=float, default=6.0)
    ap.add_argument("--sims", type=int, default=64,
                    help="max playout budget per query (bucket size)")
    ap.add_argument("--lanes", type=int, default=8)
    ap.add_argument("--slots", type=int, default=8,
                    help="concurrent queries per dispatch")
    ap.add_argument("--queries", type=int, default=8)
    ap.add_argument("--prefix-moves", type=int, default=8,
                    help="random moves played before each queried position")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--c-uct", type=float, default=None,
                    help="per-query UCT exploration constant (traced: "
                         "any value reuses the compiled bucket)")
    ap.add_argument("--virtual-loss", type=float, default=None,
                    help="per-query virtual-loss weight (traced)")
    ap.add_argument("--eval-config", default=None, metavar="SPEC",
                    help="serve through the neural evaluation lane: a "
                         "k=v,k=v EvalConfig spec, e.g. "
                         "'d_model=64,ckpt_dir=/tmp/net' (board_size is "
                         "taken from --board); empty string = defaults")
    ap.add_argument("--prior-weight", type=float, default=None,
                    help="per-query UCT<->PUCT blend weight (traced; "
                         "needs --eval-config; 0 = unguided)")
    ap.add_argument("--shards", type=int, default=1,
                    help="shard the serving pool over this many devices")
    ap.add_argument("--placement", default="round_robin",
                    help="query->shard policy (repro.core.placement)")
    ap.add_argument("--pipeline-depth", type=int, default=1,
                    help="supersteps kept in flight by the streaming "
                         "dispatch pipeline (1 = synchronous)")
    args = ap.parse_args()
    use_compile_cache()

    mesh = None
    if args.shards > 1:
        from repro.compat import make_service_mesh
        mesh = make_service_mesh(args.shards)

    mcts_kw = {}
    if args.eval_config is not None:
        from repro.core.evaluator import EvalConfig, EvalService
        cfg = EvalConfig.parse(args.eval_config, board_size=args.board)
        mcts_kw["evaluator"] = EvalService(cfg)

    engine = GoEngine(args.board, args.komi)
    rng = np.random.default_rng(args.seed)
    svc = GoService(board_size=args.board, komi=args.komi,
                    max_sims=args.sims, lanes=args.lanes, slots=args.slots,
                    seed=args.seed, mesh=mesh, placement=args.placement,
                    pipeline_depth=args.pipeline_depth, **mcts_kw)

    boards = [random_position(engine, rng, args.prefix_moves)
              for _ in range(args.queries)]

    # streaming serve loop: queue everything, then collect — result()
    # polls through the bucket pipelines, which keep pipeline-depth
    # supersteps in flight (and stall-guard with max_polls)
    t0 = time.time()
    tickets = [svc.submit(b, to_play=tp, c_uct=args.c_uct,
                          virtual_loss=args.virtual_loss,
                          prior_weight=args.prior_weight)
               for b, tp in boards]
    svc.flush()
    results = [svc.result(t) for t in tickets]
    dt = time.time() - t0

    for (board, to_play), res in zip(boards, results):
        mover = "B" if to_play == BLACK else "W"
        mv = "pass" if res.is_pass else f"{res.coord[0]},{res.coord[1]}"
        top = float(res.root_visits.max())
        print(f"ticket {res.ticket}: {mover} to play -> {mv} "
              f"({top:.0f} visits)")
    sims = args.queries * args.sims
    print(f"{args.queries} queries in {dt:.2f}s "
          f"({args.queries / dt:.1f} moves/s, ~{sims / dt:.0f} sims/s, "
          f"{svc.host_syncs} host syncs, "
          f"{svc.host_blocked_s:.2f}s host blocked, "
          f"pipeline depth {args.pipeline_depth})")
    if mesh is not None:
        print("shard occupancy: "
              + " ".join(f"{o:.2f}" for o in svc.shard_occupancy()))


if __name__ == "__main__":
    main()
