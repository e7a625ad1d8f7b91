"""Self-play launcher: the paper's experiment as a CLI.

Runs the effective-speedup match (2n lanes vs n lanes) for one point of
Figs. 4/5/11 on the batched game arena (core/arena.py): one search per
move, ``--arena-slots`` concurrent games with finished slots refilled
from the pending queue.

    PYTHONPATH=src python -m repro.launch.selfplay --board 5 --lanes 2 \
        --sims 32 --games 8 --arena-slots 4
"""
from __future__ import annotations

import argparse
import time

from repro.compile_cache import use_compile_cache
from repro.config import MCTSConfig
from repro.core.selfplay import effective_speedup_point
from repro.go import GoEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--board", type=int, default=9)
    ap.add_argument("--komi", type=float, default=6.0)
    ap.add_argument("--lanes", type=int, default=4,
                    help="base thread count n (plays 2n vs n)")
    ap.add_argument("--sims", type=int, default=64,
                    help="playouts/move for the base player")
    ap.add_argument("--games", type=int, default=16)
    ap.add_argument("--max-nodes", type=int, default=2048)
    ap.add_argument("--parallelism", default="tree",
                    choices=("tree", "root", "leaf"))
    ap.add_argument("--affinity", default="compact")
    ap.add_argument("--virtual-loss", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--arena-slots", type=int, default=0,
                    help="concurrent arena games (0 = one slot per game)")
    ap.add_argument("--max-moves", type=int, default=0,
                    help="per-game move cap (0 = engine default)")
    ap.add_argument("--refill", default="device",
                    choices=("device", "host"),
                    help="slot refill: SearchService device-side queue "
                         "(default) or the PR 1 host queue")
    args = ap.parse_args()
    use_compile_cache()

    eng = GoEngine(args.board, args.komi)
    cfg = MCTSConfig(board_size=args.board, komi=args.komi,
                     lanes=args.lanes, sims_per_move=args.sims,
                     max_nodes=args.max_nodes, parallelism=args.parallelism,
                     affinity=args.affinity, virtual_loss=args.virtual_loss)
    t0 = time.time()
    res = effective_speedup_point(eng, cfg, games=args.games,
                                  seed=args.seed,
                                  batch=args.arena_slots,
                                  max_moves=args.max_moves or None,
                                  refill=args.refill)
    dt = time.time() - t0
    moves = res.mean_moves * args.games
    print(f"board {args.board}x{args.board}  {2 * args.lanes} vs "
          f"{args.lanes} lanes  {args.sims} sims/move")
    print(f"  2x player win rate: {res.rate}")
    print(f"  games {res.a_wins}W/{res.b_wins}L/{res.draws}D  "
          f"mean length {res.mean_moves:.1f}  "
          f"mean tree {res.mean_tree_nodes:.0f} nodes  {dt:.1f}s  "
          f"({moves / dt:.1f} moves/s)")


if __name__ == "__main__":
    main()
