"""League launcher: a persistent, crash-resumable evaluation service.

Runs a :class:`~repro.core.league.League` over a set of MCTS
configurations: Bradley–Terry ratings with covariance drive the
schedule (only still-overlapping pairings get more games), the colour
ledger forces strict per-pairing +-1 Black/White balance through the
multiplexed pool, and every wave boundary snapshots league state to
``--state-dir``.  SIGTERM/SIGINT flip the
:class:`~repro.runtime.ft.PreemptionHandler` flag, the league exits at
the next wave boundary, and ``--resume`` continues the exact schedule —
the resumed run converges to the same cross table as an uninterrupted
one.

``--configs`` is a semicolon-separated list of ``k=v,k=v`` overrides on
the shared base config (board/lanes/tree shape come from the other
flags); only traced fields (``sims_per_move``, ``c_uct``,
``virtual_loss``, ``prior_weight``, ``seed``) may differ between
entries — the league exists to multiplex one compiled dispatch.  A
``name=...`` key labels the entry in the standings.

    PYTHONPATH=src python -m repro.launch.league --board 5 --komi 0.5 \
        --configs "sims_per_move=16;sims_per_move=8;sims_per_move=4" \
        --confidence 1.96 --budget 120 --state-dir /tmp/league
"""
from __future__ import annotations

import argparse
import signal

from repro.compile_cache import use_compile_cache
from repro.config import MCTSConfig, apply_overrides
from repro.core.league import League
from repro.go import GoEngine
from repro.runtime.ft import PreemptionHandler


def parse_configs(spec: str, base: MCTSConfig):
    """Parse ``k=v,k=v;k=v,...`` into (configs, names) over ``base``."""
    configs, names = [], []
    for entry in spec.split(";"):
        entry = entry.strip()
        if not entry:
            continue
        cfg, name = base, None
        for kv in entry.split(","):
            k, _, v = kv.partition("=")
            k = k.strip()
            if not _:
                raise ValueError(f"--configs entry {kv!r} is not k=v")
            if k == "name":
                name = v.strip()
            else:
                cfg = apply_overrides(cfg, {k: v.strip()})
        configs.append(cfg)
        names.append(name or f"cfg{len(configs) - 1}:"
                     f"{cfg.lanes}x{cfg.sims_per_move}")
    if len(configs) < 2:
        raise ValueError("--configs needs at least 2 entries")
    return configs, names


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", required=True,
                    help="semicolon-separated k=v,k=v override lists, one "
                         "per player (traced fields only; name=... labels)")
    ap.add_argument("--board", type=int, default=9)
    ap.add_argument("--komi", type=float, default=6.0)
    ap.add_argument("--lanes", type=int, default=8)
    ap.add_argument("--max-nodes", type=int, default=4096)
    ap.add_argument("--confidence", type=float, default=1.96,
                    help="separation threshold in standard errors of the "
                         "rating difference (1.96 = 95%%)")
    ap.add_argument("--budget", type=int, default=None,
                    help="total game budget (default: play to separation)")
    ap.add_argument("--games-per-wave", type=int, default=2,
                    help="games per still-overlapping pairing per wave")
    ap.add_argument("--round-robin", action="store_true",
                    help="control arm: fund every pairing each wave")
    ap.add_argument("--state-dir", default=None,
                    help="wave-boundary snapshot directory (enables "
                         "checkpointing; see --resume)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest valid snapshot in --state-dir "
                         "and continue the schedule")
    ap.add_argument("--max-waves", type=int, default=None)
    ap.add_argument("--slots", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--superstep", type=int, default=4)
    ap.add_argument("--pipeline-depth", type=int, default=1)
    ap.add_argument("--shards", type=int, default=1,
                    help="shard the pool over this many devices")
    ap.add_argument("--placement", default="round_robin")
    args = ap.parse_args()
    use_compile_cache()

    base = MCTSConfig(board_size=args.board, komi=args.komi,
                      lanes=args.lanes, max_nodes=args.max_nodes)
    configs, names = parse_configs(args.configs, base)
    mesh = None
    if args.shards > 1:
        from repro.compat import make_service_mesh
        mesh = make_service_mesh(args.shards)

    engine = GoEngine(args.board, args.komi)
    league = League(
        engine, configs, names=names, z=args.confidence,
        budget=args.budget, games_per_wave=args.games_per_wave,
        schedule="round_robin" if args.round_robin else "adaptive",
        state_dir=args.state_dir, resume=args.resume, slots=args.slots,
        seed=args.seed, superstep=args.superstep, mesh=mesh,
        placement=args.placement, pipeline_depth=args.pipeline_depth,
        preemption=PreemptionHandler(signals=(signal.SIGTERM,
                                              signal.SIGINT)),
        on_wave=lambda rec: print(
            f"wave {rec['wave']}: {rec['games']} games over "
            f"{len(rec['pairs'])} pairings "
            f"(total {rec['games_played']}), separation "
            + " ".join(f"{p}={s}" for p, s in rec["separation"].items())))

    if league.wave:
        print(f"resumed at wave {league.wave} "
              f"({league.games_played} games played)")
    res = league.run(max_waves=args.max_waves)
    print()
    print(res.table())
    verdict = ("converged" if res.converged
               else "preempted" if res.stopped
               else "max waves reached" if args.max_waves is not None
               and res.waves >= args.max_waves
               else "budget exhausted")
    print(f"\n{verdict}: {res.games_played} games over {res.waves} waves")


if __name__ == "__main__":
    main()
