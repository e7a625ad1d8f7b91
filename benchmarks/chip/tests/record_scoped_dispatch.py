"""Records ``data/scoped_dispatch_v5e.xplane.pb``, the trace that
``test_phases.py`` reads: one dispatch of a tiny ``SearchService``
(``board`` x ``board``, 2 slots, 2 lanes, ``sims`` simulations a move,
``steps`` moves a dispatch) and the poll after it, profiled on one TPU
chip after a warm-up dispatch.  With one search iteration XLA drops the
playouts (the root visits do not depend on them), so ``sims`` is at
least 4.  The ``/host:metadata`` plane (the programs' HLO, which the
reduction does not read) is left out of the file, which is gzipped when
its name ends in ``.gz``.

    python3 benchmarks/chip/tests/record_scoped_dispatch.py \
        <out.xplane.pb[.gz]> [board=4] [sims=4] [steps=1]

Prints the trace's size and its phase reduction.
"""
import gzip
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(out: Path, board: int = 4, sims: int = 4,
         steps: int = 1) -> None:
    import jax
    from repro.config import MCTSConfig
    from repro.core.mcts import MCTS
    from repro.core.service import SearchService
    from repro.go.board import GoEngine

    from benchmarks.chip.trace.phases import reduce_files
    from benchmarks.chip.trace.xspace import XSpace

    engine = GoEngine(board, komi=0.5)
    player = MCTS(engine, MCTSConfig(board_size=board, komi=0.5, lanes=2,
                                     sims_per_move=sims, max_nodes=32))
    svc = SearchService(engine, player, player, slots=2, superstep=steps)
    svc.reset(seed=0, game_capacity=4, ring_capacity=8)
    for _ in range(4):
        svc.submit_game()
    svc.flush()
    svc.dispatch()                      # compiles
    svc.poll()
    tmp = tempfile.mkdtemp(prefix="scoped_dispatch_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    svc.dispatch()
    svc.poll()
    jax.profiler.stop_trace()
    space = XSpace.FromString(next(Path(tmp).rglob("*.xplane.pb"))
                              .read_bytes())
    keep = [p for p in space.planes if p.name != "/host:metadata"]
    del space.planes[:]
    space.planes.extend(keep)       # the other fields pass through as read
    out.parent.mkdir(parents=True, exist_ok=True)
    data = space.SerializeToString()
    out.write_bytes(gzip.compress(data) if out.suffix == ".gz" else data)
    shutil.rmtree(tmp, ignore_errors=True)
    red = reduce_files([out], window_s=1.0)
    print(json.dumps({k: red[k] for k in (
        "trace_bytes", "busy_s", "window_s", "self_s", "scoped_share",
        "scopes", "dropped")}))


if __name__ == "__main__":
    main(Path(sys.argv[1]), *map(int, sys.argv[2:]))
