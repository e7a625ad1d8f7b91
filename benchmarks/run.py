"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Scaled for a single-CPU
container (see each module's docstring for the paper mapping and
EXPERIMENTS.md for the recorded results).

    PYTHONPATH=src python -m benchmarks.run [only] [--devices N]

``--devices N`` fakes N CPU devices (or, on a real multi-device backend,
is capped by what exists) so the sharded-service sweep in
``bench_service`` and the request-level placement section of
``fig_affinity`` exercise real shards — the ROADMAP's real-device-sweep
prep: on TPU/GPU the same flag-free invocation picks up every physical
device automatically.
"""
from __future__ import annotations

import os
import sys
import time


def _parse_args(argv):
    only, devices = None, None
    i = 1
    while i < len(argv):
        a = argv[i]
        if a == "--devices" and i + 1 < len(argv):
            devices, i = int(argv[i + 1]), i + 2
        elif a.startswith("--devices="):
            devices, i = int(a.split("=", 1)[1]), i + 1
        else:
            only, i = a, i + 1
    return only, devices


def main() -> None:
    only, devices = _parse_args(sys.argv)
    if devices is not None and devices > 1:
        # must land before any figure module initialises the jax backend
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={devices}"
                .strip())
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    figures = [
        ("fig_microbench", "Figs 6-8: FMA throughput + bandwidth"),
        ("fig_throughput", "Fig 10: playouts/sec vs lanes"),
        ("fig_treesize", "Fig 12: tree size vs budget"),
        ("fig_affinity", "Fig 9: affinity policies"),
        ("fig_selfplay", "Figs 4/5/11: effective speedup"),
        ("fig_modes", "Related work: tree vs root vs leaf parallelism"),
        ("fig_roofline", "Roofline table from the dry-run"),
        ("bench_arena", "Arena self-play throughput (BENCH_selfplay.json)"),
        ("bench_service", "Service dispatcher throughput (BENCH_service.json)"),
        ("bench_eval", "Evaluation-lane throughput (BENCH_eval.json)"),
        ("bench_kernels", "Fused superstep kernels (BENCH_kernels.json)"),
        ("bench_league", "League scheduling (BENCH_league.json)"),
    ]
    print("name,us_per_call,derived")
    for mod_name, desc in figures:
        if only and only not in mod_name:
            continue
        print(f"# === {mod_name}: {desc} ===", flush=True)
        t0 = time.time()
        mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
        mod.run()
        print(f"# {mod_name} done in {time.time() - t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
