"""Runs one cell of the benchmark once and prints its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name from
``BENCHMARK.json``: the configuration file it names, the traffic file
``traffic/<traffic>.json``, the driver ``drivers/<driver>.py`` that the
traffic file names, and one reader ``metrics/<metric>.py`` per metric.
A reader that finds nothing to read returns None and its metric is left
out of the line.

The run builds the cell's service, warms every shape its traffic uses
(set-up), measures for ``--seconds``, reads the device's peak memory,
then checks the answers (after the window, not counted in set-up).
With ``--trace 1`` the profiler records the part of the window that the
traffic file places (see :class:`Tracer`), the trace is reduced in the
same process and deleted, and the per-layer metrics are printed instead
of the end-to-end ones.

It refuses to run where the default device is not a TPU, or where there
are fewer chips than the cell asks for.  ``--cpu-rehearsal`` runs the
cell's tiny ``rehearsal`` sizes on the CPU, for tests: its line holds
``correct`` and the checks, and no metric.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import threading
import time
import traceback
from pathlib import Path

HERE = Path(__file__).resolve().parent


def fail(msg: str) -> int:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    return 2


def read_metric(base: Path, name: str, ctx: dict):
    """The value that ``<base>/metrics/<name>.py`` reads from ``ctx``."""
    return load_module(base / "metrics" / f"{name}.py",
                       "benchmarks.chip.metric").read(ctx)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(bench: dict, name: str, root: Path, base: Path = HERE) -> tuple:
    """(workload, configuration, traffic) of the cell ``name``: the
    configuration file that ``BENCHMARK.json`` names, and the traffic
    file ``<base>/traffic/<traffic>.json``."""
    work = next((w for w in bench["workloads"] if w["name"] == name), None)
    if work is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == work["config"])
    cfg = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (base / "traffic" / f"{work['traffic']}.json").read_text())
    return work, cfg, traffic


def metrics_for(bench: dict, name: str, trace: bool) -> list:
    """The metrics a run of cell ``name`` reports."""
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


class Tracer:
    """The profiler over part of the window, in a run of its own.

    The traffic file places the traced span: it starts ``trace_start_s``
    seconds into the window (counted from the window's end when negative)
    and stops at the first chance ``trace_s`` seconds later.  The span
    starts while the device is busy, since the device records its events
    only some tens of ms after the profiler starts: the open loop starts
    it from a timer, while the client waits on a dispatch (:meth:`arm`),
    and stops it at a :meth:`tick`; the closed loop starts and stops it
    itself around the boundary between two dispatches (see
    ``drivers/arena.py``).
    """

    def __init__(self, on: bool, traffic: dict, seconds: float):
        start = float(traffic.get("trace_start_s", 0.0))
        self.on = on
        self.start_at = start if start >= 0 else max(0.0, seconds + start)
        self.length = float(traffic.get("trace_s", 0.0))
        self.dir = None
        self.timer = None
        self.t0 = self.t1 = self.at = None
        self.stall_s = 0.0        # spent writing the trace out

    def due(self, elapsed: float) -> bool:
        """Whether the span should start now."""
        return self.on and self.t0 is None and elapsed >= self.start_at

    @property
    def running(self) -> bool:
        return self.t0 is not None and self.t1 is None

    def arm(self, elapsed: float) -> None:
        """Start the span from a timer ``start_at`` into the window."""
        if self.on:
            self.timer = threading.Timer(
                max(0.0, self.start_at - elapsed), self.start,
                args=(self.start_at,))
            self.timer.start()

    def tick(self, elapsed: float) -> None:
        if self.running and elapsed - self.at >= self.length:
            self.stop()

    def start(self, elapsed: float) -> None:
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0, self.at = time.perf_counter(), elapsed

    def stop(self) -> None:
        if self.timer is not None:       # a start under way finishes first
            self.timer.cancel()
            self.timer.join()
        if self.running:
            import jax
            self.t1 = time.perf_counter()
            jax.profiler.stop_trace()
            self.stall_s = time.perf_counter() - self.t1

    def files(self) -> list:
        return sorted(Path(self.dir).rglob("*.xplane.pb")) if self.dir \
            else []

    def close(self) -> None:
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def device_info(devices) -> dict:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use"))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max((p for p in peaks if p is not None),
                                     default=None)}


def main(argv=None, t_start=None, root: Path = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    root = root or HERE.parents[1]
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="the cell's tiny sizes on the CPU; no metric")
    ap.add_argument("--rate", type=float, default=None,
                    help="offered queries/s in place of the traffic "
                         "file's, for finding a serve cell's knee")
    ap.add_argument("--control", action="store_true",
                    help="the control: the sampled answers come from the "
                         "reference search with its playouts cut short "
                         "(control_playout_moves), which must fail the "
                         "comparison")
    args = ap.parse_args(argv)

    try:
        bench = json.loads((root / "BENCHMARK.json").read_text())
        work, cfg, traffic = cell(bench, args.workload, root)
    except (OSError, KeyError, StopIteration, ValueError) as e:
        return fail(f"cannot load the cell: {e!r}")
    if args.rate is not None:
        traffic = dict(traffic, rate_per_s=args.rate)
    if not (root / "src" / "repro").is_dir():
        return fail(f"no program under {root / 'src'}")
    sys.path.insert(0, str(root / "src"))

    import jax
    cache_dir = None
    if not args.cpu_rehearsal:
        from repro.compile_cache import use_compile_cache
        cache_dir = use_compile_cache()
        # every program of the cell, however small, is read back from the
        # cache by the next run
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    devices = jax.devices()
    chips = int(work["chips"])
    if args.cpu_rehearsal:
        if devices[0].platform != "cpu":
            return fail("--cpu-rehearsal runs on the CPU only")
        cfg = dict(cfg, **cfg["rehearsal"])
    elif devices[0].platform != "tpu":
        return fail(f"the default device is {devices[0].platform!r}, not "
                    "a TPU; refusing to run")
    if len(devices) < chips:
        return fail(f"the cell needs {chips} chips, found {len(devices)}")
    cfg = dict(cfg, chips=chips, control=args.control)
    devices = devices[:chips]

    from .compile_meter import CompileMeter
    from .trace import reduce as trace_reduce
    meter = CompileMeter()
    tracer = Tracer(bool(args.trace), traffic, args.seconds)
    driver = importlib.import_module(
        f"benchmarks.chip.drivers.{traffic['driver']}").Driver(
            cfg, traffic, args.seed, span)

    window, reduction, checks = None, None, {}
    device = device_info(devices)
    setup_s = None
    try:
        driver.setup(args.seconds)
        setup_s = time.perf_counter() - t_start
        at_setup = meter.snapshot()
        window = driver.window(args.seconds, tracer)
        tracer.stop()
        at_window = meter.snapshot()
        t_window = time.perf_counter()
        device = device_info(devices)
        if tracer.t1 is not None:
            reduction = trace_reduce.reduce_files(
                tracer.files(), tracer.t1 - tracer.t0)
            device["busy_s"] = reduction["busy_s"]
            device["window_s"] = reduction["window_s"]
        t_reduce = time.perf_counter()
        checks = driver.check()
        print(json.dumps({
            "setup_s": setup_s, "setup": at_setup,
            "window_compiles": at_window["compiles"] - at_setup["compiles"],
            "trace_reduce_s": t_reduce - t_window,
            "check_s": time.perf_counter() - t_reduce,
            "window": {k: v for k, v in window.items()
                       if not isinstance(v, (list, tuple))},
            "trace": None if reduction is None else {
                k: v for k, v in reduction.items() if k != "breakdown"},
            "cache_dir": cache_dir}), flush=True)
    except Exception:            # a program that fails is not correct
        traceback.print_exc()
        checks = {"run_error": 1}
    finally:
        tracer.stop()
        tracer.close()
    limits = dict(cfg["limits"], run_error=0)
    compared = {k: {"value": checks[k], "limit": limits[k]}
                for k in limits if k in checks}
    correct = "run_error" not in checks and all(
        v["value"] <= v["limit"] for v in compared.values())
    if window is None:
        window = {"attempted": 0, "failed": 0}

    ctx = {"setup_s": setup_s, "window": window, "trace": reduction,
           "device": device, "cfg": cfg, "seconds": args.seconds}
    metrics = {}
    if "run_error" not in checks and not args.cpu_rehearsal:
        for m in metrics_for(bench, args.workload, bool(args.trace)):
            value = read_metric(HERE, m["name"], ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": correct, "attempted": window["attempted"],
            "failed": window["failed"], "metrics": metrics,
            "device": device}
    if reduction is not None:
        line["breakdown"] = reduction["breakdown"]
    if args.cpu_rehearsal:
        line["rehearsal"] = True
    if args.control:
        line["control"] = True
    line["checks"] = compared
    for k, v in compared.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
