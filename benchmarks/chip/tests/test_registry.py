"""A new configuration, traffic mix or metric is found by its name in
BENCHMARK.json, with no file that exists edited."""
import json
from pathlib import Path

from benchmarks.chip.harness import HERE, cell, metrics_for, read_metric

ROOT = HERE.parents[1]


def test_committed_cells_resolve():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        work, cfg, traffic = cell(bench, w["name"], ROOT)
        assert (HERE / "drivers" / f"{traffic['driver']}.py").is_file()
        for trace in (False, True):
            for m in metrics_for(bench, w["name"], trace):
                assert (HERE / "metrics" / f"{m['name']}.py").is_file()
        assert "setup_s" in {m["name"] for m in metrics_for(
            bench, w["name"], False)}


def test_new_files_are_found_by_name(tmp_path: Path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "cfg9.json").write_text(json.dumps({"board_size": 9}))
    (tmp_path / "traffic" / "burst.json").write_text(
        json.dumps({"driver": "goservice", "arrivals": "poisson"}))
    (tmp_path / "metrics" / "probe.burst.py").write_text(
        "def read(ctx):\n    return ctx['window']['answered'] * 2\n")
    bench["configs"].append({"name": "cfg9", "file": "cfg9.json"})
    bench["workloads"].append({"name": "cfg9.burst", "config": "cfg9",
                               "traffic": "burst", "chips": 1})
    bench["end_to_end"][1]["workloads"].append("cfg9.burst")
    bench["per_layer"].append({"name": "probe.burst", "unit": "share",
                               "moves": bench["end_to_end"][1]["name"],
                               "workloads": ["cfg9.burst"]})
    work, cfg, traffic = cell(bench, "cfg9.burst", tmp_path, base=tmp_path)
    assert cfg == {"board_size": 9} and traffic["arrivals"] == "poisson"
    names = {m["name"] for m in metrics_for(bench, "cfg9.burst", True)}
    assert "probe.burst" in names
    assert read_metric(tmp_path, "probe.burst",
                       {"window": {"answered": 21}}) == 42


def test_metric_without_workloads_follows_its_end_to_end_metric():
    bench = {"end_to_end": [{"name": "a", "workloads": ["x"]},
                            {"name": "setup_s"}],
             "per_layer": [{"name": "m", "moves": "a"},
                           {"name": "n", "moves": "setup_s"}]}
    assert [m["name"] for m in metrics_for(bench, "x", True)] == ["m", "n"]
    assert [m["name"] for m in metrics_for(bench, "y", True)] == ["n"]


def _run(cwd: Path, home: Path):
    import subprocess
    import sys
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "serve9.serve", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": str(home), "TMPDIR": str(home)})


def test_refuses_without_the_program(tmp_path: Path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path, tmp_path)
    assert out.returncode != 0 and out.stdout == ""


def test_refuses_off_the_chip(tmp_path: Path):
    out = _run(ROOT, tmp_path)
    assert out.returncode != 0 and out.stdout == ""
    assert "not a TPU" in out.stderr
