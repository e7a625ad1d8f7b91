"""``chip_smoke.py`` and the compile-cache placement, rehearsed on the CPU.

The rehearsal runs every phase of the chip smoke run end to end at tiny
shapes; only the platform check is skipped.  Each run is a subprocess so
this process's JAX configuration (cache directory, device count) stays
untouched.
"""
import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, env_extra=None, cwd=REPO, script=SMOKE, timeout=600):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run([sys.executable, script, *args], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=timeout)


def _lines(out):
    return [json.loads(x) for x in out.stdout.splitlines()
            if x.startswith("{")]


def _entries(path):
    return set(os.listdir(path)) if os.path.isdir(path) else set()


def test_cpu_rehearsal_runs_every_phase(tmp_path):
    cache = tmp_path / "cache"
    default = os.path.join(REPO, ".jax_cache")
    before = _entries(default)
    out = _run(["--cpu-rehearsal"],
               {"JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert out.returncode == 0, out.stderr[-4000:]
    lines = _lines(out)
    phases = {x["phase"]: x for x in lines if "phase" in x}
    assert list(phases) == ["kernels", "service", "http", "arena"]
    for name, p in phases.items():
        assert p["repeat_compile_s"] < p["compile_s"], name
        assert p["cache_dir"] == str(cache)
    assert phases["service"]["queries"] == 4
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    # the set directory holds the cache, and the checkout's default
    # directory gained nothing
    assert _entries(cache)
    assert _entries(default) == before


def test_sharded_rehearsal_matches_one_device(tmp_path):
    out = _run(["--cpu-rehearsal", "--chips", "4"], {
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert out.returncode == 0, out.stderr[-4000:]
    lines = _lines(out)
    phases = {x["phase"]: x for x in lines if "phase" in x}
    assert list(phases) == ["single", "sharded"]
    assert phases["sharded"]["answers_match"] is True
    assert len(phases["sharded"]["shard_occupancy"]) == 4
    assert lines[-1]["device"]["count"] == 4


def test_refuses_the_cpu_without_rehearsal():
    out = _run([], timeout=300)
    assert out.returncode != 0
    assert "not a TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_fails_without_the_repository(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, alone)
    out = _run(["--cpu-rehearsal"], cwd=tmp_path, script=str(alone),
               timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def _cache_dir_in_child(env_extra):
    code = ("import jax; from repro.compile_cache import use_compile_cache;"
            "print(use_compile_cache());"
            "print(jax.config.jax_compilation_cache_dir)")
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.split()


def test_compile_cache_defaults_to_the_checkout():
    want = os.path.join(REPO, ".jax_cache")
    assert _cache_dir_in_child({}) == [want, want]


def test_compile_cache_follows_the_environment(tmp_path):
    path = str(tmp_path / "elsewhere")
    assert _cache_dir_in_child(
        {"JAX_COMPILATION_CACHE_DIR": path}) == [path, path]
