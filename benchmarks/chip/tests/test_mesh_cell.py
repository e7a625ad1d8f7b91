"""The four-chip cell ``serve9_mesh4.serve``'s comparison that decides
``correct``, end to end at its rehearsal sizes on four CPU devices.

One subprocess (the device count is fixed when JAX starts) runs the
cell's ``--cpu-rehearsal`` four times:

* the sound program, which is correct;
* the control (the reference search with its playouts cut short, in the
  program's place), which is not;
* a planted stuck shard, which is not: after set-up, shard 1's step
  returns its pool unchanged, so the queries placed there are never
  admitted and their answers never land;
* a planted wrong-answer shard, which is not: from set-up on, shard 1's
  queued queries search with a shifted key, so its answers are legal,
  within budget and most visited, but not the searches asked for; only
  the comparison with the reference sees them (every answer compared).
"""
import json
import os
import subprocess
import sys

import pytest

from benchmarks.chip.harness import HERE

ROOT = HERE.parents[1]
CHILD = """if True:
    import jax
    import jax.numpy as jnp
    from jax import lax

    import benchmarks.chip.drivers.goservice as goservice
    from benchmarks.chip.drivers import goservice_mesh
    from benchmarks.chip import harness
    from benchmarks.chip.harness import main
    from repro.core.service import SearchService

    goservice.DRAIN_S, goservice.WARM_S = 2.0, 60.0
    argv = ["--workload", "serve9_mesh4.serve", "--seed",
            str(2 ** 31 + 29), "--seconds", "1", "--trace", "0",
            "--cpu-rehearsal"]
    main(argv)
    main(argv + ["--control"])

    step, setup = SearchService._dispatch_impl, goservice_mesh.Driver.setup

    def stuck_shard(self, work, ring, steps):
        new_work, new_ring = step(self, work, ring, steps)
        stuck = lax.axis_index(self._axis) == 1
        keep = lambda new, old: jnp.where(stuck, old, new)
        return (jax.tree.map(keep, new_work, work),
                jax.tree.map(keep, new_ring, ring))

    def wrong_shard(self, work, ring, steps):
        wrong = lax.axis_index(self._axis) == 1
        keys = jnp.where(wrong, work.serve.keys + 1, work.serve.keys)
        return step(self, work._replace(serve=work.serve._replace(keys=keys)),
                    ring, steps)

    def faulty_setup(self, seconds):
        setup(self, seconds)
        SearchService._dispatch_impl = stuck_shard
        inner = self.svc._buckets[self.svc.default_komi]
        inner._dispatch_mesh = jax.jit(inner._dispatch_mesh_impl,
                                       donate_argnums=(0,),
                                       static_argnums=(2,))
        inner.dispatch()              # compiled before the window

    goservice_mesh.Driver.setup = faulty_setup
    main(argv)

    goservice_mesh.Driver.setup = setup
    cell = harness.cell

    def every_answer(*args):
        work, cfg, traffic = cell(*args)
        return work, dict(cfg, rehearsal=dict(cfg["rehearsal"],
                                              ref_samples=10 ** 6)), traffic

    harness.cell = every_answer
    SearchService._dispatch_impl = wrong_shard   # from set-up on
    main(argv)
"""


@pytest.fixture(scope="module")
def lines():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT), str(ROOT / "src")])
    out = subprocess.run([sys.executable, "-c", CHILD], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    got = [json.loads(x) for x in out.stdout.splitlines()
           if x.startswith('{"correct"')]
    assert len(got) == 4, out.stdout[-4000:]
    for line in got:
        assert line["rehearsal"] and line["device"]["count"] == 4
    return dict(zip(["sound", "control", "shard_fault", "wrong_shard"],
                    got))


def test_sound_program_is_correct(lines):
    line = lines["sound"]
    assert line["correct"], line["checks"]
    assert line["checks"]["ref_mismatch_share"]["value"] == 0.0


def test_control_fails(lines):
    line = lines["control"]
    assert line["control"] and not line["correct"], line["checks"]


def test_shard_fault_fails(lines):
    line = lines["shard_fault"]
    assert not line["correct"], line["checks"]
    assert line["checks"]["unanswered"]["value"] > 0


def test_wrong_answer_shard_fails(lines):
    """Shard 1's answers pass every check but the reference's: about a
    quarter of the answers, each wrong, read well above the limit."""
    line = lines["wrong_shard"]
    checks = {k: v["value"] for k, v in line["checks"].items()}
    assert not line["correct"], checks
    for k in ("unanswered", "illegal_moves", "visit_budget_errors",
              "not_most_visited"):
        assert checks[k] == 0, checks
    limit = line["checks"]["ref_mismatch_share"]["limit"]
    assert 0.15 <= checks["ref_mismatch_share"] <= 0.35, checks
    assert checks["ref_mismatch_share"] > limit


def test_readers_read_nothing_from_an_older_program():
    """A program without ``read_rows_s`` (the parent of the cell) leaves
    its reader silent; the others read the window's counters."""
    from benchmarks.chip.harness import read_metric
    window = {"host_busy_s": 1.5, "supersteps": 10,
              "shard_occupancy": [0.5, 0.25, 0.5, 0.5]}
    ctx = {"window": window}
    assert read_metric(HERE, "dispatch.read_rows_ms_per_dispatch."
                       "serve_mesh4", ctx) is None
    assert read_metric(HERE, "sched.host_ms_per_dispatch.serve_mesh4",
                       ctx) == 150.0
    assert read_metric(HERE, "mesh.shard_occupancy_min.serve_mesh4",
                       ctx) == 0.25
    window["read_rows_s"] = 0.5
    assert read_metric(HERE, "dispatch.read_rows_ms_per_dispatch."
                       "serve_mesh4", ctx) == 50.0
