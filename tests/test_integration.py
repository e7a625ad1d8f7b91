"""End-to-end integration: elastic rescaling, launcher CLIs, the paper's
tournament setting, and the dry-run results contract."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # end-to-end subprocess runs: full tier only

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(script: str, devices: int = 8, timeout: int = 480, env_extra=None):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.update(env_extra or {})
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


class TestElasticRescale:
    def test_train_save_then_resume_on_smaller_mesh(self, tmp_path):
        """Train on a (4, 2) mesh, checkpoint, lose half the fleet, resume
        on (2, 2) with resharded state — loss continues from where it was
        (same data stream by step index)."""
        run_sub(f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.config import TrainConfig
from repro.configs.reduced import reduced
from repro.models import build_model
from repro.models import sharding as shlib
from repro.training import init_train_state, make_train_step
from repro.training.step import TrainState
from repro.ckpt import save_checkpoint, restore_checkpoint
from repro.data import SyntheticLM
from repro.runtime import elastic_mesh_for

cfg = reduced("yi-6b")
model = build_model(cfg)
tcfg = TrainConfig(steps=6, microbatches=1, lr=1e-3, warmup_steps=1)
data = SyntheticLM(cfg, 16, 8, seed=5)
tb = lambda s: {{k: jnp.asarray(v) for k, v in data.batch_at(s).items()}}

# phase 1: 8 devices, (4 data, 2 model)
mesh_a = jax.make_mesh((4, 2), ("data", "model"))
with shlib.use_mesh(mesh_a):
    state = init_train_state(model, tcfg, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(model, tcfg, mesh=mesh_a))
    for s in range(3):
        state, m = step(state, tb(s))
losses_a = float(m["loss"])
save_checkpoint("{tmp_path}", 3, state._asdict(), extra={{"data_step": 3}})

# phase 2: "4 devices survive" -> elastic (2, 2) mesh, resharded restore
data_ax, model_ax = elastic_mesh_for(4, 2)
assert (data_ax, model_ax) == (2, 2)
mesh_b = jax.make_mesh((2, 2), ("data", "model"))
devs = np.array(jax.devices()[:4]).reshape(2, 2)
with shlib.use_mesh(mesh_b):
    template = init_train_state(model, tcfg, jax.random.PRNGKey(0))
    from repro.models import param_shardings
    restored, step_idx, extra = restore_checkpoint(
        "{tmp_path}", template._asdict())
    state_b = TrainState(**restored)
    step_b = jax.jit(make_train_step(model, tcfg, mesh=mesh_b))
    for s in range(extra["data_step"], 6):
        state_b, mb = step_b(state_b, tb(s))
print("resumed loss", float(mb["loss"]))
assert np.isfinite(float(mb["loss"]))
assert int(state_b.step) == 6
print("OK elastic rescale")
""")


class TestPaperSetting:
    def test_tournament_config_runs(self):
        """The paper's exact setting: 9x9, komi 6, Chinese (area) scoring,
        alternating colours — one tiny match end to end."""
        from repro.config import MCTSConfig
        from repro.core.selfplay import effective_speedup_point
        from repro.go import GoEngine
        eng = GoEngine(9, komi=6.0)
        cfg = MCTSConfig(board_size=9, komi=6.0, lanes=2, sims_per_move=8,
                         max_nodes=128)
        res = effective_speedup_point(eng, cfg, games=2, seed=0,
                                      max_moves=24)
        assert res.a_wins + res.b_wins + res.draws == 2

    def test_19x19_engine(self):
        """The paper also ran 19x19; the engine is size-parametric."""
        from repro.go import GoEngine, BLACK
        eng = GoEngine(19, komi=7.5)
        st = eng.init_state()
        st = eng.play(st, 3 * 19 + 3)       # corner-ish opening
        assert int(st.board[3 * 19 + 3]) == BLACK
        legal = eng.legal_moves(st)
        assert int(np.asarray(legal).sum()) == 19 * 19 - 1 + 1  # + pass
        v = eng.playout_value(st, jax.random.PRNGKey(0))
        assert int(v) in (-1, 0, 1)


class TestLauncherCLIs:
    def test_train_cli_with_resume(self, tmp_path):
        env = {"CKPT": str(tmp_path)}
        script = f"""
import sys
sys.argv = ["train", "--arch", "yi-6b", "--reduced", "--steps", "4",
            "--batch", "2", "--seq", "32", "--ckpt-dir", "{tmp_path}",
            "--ckpt-every", "2", "--log-every", "2"]
from repro.launch.train import main
main()
# resume from the checkpoint
sys.argv += ["--resume"]
sys.argv[sys.argv.index("--steps") + 1] = "6"
main()
print("OK train cli resume")
"""
        out = run_sub(script, devices=1, timeout=600)
        assert "OK train cli resume" in out
        assert "[resume] restored step 4" in out

    def test_selfplay_cli(self, tmp_path):
        # a cache directory of its own: the checkout's default one is
        # what tests/test_chip_smoke.py watches for stray writes
        out = run_sub("""
import sys
sys.argv = ["selfplay", "--board", "5", "--lanes", "1", "--sims", "8",
            "--games", "2", "--max-nodes", "64"]
from repro.launch.selfplay import main
main()
""", devices=1, timeout=600,
                      env_extra={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
        assert "win rate" in out


class TestDryrunContract:
    """The recorded dry-run must satisfy the deliverable's contract."""

    @pytest.fixture(scope="class")
    def results(self):
        path = os.path.join(REPO, "benchmarks", "results", "dryrun.json")
        if not os.path.exists(path):
            pytest.skip("dry-run cache not present")
        with open(path) as f:
            return json.load(f)

    def test_no_errors_and_full_coverage(self, results):
        from repro.config import SHAPES, list_archs, skip_reason
        errors = [k for k, v in results.items() if v.get("status") not in
                  ("ok", "skipped")]
        assert not errors, errors
        for mesh in ("16x16", "2x16x16"):
            for arch in list_archs():
                for shape in SHAPES:
                    key = f"{arch}|{shape}|{mesh}"
                    assert key in results, f"missing cell {key}"
                    want_skip = skip_reason(arch, shape) is not None
                    got = results[key]["status"]
                    assert got == ("skipped" if want_skip else "ok"), \
                        (key, got)
            assert results[f"fuego9|selfplay|{mesh}"]["status"] == "ok"

    def test_roofline_terms_present_and_positive(self, results):
        for k, v in results.items():
            if v.get("status") != "ok":
                continue
            r = v["roofline"]
            assert r["memory_s"] >= 0 and r["collective_s"] >= 0
            assert r["dominant"] in ("compute_s", "memory_s",
                                     "collective_s")
            assert v["memory"]["argument_bytes"] is not None

    def test_multi_pod_not_worse_per_device(self, results):
        """Pure-DP pod axis: per-device compute/memory terms must not grow
        going 256 -> 512 chips (beyond small partitioning noise) for dense
        train cells."""
        for arch in ("yi-6b", "glm4-9b", "gemma2-9b"):
            a = results[f"{arch}|train_4k|16x16"]["roofline"]
            b = results[f"{arch}|train_4k|2x16x16"]["roofline"]
            assert b["memory_s"] <= a["memory_s"] * 1.05
            assert b["compute_s"] <= a["compute_s"] * 1.05
