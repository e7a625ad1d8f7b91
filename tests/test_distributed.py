"""Multi-device tests (8 fake CPU devices via subprocess so the main test
process keeps its single-device view): sharded root-parallel MCTS, pipeline
parallelism numerics, PowerSGD cross-pod step, seq-sharded decode attention,
sharded train_step."""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # 8-fake-device subprocess compiles

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(script: str, devices: int = 8, timeout: int = 480):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices} "
                        + env.get("XLA_FLAGS", "")).strip()
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


class TestDistributedMCTS:
    def test_root_parallel_shard_map(self):
        run_sub("""
import jax, jax.numpy as jnp
from repro.config import MCTSConfig
from repro.core.distributed import distributed_best_move
from repro.go import GoEngine

assert jax.device_count() == 8
mesh = jax.make_mesh((4, 2), ("data", "model"))
eng = GoEngine(5, komi=0.5)
cfg = MCTSConfig(board_size=5, lanes=2, sims_per_move=32, max_nodes=64,
                 root_trees=4)
fn = distributed_best_move(eng, cfg, mesh, axis="data")
move = fn(eng.init_state(), jax.random.PRNGKey(0))
legal = eng.legal_moves(eng.init_state())
assert bool(legal[int(move)]), int(move)
print("OK", int(move))
""")

    def test_affinity_policies_change_device_busy_set(self):
        run_sub("""
import numpy as np
from repro.core import affinity
# 8 lanes on 8 devices: compact uses 2 devices, scatter uses all 8
c = affinity.lane_to_device("compact", 8, 8, slots_per_device=4)
s = affinity.lane_to_device("scatter", 8, 8)
assert affinity.utilisation(c, 8) == 0.25
assert affinity.utilisation(s, 8) == 1.0
print("OK")
""")


class TestPipelineParallel:
    def test_gpipe_matches_sequential(self):
        run_sub("""
import jax, jax.numpy as jnp, numpy as np
from repro.parallel.pipeline import pipeline_forward

mesh = jax.make_mesh((4,), ("pod",))
S, M, MB, D = 4, 8, 2, 16   # stages, microbatches, microbatch size, width
key = jax.random.PRNGKey(0)
w = jax.random.normal(key, (S, D, D)) * 0.3

def layer_fn(p, x):
    return jnp.tanh(x @ p["w"])

fn = pipeline_forward(layer_fn, mesh, axis="pod")
xs = jax.random.normal(jax.random.PRNGKey(1), (M, MB, D))
got = fn({"w": w}, xs)

# sequential reference
ref = xs
for s in range(S):
    ref = jnp.tanh(ref @ w[s])
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5,
                           atol=2e-5)
print("OK pipeline matches sequential")
""")


class TestCompressedPodStep:
    def test_powersgd_cross_pod_mean(self):
        run_sub("""
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.parallel.compress import (init_powersgd,
                                     compressed_cross_pod_mean)

mesh = jax.make_mesh((2, 4), ("pod", "data"))
# per-pod gradients: low-rank + small per-pod noise
base = jnp.outer(jnp.arange(16.0), jnp.ones(16))
g_pods = jnp.stack([base * (1.0 + 0.1 * i) for i in range(2)])
state = init_powersgd({"w": base}, rank=4)

def f(gp, q, e):
    g = {"w": gp[0]}
    st = type(state)(q={"w": q[0]}, error={"w": e[0]})
    mean, st2 = compressed_cross_pod_mean(g, st, axis="pod")
    return mean["w"][None], st2.q["w"][None], st2.error["w"][None]

fn = shard_map(f, mesh=mesh,
               in_specs=(P("pod"), P("pod"), P("pod")),
               out_specs=(P("pod"), P("pod"), P("pod")),
               check_vma=False)
qs = jnp.stack([state.q["w"]] * 2)
es = jnp.stack([state.error["w"]] * 2)
mean, q2, e2 = fn(g_pods, qs, es)
want = np.asarray(g_pods.mean(0))
# rank-4 exactly captures the rank-1 mean
np.testing.assert_allclose(np.asarray(mean[0]), want, rtol=1e-3, atol=1e-3)
# error feedback holds the (tiny) residual
assert float(jnp.abs(e2).max()) < 1.0
print("OK compressed mean")
""")

    def test_train_step_with_pod_compression_lowers(self):
        run_sub("""
import dataclasses, jax, jax.numpy as jnp
from repro.config import TrainConfig
from repro.configs.reduced import reduced
from repro.models import build_model
from repro.models import sharding as shlib
from repro.training import init_train_state, make_train_step

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = reduced("yi-6b")
model = build_model(cfg)
tcfg = TrainConfig(steps=4, microbatches=1, lr=1e-3, warmup_steps=1,
                   compress_pod_grads=True, powersgd_rank=4)
with shlib.use_mesh(mesh):
    state = init_train_state(model, tcfg, jax.random.PRNGKey(0))
    step = make_train_step(model, tcfg, mesh=mesh)
    batch = {"tokens": jnp.zeros((8, 16), jnp.int32),
             "labels": jnp.zeros((8, 16), jnp.int32)}
    state2, metrics = jax.jit(step)(state, batch)
print("OK loss", float(metrics["loss"]))
""")


class TestSeqShardedDecode:
    def test_lse_combine_matches_reference(self):
        run_sub("""
import jax, jax.numpy as jnp, numpy as np
from repro.models.attention import (KVCache, decode_attention,
                                    decode_attention_seq_sharded)

mesh = jax.make_mesh((2, 4), ("data", "model"))
B, HQ, HKV, S, D = 4, 8, 2, 64, 32
key = jax.random.PRNGKey(0)
kq, kk, kv = jax.random.split(key, 3)
q = jax.random.normal(kq, (B, HQ, 1, D))
cache = KVCache(k=jax.random.normal(kk, (B, HKV, S, D)),
                v=jax.random.normal(kv, (B, HKV, S, D)),
                length=jnp.int32(49))
ref = decode_attention(q, cache)
got = jax.jit(lambda q, c: decode_attention_seq_sharded(q, c, mesh))(q, cache)
np.testing.assert_allclose(np.asarray(got, np.float32),
                           np.asarray(ref, np.float32), rtol=2e-5, atol=2e-5)
print("OK seq-sharded decode")
""")


class TestShardedTrainStep:
    def test_dense_train_step_on_mesh(self):
        run_sub("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.config import TrainConfig
from repro.configs.reduced import reduced
from repro.models import build_model, param_shardings
from repro.models import sharding as shlib
from repro.training import init_train_state, make_train_step

mesh = jax.make_mesh((4, 2), ("data", "model"))
cfg = reduced("glm4-9b")
model = build_model(cfg)
tcfg = TrainConfig(steps=2, microbatches=2, lr=1e-3, warmup_steps=1)
with shlib.use_mesh(mesh):
    state = init_train_state(model, tcfg, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(model, tcfg, mesh=mesh))
    batch = {"tokens": jnp.zeros((8, 32), jnp.int32),
             "labels": jnp.zeros((8, 32), jnp.int32)}
    batch = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
    state, m = step(state, batch)
    state, m = step(state, batch)
import numpy as np
assert np.isfinite(float(m["loss"]))
print("OK sharded step, loss", float(m["loss"]))
""")
