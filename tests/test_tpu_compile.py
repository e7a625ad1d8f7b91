"""The search kernels compile for a TPU v5e chip that is described, not
attached: the TPU compiler runs here on the CPU and refuses what the chip
would refuse (tiling, VMEM, unsupported casts), which interpret mode never
checks.  Nothing runs.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
The persistent compilation cache is off around the compiles, since an
entry compiled for a described chip cannot be read back without one.

Each kernel carries an explicit ``name``: the compiled custom call is
named after it, whatever Python function wraps the call, so a profiler
trace finds it under that name.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.mcts_step.kernel import (mcts_backup_pallas,
                                            mcts_select_pallas)
from repro.kernels.uct_select.kernel import uct_scores_pallas

# (games, max_nodes, padded actions, lanes, max_depth)
BENCH = (4, 256, 128, 4, 16)       # bench_kernels: 5x5 (A=26 -> 128)
FUEGO9 = (16, 8192, 128, 8, 64)    # configs/fuego9.py: 9x9 (A=82 -> 128)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]


def _compile(fn, args, name: str):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    calls = re.findall(r"%([\w.]+) = [^\n]*? custom-call\(", text)
    assert calls and all(re.fullmatch(rf"{name}(\.\d+)?", c)
                         for c in calls), calls


@pytest.mark.parametrize("blend", [False, True])
@pytest.mark.parametrize("rows", [8, 64])
def test_uct_scores(one_chip, rows, blend):
    f32 = jnp.float32
    tile, col = ((rows, 128), f32), ((rows, 1), f32)
    args = _shapes(one_chip, *[tile] * 6, *[col] * (6 if blend else 4))
    _compile(lambda *a: uct_scores_pallas(*a, use_puct=False), args,
             "uct_scores_blend" if blend else "uct_scores")


@pytest.mark.parametrize("size,blend", [(BENCH, False), (FUEGO9, False),
                                        (FUEGO9, True)],
                         ids=["bench", "fuego9", "fuego9-blend"])
def test_mcts_select(one_chip, size, blend):
    g, n, a, lanes, depth = size
    f32, i32 = jnp.float32, jnp.int32
    vec, slab, knob = ((g, n), f32), ((g, n, a), f32), ((g,), f32)
    args = _shapes(one_chip, vec, vec, vec, slab, slab, ((g, n, a), i32),
                   vec, vec, vec, ((g,), jnp.uint32), knob, knob,
                   *([knob] if blend else []))
    # at 8192 nodes the three resident slabs, double-buffered, exceed
    # v5e's 16 MiB default scoped VMEM: this compiles only because the
    # kernel raises its limit
    _compile(lambda *x: mcts_select_pallas(
        *x, lanes=lanes, max_depth=depth, expand_threshold=1,
        use_puct=False), args, "mcts_select")


@pytest.mark.parametrize("size", [BENCH, FUEGO9], ids=["bench", "fuego9"])
def test_mcts_backup(one_chip, size):
    g, n, _, lanes, depth = size
    f32 = jnp.float32
    args = _shapes(one_chip, ((g, n), f32), ((g, n), f32),
                   ((g, lanes, depth), jnp.int32), ((g, lanes), f32))
    _compile(lambda *x: mcts_backup_pallas(*x, playouts=1.0), args,
             "mcts_backup")
