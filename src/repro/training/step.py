"""Distributed training step: microbatch accumulation + optimizer + FT hooks.

``train_step`` is one jitted function of ``(state, batch) -> (state,
metrics)``:

* The global batch splits into ``microbatches`` chunks scanned sequentially
  — each chunk's fwd+bwd is rematerialised, so peak activation memory is
  one microbatch while the gradient accumulator (same sharding as params)
  carries the sum.  The scan also gives XLA a window to overlap each
  chunk's gradient reduce-scatter with the next chunk's compute (the
  latency-hiding scheduler does this when
  ``--xla_tpu_enable_latency_hiding_scheduler`` is on — launch/mesh.py).
* Gradient clipping by global norm, then the optimizer (optim/).
* Optional PowerSGD compression of the *cross-pod* gradient mean
  (parallel/compress.py) under a partial-auto shard_map over the ``pod``
  axis: inside the body gradients are averaged over data/model by XLA as
  usual, while the pod-axis exchange moves only rank-r factors.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.config import TrainConfig
from repro.optim import (clip_by_global_norm, make_optimizer, make_schedule)
from repro.parallel.compress import (PowerSGDState, compressed_cross_pod_mean,
                                     init_powersgd)


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jax.Array
    psgd: Optional[PowerSGDState]


def init_train_state(model, tcfg: TrainConfig, key) -> TrainState:
    params = model.init(key)
    opt = make_optimizer(tcfg.optimizer, tcfg.weight_decay)
    opt_state = opt.init(params)
    psgd = None
    if tcfg.compress_pod_grads:
        grads_like = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        psgd = init_powersgd(grads_like, rank=tcfg.powersgd_rank)
    return TrainState(params=params, opt_state=opt_state,
                      step=jnp.int32(0), psgd=psgd)


def _split_microbatches(batch: Dict[str, jax.Array], n: int):
    def split(x):
        b = x.shape[0]
        assert b % n == 0, f"global batch {b} not divisible by {n} chunks"
        return x.reshape(n, b // n, *x.shape[1:])

    return jax.tree.map(split, batch)


def make_train_step(model, tcfg: TrainConfig, total_steps: Optional[int]
                    = None, mesh: Optional[Mesh] = None):
    """Build the jittable step.  ``model`` must expose ``loss(params,
    batch, z_loss)``."""
    opt = make_optimizer(tcfg.optimizer, tcfg.weight_decay)
    sched = make_schedule(tcfg.schedule, tcfg.lr, tcfg.warmup_steps,
                          total_steps or tcfg.steps)
    n_mb = max(1, tcfg.microbatches)
    use_pod_compress = (tcfg.compress_pod_grads and mesh is not None
                        and "pod" in mesh.axis_names
                        and mesh.shape["pod"] > 1)

    def loss_fn(params, mb):
        loss, metrics = model.loss(params, mb, z_loss=tcfg.z_loss)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def accumulate(params, batch):
        mbs = _split_microbatches(batch, n_mb)

        def body(carry, mb):
            gsum, lsum = carry
            (l, metrics), g = grad_fn(params, mb)
            gsum = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32), gsum, g)
            return (gsum, lsum + l), metrics["ce"]

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (gsum, lsum), ces = jax.lax.scan(body, (g0, jnp.float32(0.0)), mbs)
        grads = jax.tree.map(lambda g: g / n_mb, gsum)
        return grads, lsum / n_mb, ces.mean()

    def train_step(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        new_psgd = state.psgd
        if use_pod_compress:
            # gradients stay per-pod until the compressed exchange: the
            # whole accumulate runs under a pod-manual shard_map (data and
            # model stay auto => XLA shards them as usual inside), so the
            # only cross-pod traffic is the rank-r factors.
            def per_pod(params, batch_pod, psgd):
                from repro.models import sharding as shlib
                with shlib.manual_axes({"pod"}):
                    grads, loss, ce = accumulate(params, batch_pod)
                grads, psgd = compressed_cross_pod_mean(grads, psgd,
                                                        axis="pod")
                loss = jax.lax.pmean(loss, "pod")
                ce = jax.lax.pmean(ce, "pod")
                return grads, loss, ce, psgd

            # manual over pod only; data/model stay auto
            grads, loss, ce, new_psgd = shard_map(
                per_pod, mesh=mesh, in_specs=(P(), P("pod"), P()),
                out_specs=(P(), P(), P(), P()), check_vma=False,
                axis_names={"pod"})(state.params, batch, state.psgd)
        else:
            grads, loss, ce = accumulate(state.params, batch)

        grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
        lr = sched(state.step)
        params, opt_state = opt.update(grads, state.opt_state, state.params,
                                       lr)
        new_state = TrainState(params=params, opt_state=opt_state,
                               step=state.step + 1, psgd=new_psgd)
        return new_state, {"loss": loss, "ce": ce, "grad_norm": gnorm,
                           "lr": lr}

    return train_step
