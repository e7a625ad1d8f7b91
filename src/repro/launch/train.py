"""Training launcher: the end-to-end driver (deliverable (b)).

Composes every substrate: config registry (--arch), mesh, sharded train
step with microbatch accumulation, deterministic resumable data pipeline,
async checkpointing, preemption handling, heartbeats and straggler
monitoring.  On this CPU container it trains reduced configs (see
``--reduced``); on a pod the same driver runs the full configs.

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --reduced \
        --steps 50 --batch 8 --seq 128 --data-mesh 1 --model-mesh 1
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.ckpt import AsyncCheckpointer, latest_step, restore_checkpoint
from repro.compile_cache import use_compile_cache
from repro.config import TrainConfig, get_model_config
from repro.configs.reduced import reduce_config
from repro.data import Prefetcher, SyntheticLM
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.models import sharding as shlib
from repro.runtime import Heartbeat, PreemptionHandler
from repro.training import init_train_state, make_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU container)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--data-mesh", type=int, default=1)
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compress-pod-grads", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_model_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    mesh = make_mesh(args.data_mesh, args.model_mesh, args.pods)
    tcfg = TrainConfig(
        steps=args.steps, microbatches=args.microbatches, lr=args.lr,
        warmup_steps=args.warmup, optimizer=args.optimizer,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        compress_pod_grads=args.compress_pod_grads, seed=args.seed)

    model = build_model(cfg, mesh=mesh)
    seq = args.seq + cfg.frontend_tokens
    data = SyntheticLM(cfg, seq, args.batch, seed=args.seed)

    handler = PreemptionHandler()
    heartbeat = Heartbeat(args.ckpt_dir, jax.process_index())
    ckpt = AsyncCheckpointer(args.ckpt_dir)

    with shlib.use_mesh(mesh):
        state = init_train_state(model, tcfg, jax.random.PRNGKey(args.seed))
        start = 0
        if args.resume and latest_step(args.ckpt_dir) is not None:
            restored, start, extra = restore_checkpoint(
                args.ckpt_dir, state._asdict())
            from repro.training.step import TrainState
            state = TrainState(**restored)
            print(f"[resume] restored step {start}")
        step_fn = jax.jit(make_train_step(model, tcfg, total_steps=args.steps,
                                          mesh=mesh), donate_argnums=(0,))

        prefetch = Prefetcher(
            lambda s: {k: jnp.asarray(v) for k, v in
                       data.batch_at(s).items()}, start_step=start)
        t_last = time.time()
        try:
            for i in range(start, args.steps):
                step_idx, batch = next(prefetch)
                state, metrics = step_fn(state, batch)
                if (i + 1) % args.log_every == 0 or i == start:
                    loss = float(metrics["loss"])
                    dt = time.time() - t_last
                    t_last = time.time()
                    heartbeat.beat(i + 1, dt / args.log_every)
                    print(f"step {i + 1:6d}  loss {loss:8.4f}  "
                          f"gnorm {float(metrics['grad_norm']):7.3f}  "
                          f"lr {float(metrics['lr']):.2e}  "
                          f"{dt:6.2f}s/{args.log_every}", flush=True)
                if (i + 1) % args.ckpt_every == 0 or handler.should_stop:
                    ckpt.save(i + 1, state._asdict(),
                              extra={"data_step": i + 1})
                if handler.should_stop:
                    print("[preempt] checkpointed, exiting cleanly")
                    break
        finally:
            prefetch.close()
            ckpt.wait()
    print("done")


if __name__ == "__main__":
    main()
