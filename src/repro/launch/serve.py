"""Serving launcher: batched generation with the ServeEngine.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --reduced \
        --batch 4 --max-new 16
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.config import get_model_config
from repro.configs.reduced import reduce_config
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.models import sharding as shlib
from repro.serving import ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-prompt", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--data-mesh", type=int, default=1)
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_model_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    if cfg.family == "audio":
        raise SystemExit("encoder-only arch has no decode step")
    mesh = make_mesh(args.data_mesh, args.model_mesh)

    with shlib.use_mesh(mesh):
        model = build_model(cfg, mesh=mesh)
        params = model.init(jax.random.PRNGKey(args.seed))
        engine = ServeEngine(model, params, batch=args.batch,
                             max_prompt=args.max_prompt,
                             max_new=args.max_new,
                             temperature=args.temperature)

        rng = np.random.default_rng(args.seed)
        frontend = None
        if cfg.frontend_tokens:
            frontend = jax.numpy.asarray(rng.standard_normal(
                (args.batch, cfg.frontend_tokens, 1024), dtype=np.float32))
        done = 0
        t0 = time.time()
        while done < args.requests:
            n = min(args.batch, args.requests - done)
            prompts = [list(rng.integers(3, cfg.vocab_size,
                                         rng.integers(4, args.max_prompt)))
                       for _ in range(n)]
            outs = engine.generate(prompts, seed=args.seed + done,
                                   frontend=frontend)
            for i, o in enumerate(outs):
                print(f"req {done + i}: prompt {len(prompts[i])} toks -> "
                      f"{len(o)} new: {o[:10]}...")
            done += n
        dt = time.time() - t0
        total_new = args.requests * args.max_new
        print(f"{args.requests} requests, ~{total_new} tokens in {dt:.1f}s "
              f"({total_new / dt:.1f} tok/s)")


if __name__ == "__main__":
    main()
