import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the abstract inputs (ShapeDtypeStruct with
shardings — zero allocation), lowers the right step function
(train_step / prefill / serve decode_step), compiles it for the production
mesh, and records:

* ``compiled.memory_analysis()``  — proves the per-device program fits
* ``compiled.cost_analysis()``    — per-device FLOPs / bytes for §Roofline
* collective wire bytes parsed from the post-partitioning HLO
* the three roofline terms + dominant bottleneck + useful-FLOPs fraction

Results accumulate in a JSON cache (``--out``); finished cells are skipped
so the sweep is resumable.  Usage:

    python -m repro.launch.dryrun --all                 # every cell, 1 pod
    python -m repro.launch.dryrun --all --multi-pod     # 2x16x16
    python -m repro.launch.dryrun --arch glm4-9b --shape train_4k
    python -m repro.launch.dryrun --arch fuego9         # the MCTS app cell
"""
import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import numpy as np

from repro.analysis.hlo import analyze
from repro.analysis.roofline import (model_flops, roofline_terms,
                                     useful_fraction)
from repro.compile_cache import use_compile_cache
from repro.config import (SHAPES, TrainConfig, get_model_config, list_archs,
                          skip_reason)
from repro.launch.mesh import make_production_mesh
from repro.models import (batch_specs, build_model, decode_specs,
                          param_specs)
from repro.models import sharding as shlib
from repro.models.transformer import TransformerLM
from repro.optim.optimizers import (AdamState, FactorState, SGDMState,
                                    make_optimizer)
from repro.training.step import TrainState, make_train_step

DEFAULT_OUT = "benchmarks/results/dryrun.json"


# ---------------------------------------------------------------------------
# abstract state construction
# ---------------------------------------------------------------------------


def _with_sharding(leaf: jax.ShapeDtypeStruct, sh):
    return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=sh)


def abstract_train_state(cfg, tcfg: TrainConfig, mesh) -> TrainState:
    """TrainState of ShapeDtypeStructs with shardings (no allocation)."""
    pspecs = param_specs(cfg, mesh)
    opt = make_optimizer(tcfg.optimizer, tcfg.weight_decay)
    opt_abs = jax.eval_shape(opt.init, pspecs)

    model = TransformerLM(cfg)
    logical = model.param_logical()
    shapes = model.param_shapes()
    is_ax = lambda x: isinstance(x, tuple) and all(
        isinstance(v, (str, type(None))) for v in x)

    def like_param(tree_abs):
        return jax.tree.map(
            lambda lg, shp, leaf: _with_sharding(
                leaf, shlib.named_sharding(lg, shp, mesh)),
            logical, shapes, tree_abs, is_leaf=is_ax)

    def factored(tree_abs):
        def one(lg, shp, leaf):
            if leaf.shape == tuple(shp):
                axes = lg
            elif leaf.shape == tuple(shp[:-1]):
                axes = lg[:-1]
            elif leaf.shape == tuple(shp[:-2]) + tuple(shp[-1:]):
                axes = lg[:-2] + lg[-1:]
            else:
                axes = (None,) * len(leaf.shape)
            return _with_sharding(
                leaf, shlib.named_sharding(axes, leaf.shape, mesh))

        return jax.tree.map(one, logical, shapes, tree_abs, is_leaf=is_ax)

    rep = lambda leaf: _with_sharding(
        leaf, shlib.named_sharding((), (), mesh))
    if isinstance(opt_abs, AdamState):
        opt_abs = AdamState(step=rep(opt_abs.step), m=like_param(opt_abs.m),
                            v=like_param(opt_abs.v))
    elif isinstance(opt_abs, FactorState):
        opt_abs = FactorState(step=rep(opt_abs.step),
                              vr=factored(opt_abs.vr),
                              vc=factored(opt_abs.vc))
    elif isinstance(opt_abs, SGDMState):
        opt_abs = SGDMState(step=rep(opt_abs.step),
                            mom=like_param(opt_abs.mom))
    return TrainState(params=pspecs, opt_state=opt_abs,
                      step=jax.ShapeDtypeStruct((), np.int32), psgd=None)


def _train_tcfg(cfg, shape, mesh_cfg_chips_data: int) -> TrainConfig:
    # one row per device per microbatch: peak activations ~ one sequence
    mb = max(1, shape.global_batch // mesh_cfg_chips_data)
    opt = "adafactor" if cfg.moe.num_experts else "adamw"
    return TrainConfig(microbatches=mb, optimizer=opt, remat=True)


# ---------------------------------------------------------------------------
# per-cell lowering
# ---------------------------------------------------------------------------


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               save_hlo: Optional[str] = None) -> Dict[str, Any]:
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    t0 = time.time()

    if arch == "fuego9":
        lowered, donate = _lower_fuego(mesh), None
        cfg = None
        shape = None
    else:
        cfg = get_model_config(arch)
        shape = SHAPES[shape_name]
        with shlib.use_mesh(mesh):
            model = build_model(cfg, mesh=mesh)
            if shape.kind == "train":
                data_ways = mesh.shape["data"] * mesh.shape.get("pod", 1)
                tcfg = _train_tcfg(cfg, shape, data_ways)
                state_abs = abstract_train_state(cfg, tcfg, mesh)
                batch_abs = batch_specs(cfg, shape, mesh)
                step = make_train_step(model, tcfg, mesh=mesh)
                lowered = jax.jit(step, donate_argnums=(0,)).lower(
                    state_abs, batch_abs)
            elif shape.kind == "prefill":
                pspecs = param_specs(cfg, mesh)
                batch_abs = batch_specs(cfg, shape, mesh)
                if cfg.family == "audio":
                    fn = lambda p, fe: model.forward(p, None, fe)
                    lowered = jax.jit(fn).lower(pspecs, batch_abs["frontend"])
                else:
                    args = [pspecs, batch_abs["tokens"]]
                    fn = (lambda p, t, fe: model.prefill(p, t, fe)) \
                        if cfg.frontend_tokens else \
                        (lambda p, t: model.prefill(p, t))
                    if cfg.frontend_tokens:
                        args.append(batch_abs["frontend"])
                    lowered = jax.jit(fn).lower(*args)
            else:  # decode
                pspecs = param_specs(cfg, mesh)
                cache_abs, tok_abs = decode_specs(cfg, shape, mesh)
                fn = lambda p, c, t: model.decode_step(p, c, t)
                lowered = jax.jit(fn, donate_argnums=(1,)).lower(
                    pspecs, cache_abs, tok_abs)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    hc = analyze(hlo)          # trip-count-aware per-device flops/bytes/wire
    coll = dict(hc["wire"])
    coll["counts"] = hc["wire_counts"]
    if save_hlo:
        os.makedirs(save_hlo, exist_ok=True)
        tag = f"{arch}_{shape_name}_{'pod2' if multi_pod else 'pod1'}"
        with open(os.path.join(save_hlo, tag + ".hlo.txt"), "w") as f:
            f.write(hlo)

    terms = roofline_terms(hc, coll, chips)
    from repro.models import optflags as _of
    rec = {
        "status": "ok",
        "opt_flags": {k: v for k, v in _of.flags().__dict__.items() if v},
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "cost": {"flops": hc["flops"], "hbm_bytes": hc["hbm_bytes"],
                 "xla_flops_bodies_once": ca.get("flops"),
                 "xla_bytes_bodies_once": ca.get("bytes accessed")},
        "memory": {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
            "output_bytes": getattr(ma, "output_size_in_bytes", None),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            "alias_bytes": getattr(ma, "alias_size_in_bytes", None),
            "code_bytes": getattr(ma, "generated_code_size_in_bytes", None),
        },
        "collectives": {k: v for k, v in coll.items() if k != "counts"},
        "collective_counts": coll.get("counts", {}),
        "roofline": terms,
    }
    if cfg is not None and shape is not None:
        rec["model_flops"] = model_flops(cfg, shape)
        rec["useful_fraction"] = useful_fraction(
            cfg, shape, {"flops": hc["flops"]}, chips)
        # per-device live bytes: params(+opt) args + temps
        arg = rec["memory"]["argument_bytes"] or 0
        tmp = rec["memory"]["temp_bytes"] or 0
        rec["memory"]["per_device_total_gib"] = round(
            (arg + tmp) / 2 ** 30, 3)
        rec["fits_16g_hbm"] = bool(arg + tmp < 16 * 2 ** 30)
    return rec


def _lower_fuego(mesh):
    from repro.configs.fuego9 import config as fuego_config
    from repro.core.distributed import selfplay_step
    from repro.go import GoEngine

    mcfg = fuego_config()
    eng = GoEngine(mcfg.board_size, mcfg.komi)
    step = selfplay_step(eng, mcfg, mesh, axis="data")
    root = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jax.numpy.shape(x), x.dtype),
        eng.init_state())
    rng = jax.ShapeDtypeStruct((2,), np.uint32)
    return jax.jit(step).lower(root, rng)


# ---------------------------------------------------------------------------
# sweep driver
# ---------------------------------------------------------------------------


def cell_key(arch: str, shape: str, multi_pod: bool) -> str:
    return f"{arch}|{shape}|{'2x16x16' if multi_pod else '16x16'}"


def load_results(path: str) -> Dict[str, Any]:
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return {}


def save_results(path: str, results: Dict[str, Any]) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(results, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def run_cells(cells, out: str, save_hlo: Optional[str], force: bool,
              verbose: bool = True) -> Dict[str, Any]:
    results = load_results(out)
    for arch, shape_name, multi_pod in cells:
        key = cell_key(arch, shape_name, multi_pod)
        if not force and results.get(key, {}).get("status") == "ok":
            if verbose:
                print(f"[skip cached] {key}")
            continue
        reason = skip_reason(arch, shape_name) if arch != "fuego9" else None
        if reason:
            results[key] = {"status": "skipped", "arch": arch,
                            "shape": shape_name, "reason": reason}
            save_results(out, results)
            if verbose:
                print(f"[skip] {key}: {reason}")
            continue
        if verbose:
            print(f"[lower+compile] {key} ...", flush=True)
        try:
            rec = lower_cell(arch, shape_name, multi_pod, save_hlo)
            results[key] = rec
            if verbose:
                r = rec["roofline"]
                print(f"  ok: compile {rec['compile_s']}s  "
                      f"compute {r['compute_s']:.4f}s  "
                      f"memory {r['memory_s']:.4f}s  "
                      f"collective {r['collective_s']:.4f}s  "
                      f"dominant={r['dominant']}", flush=True)
        except Exception as e:
            results[key] = {"status": "error", "arch": arch,
                            "shape": shape_name,
                            "error": f"{type(e).__name__}: {e}",
                            "trace": traceback.format_exc()[-2000:]}
            print(f"  ERROR {key}: {type(e).__name__}: {e}", flush=True)
        save_results(out, results)
    return results


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None,
                    help="architecture id (default: all)")
    ap.add_argument("--shape", default=None,
                    help="shape name (default: all four)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="all archs x shapes (+ fuego9)")
    ap.add_argument("--out", default=None,
                    help="results JSON (default depends on --opt)")
    ap.add_argument("--opt", type=int, default=0,
                    help="optimization level 0..3 (models.optflags)")
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    from repro.models import optflags
    optflags.set_level(args.opt)
    if args.out is None:
        args.out = DEFAULT_OUT if args.opt == 0 else \
            DEFAULT_OUT.replace(".json", f"_opt{args.opt}.json")

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    cells = []
    if args.all or args.arch is None:
        archs = list_archs()
        for mp in meshes:
            for a in archs:
                for s in SHAPES:
                    cells.append((a, s, mp))
            cells.append(("fuego9", "selfplay", mp))
    else:
        shapes = [args.shape] if args.shape else \
            (["selfplay"] if args.arch == "fuego9" else list(SHAPES))
        for mp in meshes:
            for s in shapes:
                cells.append((args.arch, s, mp))

    results = run_cells(cells, args.out, args.save_hlo, args.force)
    ok = sum(1 for v in results.values() if v.get("status") == "ok")
    sk = sum(1 for v in results.values() if v.get("status") == "skipped")
    err = sum(1 for v in results.values() if v.get("status") == "error")
    print(f"\n== dry-run summary: {ok} ok / {sk} skipped / {err} error ==")
    if err:
        for k, v in results.items():
            if v.get("status") == "error":
                print(f"  FAIL {k}: {v['error']}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
