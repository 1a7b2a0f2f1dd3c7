import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
for the production meshes and extract memory/cost/roofline artifacts.

This proves the distribution config is coherent without hardware:
  * (16,16) ("data","model")          — one v5e-256 pod
  * (2,16,16) ("pod","data","model")  — 2 pods = 512 chips, the "pod" axis
    carrying DANA's async-worker round (DESIGN.md Sec. 2)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b \
      --shape train_4k --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single
Results are appended to --out (JSON) incrementally so long sweeps resume.

(No ``from __future__`` import here: the XLA_FLAGS assignment must be the
very first statements of the module, before any jax-importing import.)
"""
import argparse
import json
import os.path
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs import INPUT_SHAPES, get_config, list_configs
from ..models.api import build_model, cache_spec_for, supports_shape
from ..roofline.analysis import analyze_compiled, analytic_model_flops
from .cache import enable_compile_cache
from .mesh import make_production_mesh
from .sharding import (batch_specs, cache_pspecs, param_pspecs,
                       to_shardings)
from .steps import (TrainSettings, build_decode_step, build_prefill_step,
                    build_train_step, init_train_state)


def _param_counts(cfg):
    import math
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    total = sum(math.prod(l.shape) for l in jax.tree.leaves(shapes))
    active = total
    if cfg.num_experts:
        expert = 0
        def count_experts(path, leaf):
            nonlocal expert
            keys = [k.key for k in path if hasattr(k, "key")]
            if ("moe" in keys and "shared" not in keys
                    and keys[-1] in ("w_gate", "w_up", "w_down")):
                expert += math.prod(leaf.shape)
            return leaf
        jax.tree_util.tree_map_with_path(count_experts, shapes)
        active = total - expert + expert * cfg.experts_per_tok \
            / cfg.num_experts
    return int(total), int(active)


def _bf16_params_struct(model):
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(
            l.shape, jnp.bfloat16 if l.dtype == jnp.float32 else l.dtype),
        shapes)


def run_one(arch: str, shape_name: str, multi_pod: bool,
            settings: TrainSettings | None = None,
            kv_quant: bool = False) -> dict:
    import dataclasses as _dc
    cfg = get_config(arch)
    if kv_quant:
        cfg = _dc.replace(cfg, kv_quant=True)
    shape = INPUT_SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    ok, why = supports_shape(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": why}

    if settings is None:
        # microbatch heuristic (paper Sec. 5.4 gradient accumulation):
        # large models need activation memory relief to fit 16 GB HBM
        total, _ = _param_counts(cfg)
        mb = 4 if total > 5e10 else (2 if total > 1e10 else 1)
        if cfg.num_experts:
            mb = max(mb, 2)     # MoE dispatch buffers are activation-heavy
        settings = TrainSettings(microbatches=mb)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    model = build_model(cfg)
    t0 = time.time()

    recipe = "tp"
    with mesh:
        if shape.kind == "train":
            recipe = settings.recipe
            if recipe == "auto":
                from .sharding import default_recipe
                recipe = default_recipe(cfg, mesh, "train")
            step, state_specs, in_sh, out_sh = build_train_step(
                model, mesh, settings, global_batch=shape.global_batch)
            num_pods = mesh.shape.get("pod", 1)
            state_struct = jax.eval_shape(
                lambda k: init_train_state(model, k, num_pods),
                jax.random.PRNGKey(0))
            m2 = build_model(cfg)
            specs = m2.input_specs(shape)
            batch_struct = specs["batch"]
            b_sh = to_shardings(mesh, batch_specs(cfg, mesh, batch_struct,
                                                  recipe))
            jitted = jax.jit(step, in_shardings=(in_sh[0], b_sh),
                             out_shardings=(out_sh[0], None),
                             donate_argnums=(0,))   # state updates in place
            lowered = jitted.lower(state_struct, batch_struct)
        elif shape.kind == "prefill":
            step = build_prefill_step(model, mesh, shape)
            pspecs = param_pspecs(cfg, jax.eval_shape(
                model.init, jax.random.PRNGKey(0)), mesh, fsdp=False)
            p_sh = to_shardings(mesh, pspecs)
            params_struct = _bf16_params_struct(model)
            specs = model.input_specs(shape)
            batch_struct = specs["batch"]
            b_sh = to_shardings(mesh, batch_specs(cfg, mesh, batch_struct))
            jitted = jax.jit(step, in_shardings=(p_sh, b_sh))
            lowered = jitted.lower(params_struct, batch_struct)
        else:  # decode
            step = build_decode_step(model, mesh, shape)
            pspecs = param_pspecs(cfg, jax.eval_shape(
                model.init, jax.random.PRNGKey(0)), mesh, fsdp=False)
            p_sh = to_shardings(mesh, pspecs)
            params_struct = _bf16_params_struct(model)
            specs = model.input_specs(shape)
            tok_struct, cache_struct = specs["token"], specs["cache"]
            c_sh = to_shardings(mesh, cache_pspecs(cfg, mesh, cache_struct))
            jitted = jax.jit(step, in_shardings=(p_sh, None, c_sh),
                             out_shardings=(None, c_sh))
            lowered = jitted.lower(params_struct, tok_struct, cache_struct)

        compiled = lowered.compile()

    total, active = _param_counts(cfg)
    mf = analytic_model_flops(cfg, shape, total, active)
    rep = analyze_compiled(lowered, compiled, arch=arch, shape=shape_name,
                           mesh_name=mesh_name, chips=chips,
                           model_flops=mf)
    mem = compiled.memory_analysis()
    row = rep.row()
    row.update({
        "status": "ok",
        "recipe": recipe,
        "microbatches": settings.microbatches,
        "compile_s": round(time.time() - t0, 1),
        "params_total": total,
        "params_active": active,
        "memory_analysis": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        },
    })
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--redo", action="store_true",
                    help="recompute combos already in --out")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache for decode shapes")
    args = ap.parse_args()
    enable_compile_cache()

    archs = list_configs() if args.all or not args.arch else [args.arch]
    shapes = list(INPUT_SHAPES) if args.all or not args.shape \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    d = os.path.dirname(args.out)
    if d:
        os.makedirs(d, exist_ok=True)
    try:
        with open(args.out) as f:
            results = json.load(f)
    except (OSError, json.JSONDecodeError):
        results = {}

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                key = f"{arch}|{shape}|{'2x16x16' if mp else '16x16'}"
                if key in results and not args.redo \
                        and results[key].get("status") in ("ok", "skipped"):
                    print(f"[cached] {key}")
                    continue
                print(f"[dryrun] {key} ...", flush=True)
                try:
                    row = run_one(arch, shape, mp, kv_quant=args.kv_quant)
                except Exception as e:  # noqa: BLE001 — record and continue
                    row = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "error", "error": repr(e),
                           "trace": traceback.format_exc()[-2000:]}
                results[key] = row
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1, default=str)
                status = row.get("status")
                extra = (f" dominant={row.get('dominant')}"
                         f" compute={row.get('compute_s', 0):.2e}s"
                         f" mem={row.get('memory_s', 0):.2e}s"
                         f" coll={row.get('collective_s', 0):.2e}s"
                         if status == "ok" else row.get("reason",
                                                        row.get("error", "")))
                print(f"[{status}] {key}{extra}", flush=True)


if __name__ == "__main__":
    main()
