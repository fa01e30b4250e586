import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. resolves the runtime distribution plan (runtime.sharding.resolve_plan)
     and the microbatch plan (core.mapper.plan_microbatch) — the paper's
     technique applied at the mesh tier;
  2. builds the train/prefill/decode step with proper in/out shardings;
  3. ``.lower().compile()`` against ShapeDtypeStruct stand-ins (no
     allocation);
  4. records memory_analysis / cost_analysis / per-collective traffic and
     the three roofline terms into experiments/dryrun/<mesh>/<cell>.json.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --mesh both --skip-existing
"""

import argparse
import dataclasses
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, cell_is_valid, get_config, list_configs
from repro.core import costmodel as cm
from repro.core.mapper import MappingPolicy
from repro.core.roofline import (collective_stats_from_hlo,
                                 model_flops_per_step, roofline_from_compiled,
                                 roofline_from_numbers)
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (StepConfig, abstract_train_state,
                                make_decode_step, make_prefill_step,
                                make_train_step, resolve_microbatches)
from repro.models import build_model
from repro.optim import AdamWConfig
from repro.runtime import sharding as shd

OUT_ROOT = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _shardings_for_state(model, plan):
    p_sh = shd.param_shardings(model.specs, plan)
    z_sh = shd.zero1_shardings(model.specs, plan)
    rep = jax.sharding.NamedSharding(plan.info.mesh,
                                     jax.sharding.PartitionSpec())
    return {"params": p_sh, "opt": {"m": z_sh, "v": z_sh, "step": rep}}


def lower_cell(arch: str, shape_name: str, mesh, mesh_name: str,
               *, policy: MappingPolicy = MappingPolicy.AUTO,
               remat: str = "full", save_hlo: bool = False,
               overrides: dict | None = None, plan_tweak=None):
    """Lower+compile one cell; returns the result record dict."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_is_valid(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": why}

    model = build_model(cfg)
    plan = shd.resolve_plan(cfg, mesh, shape)
    if plan_tweak is not None:
        plan = plan_tweak(plan)
    rep = jax.sharding.NamedSharding(plan.info.mesh,
                                     jax.sharding.PartitionSpec())
    t0 = time.perf_counter()

    if shape.kind == "train":
        mb_plan = resolve_microbatches(cfg, shape, plan, policy=policy)
        step_cfg = StepConfig(remat=remat,
                              microbatches=mb_plan.num_microbatches)
        if overrides:
            sc_fields = {f.name for f in dataclasses.fields(StepConfig)}
            step_cfg = dataclasses.replace(
                step_cfg, **{k: v for k, v in overrides.items()
                             if k in sc_fields})
            remat = step_cfg.remat
        opt_cfg = AdamWConfig()
        train_step = make_train_step(model, opt_cfg, plan, step_cfg)
        state = abstract_train_state(model, plan)
        batch = model.input_specs(shape)
        st_sh = _shardings_for_state(model, plan)
        b_sh = shd.batch_shardings(batch, plan)
        fn = jax.jit(train_step,
                     in_shardings=(st_sh, b_sh),
                     out_shardings=(st_sh, rep),
                     donate_argnums=(0,))
        lowered = fn.lower(state, batch)
        extra = {"microbatches": step_cfg.microbatches,
                 "per_device_batch": mb_plan.per_device_batch,
                 "regime": mb_plan.regime.value}
        mf = model_flops_per_step(cfg.n_params_active(),
                                  model.tokens_per_step(shape), training=True)
    elif shape.kind == "prefill":
        prefill = make_prefill_step(model, plan, max_len=shape.seq_len,
                                    flags=overrides)
        batch = model.input_specs(shape)
        params = model.abstract_params()
        p_sh = shd.param_shardings(model.specs, plan)
        b_sh = shd.batch_shardings(batch, plan)
        cache_abs = model.init_cache(shape.global_batch, shape.seq_len,
                                     abstract=True, expand_kv=plan.expand_kv)
        c_sh = shd.cache_shardings(cache_abs, plan, cfg)
        fn = jax.jit(prefill, in_shardings=(p_sh, b_sh),
                     out_shardings=(rep, c_sh))
        lowered = fn.lower(params, batch)
        extra = {}
        mf = model_flops_per_step(cfg.n_params_active(),
                                  model.tokens_per_step(shape), training=False)
    else:  # decode
        decode = make_decode_step(model, plan, flags=overrides)
        params = model.abstract_params()
        p_sh = shd.param_shardings(model.specs, plan)
        cdt = plan.kv_spec.dtype
        cache_abs = model.init_cache(shape.global_batch, shape.seq_len,
                                     abstract=True, expand_kv=plan.expand_kv,
                                     cache_dtype=cdt)
        c_sh = shd.cache_shardings(cache_abs, plan, cfg)
        tokens = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
        t_sh = shd.batch_shardings({"tokens": tokens}, plan)["tokens"]
        fn = jax.jit(decode, in_shardings=(p_sh, c_sh, t_sh),
                     out_shardings=(rep, c_sh), donate_argnums=(1,))
        lowered = fn.lower(params, cache_abs, tokens)
        extra = {}
        mf = model_flops_per_step(cfg.n_params_active(),
                                  model.tokens_per_step(shape), training=False)

    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    hlo_text = compiled.as_text()
    chips = plan.info.n_devices
    # primary roofline: analytic cost model (validated vs cost_analysis on
    # loop-free configs — XLA counts while bodies once, see core.costmodel)
    mbs = extra.get("microbatches", 1) if shape.kind == "train" else 1
    cost = cm.cell_cost(cfg, shape, plan, microbatches=mbs, remat=remat,
                        overrides=overrides)
    rep_roof = roofline_from_numbers(
        arch=arch, shape=shape_name, mesh_name=mesh_name, chips=chips,
        flops=cost.flops, hbm_bytes=cost.hbm_bytes,
        coll_bytes=cost.coll_bytes, model_flops=mf,
        peak_memory=cost.peak_memory)
    # corroboration: raw compiled numbers (loop bodies counted once)
    raw_cost = compiled.cost_analysis()
    raw_coll = collective_stats_from_hlo(hlo_text, chips)

    mem = {}
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
                "peak_bytes": int(ma.argument_size_in_bytes
                                  + ma.output_size_in_bytes
                                  + ma.temp_size_in_bytes
                                  - ma.alias_size_in_bytes),
            }
    except Exception as e:  # pragma: no cover
        mem = {"error": str(e)}

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "status": "ok", "chips": chips,
        "kind": shape.kind,
        "plan_notes": plan.notes, "fsdp": plan.fsdp,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": mem,
        "memory_model": {k: round(v) for k, v in cost.mem_bytes.items()},
        "fits_hbm": cost.peak_memory < 16 * 1024**3,
        "raw_cost_analysis": {
            "flops_once_per_loop": float(raw_cost.get("flops", 0.0)),
            "bytes_once_per_loop": float(raw_cost.get("bytes accessed", 0.0)),
            "collective_bytes_once_per_loop": raw_coll.total_bytes,
            "collective_counts": dict(raw_coll.count_by_kind),
        },
        **extra,
        **rep_roof.row(),
    }
    if save_hlo:
        d = OUT_ROOT / mesh_name
        d.mkdir(parents=True, exist_ok=True)
        (d / f"{arch}_{shape_name}.hlo.txt").write_text(hlo_text)
    return rec


def run(archs, shapes, meshes, *, skip_existing=False, save_hlo=False,
        remat="full", policy=MappingPolicy.AUTO):
    results = []
    for mesh_name in meshes:
        mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
        out_dir = OUT_ROOT / mesh_name
        out_dir.mkdir(parents=True, exist_ok=True)
        for arch in archs:
            for shape_name in shapes:
                out = out_dir / f"{arch}_{shape_name}.json"
                if skip_existing and out.exists():
                    rec = json.loads(out.read_text())
                    results.append(rec)
                    print(f"[cached] {mesh_name}/{arch}/{shape_name}: "
                          f"{rec.get('status')}")
                    continue
                print(f"[dryrun] {mesh_name}/{arch}/{shape_name} ...",
                      flush=True)
                try:
                    rec = lower_cell(arch, shape_name, mesh, mesh_name,
                                     save_hlo=save_hlo, remat=remat,
                                     policy=policy)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "status": "error",
                           "error": f"{type(e).__name__}: {e}",
                           "trace": traceback.format_exc()[-2000:]}
                out.write_text(json.dumps(rec, indent=1, default=str))
                results.append(rec)
                if rec["status"] == "ok":
                    mb = rec["memory"].get("peak_bytes", 0) / 1e9
                    print(f"  ok: compile={rec['compile_s']}s "
                          f"peak={mb:.2f}GB/dev dominant={rec['dominant']} "
                          f"roofline_frac={rec['roofline_fraction']:.3f}",
                          flush=True)
                else:
                    print(f"  {rec['status']}: "
                          f"{rec.get('reason', rec.get('error'))}", flush=True)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--remat", default="full",
                    choices=["none", "dots", "full"])
    ap.add_argument("--policy", default="auto",
                    choices=["naive", "fixed", "auto"])
    args = ap.parse_args()
    archs = list_configs() if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    results = run(archs, shapes, meshes, skip_existing=args.skip_existing,
                  save_hlo=args.save_hlo, remat=args.remat,
                  policy=MappingPolicy(args.policy))
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\ndone: {n_ok} ok, {n_skip} skipped, {n_err} errors "
          f"of {len(results)} cells")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
