import argparse
import json
import os
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (
    ARCH_IDS, SHAPES, get_config, get_shape, supports_shape,
)
from repro.configs.base import ModelConfig, RunConfig, ShapeConfig
from repro.distributed.sharding import ShardingEnv, activate, resolve_spec
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs
from repro.models import Model, abstract_params, count_params
from repro.models.kvcache import build_cache
from repro.training.optimizer import make_optimizer
from repro.training.train_step import (
    batch_pspecs, jit_sharded_train_step, param_pspecs, to_named,
)

from repro.launch.hlo_analysis import analyze_module

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"

# The dry-run fakes 512 host devices so the production meshes (16x16
# single-pod, 2x16x16 multi-pod) can be built.  jax fixes the device count
# when its backend first starts, so a main() sets this before any jax call;
# importing this module changes nothing.
FAKE_DEVICES_FLAGS = "--xla_force_host_platform_device_count=512"


# ---------------------------------------------------------------------------
# Run-config defaults per cell
# ---------------------------------------------------------------------------

def default_run(cfg: ModelConfig, shape: ShapeConfig, multi_pod: bool,
                overrides: dict | None = None) -> RunConfig:
    n = count_params(cfg)
    kw = dict(
        pod=2 if multi_pod else 1,
        data=16, model_axis=16,
        optimizer="adafactor" if n > 100e9 else "adamw",
        zero_stage=3 if n > 5e9 else 1,
        remat_policy="block" if shape.kind == "train" else "none",
        microbatches=1,
    )
    if overrides:
        kw.update(overrides)
    return RunConfig(model=cfg, shape=shape, **kw)


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------

def _cache_pspecs(cfg: ModelConfig, env: ShardingEnv, B: int, S: int):
    """Resolve decode-cache logical axes against the active mesh."""
    def creator(shp, logical, dtype):
        return resolve_spec(env, tuple(logical), shp)
    return build_cache(cfg, creator, B, S)


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               run_overrides: dict | None = None,
               model_overrides: dict | None = None):
    """Lower + compile one (arch x shape x mesh) cell.

    Returns (record, lowered, compiled) — record carries cost/memory/collective
    numbers for EXPERIMENTS.md §Dry-run and §Roofline.
    """
    cfg = get_config(arch)
    if model_overrides:
        cfg = cfg.replace(**model_overrides)
    shape = get_shape(shape_name)
    if not supports_shape(cfg, shape):
        return ({"arch": arch, "shape": shape_name, "mesh": "multi" if multi_pod else "single",
                 "status": "skipped", "reason": "sub-quadratic-only shape on full-attention arch"},
                None, None)
    mesh = make_production_mesh(multi_pod=multi_pod)
    env = ShardingEnv(mesh)
    run = default_run(cfg, shape, multi_pod, run_overrides)
    B, S = shape.global_batch, shape.seq_len
    t0 = time.time()

    with activate(env), mesh:
        params_abs = abstract_params(cfg)
        p_ns = to_named(env, param_pspecs(cfg, env, run.zero_stage if shape.kind == "train" else 0))
        b_ns = to_named(env, batch_pspecs(cfg, env, B, kind=shape.kind))
        batch_abs = input_specs(cfg, shape)

        if shape.kind == "train":
            optimizer = make_optimizer(run.optimizer)
            opt_abs = jax.eval_shape(optimizer.init, params_abs)
            state_abs = {"params": params_abs, "opt": opt_abs,
                         "step": jax.ShapeDtypeStruct((), jnp.int32)}
            jitted, _ = jit_sharded_train_step(cfg, run, optimizer, env)
            lowered = jitted.lower(state_abs, batch_abs)
        elif shape.kind == "prefill":
            model = Model(cfg)

            def prefill_step(params, batch):
                return model.prefill(params, batch, cache_len=S)

            jitted = jax.jit(prefill_step, in_shardings=(p_ns, b_ns))
            lowered = jitted.lower(params_abs, batch_abs)
        else:  # decode
            model = Model(cfg)
            cache_abs = build_cache(cfg, lambda s, l, d: jax.ShapeDtypeStruct(s, d), B, S)
            c_ns = to_named(env, _cache_pspecs(cfg, env, B, S))

            def serve_step(params, cache, batch):
                return model.decode_step(params, cache, batch)

            jitted = jax.jit(serve_step, in_shardings=(p_ns, c_ns, b_ns),
                             out_shardings=(None, c_ns), donate_argnums=(1,))
            lowered = jitted.lower(params_abs, cache_abs, batch_abs)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_rec = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not implement it
        mem_rec = {"error": str(e)}

    hlo = compiled.as_text()
    hlo_stats = analyze_module(hlo)
    coll = hlo_stats["collectives"]
    n_dev = mesh.devices.size

    record = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok",
        "n_devices": int(n_dev),
        "kind": shape.kind,
        "params": count_params(cfg),
        "active_params": count_params(cfg, active_only=True),
        # raw XLA cost analysis (per-device; while bodies counted ONCE)
        "xla_flops": cost.get("flops"),
        "xla_bytes_accessed": cost.get("bytes accessed"),
        # trip-count-aware per-device numbers (launch/hlo_analysis.py)
        "flops_per_device": hlo_stats["flops"],
        "hbm_bytes_per_device": hlo_stats["hbm_bytes"],
        "while_loops": hlo_stats["while_loops"],
        "memory_analysis": mem_rec,
        "collectives": coll,
        "zero_stage": run.zero_stage,
        "optimizer": run.optimizer,
        "remat": run.remat_policy,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "hlo_bytes": len(hlo),
    }
    return record, lowered, compiled


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def run_cell_to_file(arch: str, shape_name: str, multi_pod: bool) -> dict:
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    tag = f"{arch}__{shape_name}__{'multi' if multi_pod else 'single'}"
    out = RESULTS_DIR / f"{tag}.json"
    try:
        record, lowered, compiled = lower_cell(arch, shape_name, multi_pod)
    except Exception as e:
        record = {"arch": arch, "shape": shape_name,
                  "mesh": "2x16x16" if multi_pod else "16x16",
                  "status": "error", "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-4000:]}
    out.write_text(json.dumps(record, indent=1))
    return record


def main():
    os.environ["XLA_FLAGS"] = FAKE_DEVICES_FLAGS
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None, choices=list(ARCH_IDS))
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true", help="run every remaining cell")
    ap.add_argument("--force", action="store_true", help="recompute cached cells")
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    cells = []
    for arch in ([args.arch] if args.arch else ARCH_IDS):
        for shape_name in ([args.shape] if args.shape else SHAPES):
            for mp in meshes:
                cells.append((arch, shape_name, mp))
    if not args.all and not (args.arch and args.shape):
        ap.error("give --arch and --shape, or --all")

    for arch, shape_name, mp in cells:
        tag = f"{arch}__{shape_name}__{'multi' if mp else 'single'}"
        out = RESULTS_DIR / f"{tag}.json"
        if out.exists() and not args.force:
            rec = json.loads(out.read_text())
            print(f"[cached] {tag}: {rec.get('status')}", flush=True)
            continue
        t0 = time.time()
        rec = run_cell_to_file(arch, shape_name, mp)
        status = rec.get("status")
        extra = "" if status != "error" else " :: " + rec.get("error", "")[:160]
        print(f"[{time.time()-t0:7.1f}s] {tag}: {status}{extra}", flush=True)
        if status == "ok":
            ma = rec.get("memory_analysis", {})
            print(f"    flops/dev={rec.get('flops_per_device'):.3e} "
                  f"hbm/dev={rec.get('hbm_bytes_per_device'):.3e} "
                  f"coll_traffic/dev={rec['collectives']['traffic_bytes']:.3e} "
                  f"(n={rec['collectives']['count']}) mem={ma}", flush=True)


if __name__ == "__main__":
    main()
