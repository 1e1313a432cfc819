"""Multi-pod dry-run (deliverable e): for every (arch x input-shape) cell,
``jit(step).lower(...).compile()`` against the production mesh — 16x16
single-pod and 2x16x16 multi-pod — with ShapeDtypeStruct inputs (no device
allocation).  Prints memory_analysis() and cost_analysis() and records the
roofline terms (launch/roofline.py) to JSON for EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch gemma-7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""
import argparse
import json
import os
import pathlib
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import SHAPES_BY_NAME, ModelConfig, ShapeSpec
from repro.configs.shapes import input_specs
from repro.launch import roofline, steps
from repro.launch.mesh import make_production_mesh, mesh_num_devices
from repro.optim.adamw import OptimizerConfig
from repro.serving import engine
from repro.sharding import axis_rules, rules_for_mesh
from repro.train import state as S


# The pod the dry-run models on virtual CPU devices: its roofline terms
# use this chip's published peaks (launch/roofline.py PEAKS).
TARGET_KIND = "TPU v5 lite"


def lower_cell(cfg: ModelConfig, shape: ShapeSpec, mesh,
               loss_chunk: int = 512):
    """Returns (lowered, aux_info). Pure AOT: no arrays are created."""
    rules = rules_for_mesh(mesh)
    specs = input_specs(cfg, shape)
    with mesh, axis_rules(rules):
        if shape.kind == "train":
            step = steps.build_train_step(cfg, OptimizerConfig(),
                                          loss_chunk=loss_chunk)
            st_sh, b_sh, out_sh, m_sh = steps.train_shardings(
                cfg, mesh, rules, specs)
            fn = jax.jit(step, in_shardings=(st_sh, b_sh),
                         out_shardings=(out_sh, m_sh),
                         donate_argnums=(0,))
            lowered = fn.lower(S.abstract_state(cfg), specs)
        elif shape.kind == "prefill":
            step = steps.build_prefill_step(cfg, max_len=shape.seq_len)
            rules_ = rules
            ps = steps._map_specs(mesh, S.param_specs(cfg, rules_))
            bs = steps._map_specs(
                mesh, steps.batch_specs(cfg, specs, rules_))
            fn = jax.jit(step, in_shardings=(ps, bs))
            lowered = fn.lower(_abstract_params(cfg), specs)
        elif shape.kind == "decode":
            step = steps.build_decode_step(cfg)
            caches = engine.abstract_decode_caches(
                cfg, shape.global_batch, shape.seq_len)
            ps, cs, bs, ls = steps.decode_shardings(cfg, mesh, rules, caches,
                                                    specs)
            fn = jax.jit(step, in_shardings=(ps, cs, bs["token"], bs["pos"]),
                         out_shardings=(cs, ls), donate_argnums=(1,))
            lowered = fn.lower(_abstract_params(cfg), caches,
                               specs["token"], specs["pos"])
        else:
            raise ValueError(shape.kind)
    return lowered


def _abstract_params(cfg: ModelConfig):
    from repro.core.params import abstract_tree
    return abstract_tree(S.model_defs(cfg))


def _unit_config(cfg: ModelConfig, units: int) -> ModelConfig:
    """A copy of cfg with exactly `units` pattern units (no tail)."""
    import dataclasses as dc
    kw = {"num_layers": units * len(cfg.pattern)}
    if cfg.family == "audio":
        kw["encoder_layers"] = units
    return dc.replace(cfg, **kw)


def _analysis_cfg(cfg: ModelConfig, shape: ShapeSpec) -> ModelConfig:
    """Bigger chunks => fewer unrolled loop iterations in analysis mode.
    (ssm_chunk is left alone: SSD FLOPs scale with the chunk size.)"""
    return cfg.with_spt(chunk_q=min(2048, shape.seq_len))


def exact_roofline(cfg: ModelConfig, shape: ShapeSpec, mesh,
                   verbose: bool = False) -> Dict[str, Any]:
    """Loop-aware cost accounting (EXPERIMENTS.md §Dry-run calibration):
    XLA cost_analysis counts while-loop bodies ONCE, so the scanned
    lowering undercounts by the trip count.  We lower 1-unit and 2-unit
    copies of the model with every loop unrolled (analysis_mode) and
    extrapolate linearly: F(U units) = F1 + (U - 1) (F2 - F1).  Tail layers
    (num_layers % pattern) count fractionally."""
    from repro.core.chunking import analysis_mode
    acfg = _analysis_cfg(cfg, shape)
    units_equiv = cfg.num_layers / len(cfg.pattern)
    out: Dict[str, Any] = {}
    rl = {}
    with analysis_mode():
        for u in (1, 2):
            compiled = lower_cell(_unit_config(acfg, u), shape, mesh,
                                  loss_chunk=2048).compile()
            rl[u] = roofline.analyze(compiled, TARGET_KIND)
    per_unit = {
        "flops": rl[2].flops - rl[1].flops,
        "hbm_bytes": rl[2].hbm_bytes - rl[1].hbm_bytes,
        "coll_bytes": rl[2].coll_bytes - rl[1].coll_bytes,
    }
    total = roofline.Roofline(
        flops=rl[1].flops + per_unit["flops"] * (units_equiv - 1),
        hbm_bytes=rl[1].hbm_bytes + per_unit["hbm_bytes"] * (units_equiv - 1),
        coll_bytes=max(0.0, rl[1].coll_bytes
                       + per_unit["coll_bytes"] * (units_equiv - 1)),
        coll_by_kind={k: int(v + (rl[2].coll_by_kind.get(k, 0) - v)
                             * (units_equiv - 1))
                      for k, v in rl[1].coll_by_kind.items()},
        device_kind=TARGET_KIND)
    out["per_unit"] = per_unit
    out["one_unit"] = rl[1].to_dict()
    out["roofline_exact"] = total.to_dict()
    return out


def parse_overrides(pairs) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for pair in pairs or []:
        k, _, v = pair.partition("=")
        if v.lower() in ("true", "false"):
            out[k] = v.lower() == "true"
            continue
        for cast in (int, float):
            try:
                out[k] = cast(v)
                break
            except ValueError:
                continue
        else:
            out[k] = v
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             variant: str = "spt", verbose: bool = True,
             cfg_override: Optional[ModelConfig] = None,
             spt_overrides: Optional[Dict[str, Any]] = None
             ) -> Dict[str, Any]:
    shape = SHAPES_BY_NAME[shape_name]
    ok, why = configs.cell_supported(arch, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}
    cfg = cfg_override or configs.apply_variant(configs.get_config(arch),
                                                variant)
    if spt_overrides:
        cfg = cfg.with_spt(**spt_overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh_num_devices(mesh)
    t0 = time.time()
    result: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "variant": variant,
        "mesh": "multi" if multi_pod else "single", "chips": chips,
    }
    try:
        lowered = lower_cell(cfg, shape, mesh)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        rl = roofline.analyze(compiled, TARGET_KIND)
        if shape.kind == "train":
            tokens = shape.global_batch * shape.seq_len
            mf = roofline.model_flops(cfg, tokens)
        elif shape.kind == "prefill":
            tokens = shape.global_batch * shape.seq_len
            mf = roofline.model_flops(cfg, tokens) / 3.0  # fwd only: 2ND
        else:
            mf = 2.0 * roofline.active_params(cfg) * shape.global_batch
        mem = None
        try:
            ma = compiled.memory_analysis()
            mem = {k: int(getattr(ma, k)) for k in
                   ("argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "generated_code_size_in_bytes",
                    "alias_size_in_bytes") if hasattr(ma, k)}
            if verbose:
                print(f"  memory_analysis: {mem}")
        except Exception as e:  # pragma: no cover
            mem = {"error": str(e)}
        result.update({
            "status": "ok",
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "roofline_scanned": rl.to_dict(),
            "model_flops_total": mf,
            "model_flops_per_chip": mf / chips,
            "memory_analysis": mem,
        })
        if not multi_pod:   # roofline table is single-pod only
            try:
                result.update(exact_roofline(cfg, shape, mesh))
                ex = result["roofline_exact"]
                result["useful_flops_ratio"] = (
                    (mf / chips) / ex["flops"] if ex["flops"] else None)
                if verbose:
                    print(f"  roofline(exact): flops/dev={ex['flops']:.3e} "
                          f"bytes/dev={ex['hbm_bytes']:.3e} "
                          f"coll/dev={ex['coll_bytes']:.3e}")
                    print(f"    compute={ex['t_compute']*1e3:.2f}ms "
                          f"memory={ex['t_memory']*1e3:.2f}ms "
                          f"collective={ex['t_collective']*1e3:.2f}ms "
                          f"-> {ex['bottleneck']}-bound  "
                          f"useful={result['useful_flops_ratio']}")
            except Exception as e:
                result["roofline_exact_error"] = repr(e)
        if verbose:
            c = rl.to_dict()
            print(f"  cost_analysis(scanned): flops/dev={rl.flops:.3e} "
                  f"bytes/dev={rl.hbm_bytes:.3e} coll/dev={rl.coll_bytes:.3e}")
    except Exception as e:
        result.update({"status": "error", "error": repr(e),
                       "traceback": traceback.format_exc()})
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES_BY_NAME) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--variant", default="spt",
                    choices=["spt", "lora", "full"])
    ap.add_argument("--all", action="store_true",
                    help="sweep every (arch x shape) cell")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--set", action="append", default=[], dest="overrides",
                    help="SPTConfig override, e.g. --set attn_impl=sparse_masked")
    ap.add_argument("--tag", default="", help="suffix for output filenames")
    args = ap.parse_args()
    overrides = parse_overrides(args.overrides)

    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    archs = list(configs.ARCH_NAMES) if (args.all or not args.arch) \
        else [args.arch]
    shapes = list(SHAPES_BY_NAME) if (args.all or not args.shape) \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape}_{'multi' if mp else 'single'}"
                if args.variant != "spt":
                    tag += f"_{args.variant}"
                if args.tag:
                    tag += f"_{args.tag}"
                print(f"[dryrun] {tag}")
                res = run_cell(arch, shape, mp, args.variant,
                               spt_overrides=overrides)
                if overrides:
                    res["spt_overrides"] = overrides
                (outdir / f"{tag}.json").write_text(json.dumps(res, indent=1))
                print(f"  -> {res['status']}" +
                      (f" ({res.get('reason', res.get('error', ''))})"
                       if res["status"] != "ok" else ""))
                failures += res["status"] == "error"
    return 1 if failures else 0


if __name__ == "__main__":
    # 512 virtual CPU devices for the production mesh.  Set here, before
    # the first backend use, and only when run as a program, so importing
    # this module changes nothing for its importer.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    sys.exit(main())
