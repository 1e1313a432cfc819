"""Routed FFN with an explicit shard_map collective schedule (§Perf it10).

Why: under plain pjit, the TP contraction over the model-sharded FFN dim
emits all-reduces of the (B, G, C, d) dispatch-buffer cotangent — measured
at 727 GB/device/step on gemma-7b train_4k — and a sharding-constraint-only
sequence-parallel attempt made it worse (EXPERIMENTS.md §Perf it7: XLA
reshards around the gather/scatter instead of adopting AG->compute->RS).

This module pins the Megatron-SP schedule by hand:

    x (batch->data, seq->model)                     [seq-sharded residual]
      -- all_gather(seq, model) -> full local seq
      -- route + capacity dispatch (local, per sequence)
      -- up/gate GEMMs with the local (G, d, F/TP) weight shard
      -- down GEMM -> partial (B, G, C, d)
      -- combine scatter -> partial (B, S, d)
      -- psum_scatter(seq, model) -> (batch->data, seq->model) output

Collective bytes per layer: AG(N) + RS(N) forward, RS(N) + AG(N) backward,
N = |activations| — vs >= 2 all-reduces (2N each) for the pjit schedule.
The inner math reuses core.dispatch / core.routed_ffn pieces unchanged, so
the function is numerically identical to impl="grouped" (asserted in
tests/test_ffn_shmap.py).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import dispatch, lora
from repro.core.routed_ffn import (ACTIVATIONS, RoutedFFNConfig, route)


def _specs(mesh):
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    model = "model" if "model" in mesh.axis_names else None
    b = batch_axes if batch_axes else None
    return b, model


def applicable(mesh, cfg: RoutedFFNConfig, d_ff: int, seq: int,
               batch: int) -> bool:
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return False
    tp = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    dp = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            dp *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]
    return (cfg.group_dim % tp == 0 and seq % tp == 0 and batch % dp == 0)


def routed_ffn_shmap(x: jax.Array, p: dict, cfg: RoutedFFNConfig,
                     lora_cfg: lora.LoRAConfig, mesh,
                     need_aux: bool = True
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, d) logically; enters/leaves seq-sharded on "model".

    ``need_aux=False`` (inference) skips the router softmax, the
    load-balance loss and its cross-data pmean."""
    b_ax, model = _specs(mesh)
    r = lora_cfg.rank if lora_cfg.enabled else 0
    use_lora = lora_cfg.enabled and "lora_inner" in p
    act = ACTIVATIONS[cfg.activation]

    def inner(x_l, router_w, wi, wo, wg, li_b, li_c, lg_b, lg_c, lo_b, lo_c):
        # x_l: (b_loc, s/tp, d) -> gather full sequence locally
        xf = jax.lax.all_gather(x_l, "model", axis=1, tiled=True)
        bl, s, d = xf.shape
        choice, gate_w, probs = route(xf, router_w, cfg, need_aux=need_aux)
        cap = dispatch.capacity(s, cfg.num_groups, cfg.active_groups,
                                cfg.capacity_factor, pad=cfg.capacity_pad)
        plan = dispatch.make_plan(choice, gate_w, cfg.num_groups, cap)
        xg = dispatch.gather(xf, plan)                  # (bl, G, C, d)

        def proj_up(w, lb, lc_):
            up = jnp.einsum("bgcd,gdf->bgcf", xg,
                            jax.lax.stop_gradient(w).astype(xf.dtype))
            if use_lora:
                xb = jnp.einsum("bgcd,dr->bgcr", xg, lb.astype(xf.dtype))
                up = up + lora_cfg.scale * jnp.einsum(
                    "bgcr,grf->bgcf", xb, lc_.astype(xf.dtype))
            return up

        up = proj_up(wi, li_b, li_c)
        if cfg.gated:
            h = act(proj_up(wg, lg_b, lg_c)) * up
        else:
            h = act(up)
        y = jnp.einsum("bgcf,gfd->bgcd", h,
                       jax.lax.stop_gradient(wo).astype(xf.dtype))
        if use_lora:
            hb = jnp.einsum("bgcf,gfr->bgcr", h, lo_b.astype(xf.dtype))
            y = y + lora_cfg.scale * jnp.einsum(
                "bgcr,rd->bgcd", hb, lo_c.astype(xf.dtype))
        y_full = dispatch.combine(y.astype(xf.dtype), plan, s)
        # partial over the TP contraction -> reduce-scatter along seq
        y_out = jax.lax.psum_scatter(y_full, "model", scatter_dimension=1,
                                     tiled=True)
        if need_aux:
            lb_loss = jax.lax.pmean(
                dispatch.load_balance_loss(probs, choice, cfg.num_groups),
                axis_name=tuple(a for a in ("pod", "data")
                                if a in mesh.axis_names) or "model")
        else:
            lb_loss = jnp.zeros((), jnp.float32)
        dropped = jax.lax.pmean(plan.dropped, axis_name="model")
        return y_out, lb_loss, dropped

    zero = jnp.zeros((), jnp.float32)
    wi, wo = p["w_inner"], p["w_outer"]
    wg = p.get("w_gate", wi)                 # unused when not gated
    li_b = p["lora_inner"]["b"] if use_lora else zero
    li_c = p["lora_inner"]["c"] if use_lora else zero
    lg_b = p["lora_gate"]["b"] if (use_lora and cfg.gated) else zero
    lg_c = p["lora_gate"]["c"] if (use_lora and cfg.gated) else zero
    lo_b = p["lora_outer"]["b"] if use_lora else zero
    lo_c = p["lora_outer"]["c"] if use_lora else zero

    w_col = P(None, None, model)             # F sharded (last dim)
    w_row = P(None, model, None)             # F sharded (middle dim)
    scalar = P()
    in_specs = (P(b_ax, model, None),        # x: seq-sharded
                P(None, None),               # router (replicated)
                w_col, w_row, w_col,
                scalar if not use_lora else P(None, None),
                scalar if not use_lora else w_col,
                scalar if not (use_lora and cfg.gated) else P(None, None),
                scalar if not (use_lora and cfg.gated) else w_col,
                scalar if not use_lora else w_row,
                scalar if not use_lora else P(None, None))
    fn = jax.shard_map(inner, mesh=mesh, in_specs=in_specs,
                       out_specs=(P(b_ax, model, None), P(), P()),
                       check_vma=False)
    y, lb_loss, dropped = fn(x, p["router"], wi, wo, wg, li_b, li_c,
                             lg_b, lg_c, lo_b, lo_c)
    return y, {"lb_loss": lb_loss, "dropped": dropped}
