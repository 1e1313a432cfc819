"""FFN layer: dense (Full/LoRA baseline) or the paper's routed FFN.

Routed-FFN execution paths (selection in core/dispatch.py):

  * ``spt.ffn_impl="pallas"`` — fused Pallas grouped-GEMM kernel with
    in-kernel scalar-prefetch dispatch (kernels/routed_ffn); falls back
    to "grouped" under REPRO_DISABLE_KERNELS=1.
  * ``mode="decode"`` at (B, 1, d) — block-gather decode kernel (no
    capacity plan, no dispatch buffer) when
    ``dispatch.use_decode_ffn_kernel(cfg)`` says so.
  * ``"grouped"`` / ``"dense"`` / ``"grouped_shmap"`` — the jnp paths.

``mode`` ("train" | "prefill" | "decode") also gates the router aux:
inference skips the full-group softmax and load-balance loss.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import dispatch, lora, routed_ffn
from repro.core.params import ParamDef
from repro.models.layers import norm_defs
from repro.sharding import shard


def _routed_cfg(cfg: ModelConfig) -> routed_ffn.RoutedFFNConfig:
    return routed_ffn.RoutedFFNConfig(
        d_model=cfg.d_model, d_ff=cfg.d_ff,
        num_groups=cfg.spt.ffn_groups,
        active_groups=cfg.spt.ffn_active_groups,
        capacity_factor=cfg.spt.ffn_capacity_factor,
        capacity_pad=cfg.spt.dispatch_pad,
        activation=cfg.activation, gated=cfg.gated_ffn,
        lb_loss_weight=cfg.spt.lb_loss_weight)


def routed_applicable(cfg: ModelConfig) -> bool:
    return (cfg.spt.routed_ffn and cfg.d_ff > 0
            and cfg.d_ff % cfg.spt.ffn_groups == 0)


def slot_counts(cfg: ModelConfig, batch: int, seq: int,
                dropped: float) -> Optional[Dict[str, float]]:
    """``dispatch.slot_counts`` of one train step of (batch, seq) tokens
    through the layers of a decoder-only stack whose FFN routes through
    the capacity dispatch, which the step's ``dropped`` is summed over.
    None where no layer does (dense or MoE FFNs, the dense routed oracle,
    the audio encoder-decoder)."""
    if (cfg.family == "audio" or cfg.num_experts > 0
            or not routed_applicable(cfg) or cfg.spt.ffn_impl == "dense"):
        return None
    layers = sum(kind != "ssd" for kind in cfg.layer_types())
    rc = _routed_cfg(cfg)
    return dispatch.slot_counts(batch, seq, layers, rc.num_groups,
                                rc.active_groups, rc.capacity_factor,
                                dropped, pad=rc.capacity_pad)


def ffn_defs(cfg: ModelConfig) -> dict:
    lc = cfg.spt.lora
    if routed_applicable(cfg):
        return routed_ffn.param_defs(_routed_cfg(cfg), lc)
    d, f = cfg.d_model, cfg.d_ff
    defs = {
        "wi": lora.linear_defs(d, f, lc, "embed", "ffn"),
        "wo": lora.linear_defs(f, d, lc, "ffn", "embed"),
    }
    if cfg.gated_ffn:
        defs["wg"] = lora.linear_defs(d, f, lc, "embed", "ffn")
    return defs


def _tel_expert_load(choice: jax.Array, num_groups: int, x: jax.Array,
                     seq_lengths) -> jax.Array:
    """(B, G) per-row token->expert load from the router's top-k choices
    (telemetry layer).  Right-pad rows of a ragged prefill batch are
    masked out so loads count real tokens only."""
    oh = jax.nn.one_hot(choice, num_groups, dtype=jnp.float32)  # (B,S,k,G)
    if seq_lengths is not None:
        valid = (jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]
                 < seq_lengths[:, None]).astype(jnp.float32)    # (B, S)
        oh = oh * valid[:, :, None, None]
    return oh.sum(axis=(1, 2))


def _routed_apply(p: dict, x: jax.Array, cfg: ModelConfig, mode: str,
                  seq_lengths=None) -> Tuple[jax.Array, dict]:
    lc = cfg.spt.lora
    rcfg = _routed_cfg(cfg)
    need_aux = mode == "train"
    y = aux = None
    if mode == "decode" and x.ndim == 3 and x.shape[1] == 1:
        if dispatch.use_decode_ffn_kernel(cfg):
            from repro.kernels.routed_ffn import ops as rffn_ops
            y, aux = rffn_ops.routed_ffn_decode(x, p, rcfg, lc)
        elif cfg.spt.decode_ffn_impl == "jnp":
            # explicit per-path override: grouped jnp at decode even when
            # ffn_impl="pallas" keeps the train/prefill kernel on
            y, aux = routed_ffn.routed_ffn(x, p, rcfg, lc, impl="grouped",
                                           need_aux=False)
    if y is None:
        impl = cfg.spt.ffn_impl
        if impl == "pallas":
            if dispatch.use_routed_ffn_kernel(cfg):
                from repro.kernels.routed_ffn import ops as rffn_ops
                y, aux = rffn_ops.routed_ffn(x, p, rcfg, lc,
                                             need_aux=need_aux,
                                             seq_lengths=seq_lengths)
            else:
                impl = "grouped"               # REPRO_DISABLE_KERNELS=1
        if y is None and impl == "grouped_shmap":
            from repro.core import ffn_shmap
            from repro.sharding import current_rules
            rules = current_rules() or {}
            mesh = rules.get("__mesh__")
            if (x.ndim == 3 and seq_lengths is None and ffn_shmap.applicable(
                    mesh, rcfg, cfg.d_ff, x.shape[1], x.shape[0])):
                y, aux = ffn_shmap.routed_ffn_shmap(x, p, rcfg, lc, mesh,
                                                    need_aux=need_aux)
            else:
                impl = "grouped"
        if y is None:
            y, aux = routed_ffn.routed_ffn(x, p, rcfg, lc, impl=impl,
                                           need_aux=need_aux,
                                           seq_lengths=seq_lengths)
    if (dispatch.use_telemetry_counters(cfg) and x.ndim == 3
            and mode in ("prefill", "decode")):
        # jit-pure telemetry counters: re-run the (tiny) router einsum so
        # every execution path — kernel or jnp — reports identical loads
        choice, _, _ = routed_ffn.route(x, p["router"], rcfg, need_aux=False)
        aux = dict(aux)
        aux["tel_expert_load"] = _tel_expert_load(
            choice, rcfg.num_groups, x, seq_lengths)
        aux["tel_expert_drop"] = jnp.asarray(
            aux.get("dropped", 0.0), jnp.float32)
    return y, aux


def ffn_apply(p: dict, x: jax.Array, cfg: ModelConfig, mode: str = "train",
              seq_lengths=None) -> Tuple[jax.Array, dict]:
    """seq_lengths: per-row real lengths (B,) for batched ragged prefill —
    threads the exact-length dispatch capacity into the routed paths (the
    dense FFN is per-token, so it ignores them)."""
    lc = cfg.spt.lora
    if routed_applicable(cfg):
        return _routed_apply(p, x, cfg, mode, seq_lengths=seq_lengths)
    act = routed_ffn.ACTIVATIONS[cfg.activation]
    up = lora.linear(x, p["wi"], lc)
    up = shard(up, "batch", None, "ffn")
    if cfg.gated_ffn:
        gate = lora.linear(x, p["wg"], lc)
        h = act(gate) * up
    else:
        h = act(up)
    y = lora.linear(h, p["wo"], lc)
    return shard(y, "batch", None, None), {}
