"""Single-Transformer-block benchmark machinery shared by the Table 1/4 and
Figure 8/9 analogues: build one block of a paper Table-2 config under
Full / LoRA / SPT, time forward+backward, and probe compiled peak memory.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.paper_blocks import blocks
from repro.core.params import init_tree
from repro.configs import apply_variant
from repro.models import transformer
from benchmarks.common import compiled_temp_bytes, time_fn


def reduced(name: str, scale: int = 4,
            variant: str = "spt") -> configs.ModelConfig:
    """Paper block config with dims / `scale` (CPU feasibility)."""
    cfg = blocks()[name]
    cfg = dataclasses.replace(
        cfg,
        d_model=cfg.d_model // scale,
        num_heads=max(2, cfg.num_heads // scale),
        num_kv_heads=max(2, cfg.num_kv_heads // scale),
        head_dim=cfg.resolved_head_dim // 2 if scale > 2 else cfg.head_dim,
        d_ff=cfg.d_ff // scale,
        vocab_size=2048, max_position=4096)
    cfg = apply_variant(cfg, variant)
    if variant in ("full", "lora"):
        # paper-faithful baseline: attention materializes the full (n, n)
        # weight matrix (the PyTorch behavior SPT's memory claim targets)
        cfg = cfg.with_spt(chunk_q=1 << 20)
    return cfg


def block_step(cfg, module: str = "both"):
    """Returns (fn(params, x) -> scalar loss, params, x) for one block's
    forward+backward.  module: mha | ffn | both."""
    kind = cfg.pattern[0]
    defs = transformer.block_defs(cfg, kind)
    if module == "mha":
        defs.pop("ffn", None)
        defs.pop("norm_ffn", None)
    params = init_tree(defs, jax.random.PRNGKey(0))

    def fwd(p, x):
        if module == "ffn":
            from repro.models import ffn as ffn_mod
            from repro.models.layers import apply_norm
            h = apply_norm(p["norm_ffn"], x, cfg.norm)
            y, _ = ffn_mod.ffn_apply(p["ffn"], h, cfg)
            return jnp.sum((x + y.astype(x.dtype)) ** 2)
        y, _, _ = transformer.block_apply(p, x, cfg, kind, mode="train")
        return jnp.sum(y ** 2)

    def step(p, x):
        from repro.core.params import trainable_mask, partition, combine
        loss, grads = jax.value_and_grad(fwd)(p, x)
        return loss

    return step, params


def bench_block(name: str, variant: str, batch: int = 4, seq: int = 256,
                module: str = "both", scale: int = 4
                ) -> Dict[str, float]:
    cfg = reduced(name, scale, variant)
    step, params = block_step(cfg, module)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, seq, cfg.d_model)
                          ).astype(jnp.bfloat16)
    jit_step = jax.jit(step)
    us = time_fn(jit_step, params, x, iters=3, warmup=1)
    ax = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    xs = jax.ShapeDtypeStruct(x.shape, x.dtype)
    mem = compiled_temp_bytes(step, ax, xs)
    toks = batch * seq
    return {"us": us, "temp_mb": (mem or 0) / 1e6,
            "tokens_per_s": toks / (us / 1e6)}
