"""Attention layer: GQA/MQA, RoPE, qk-norm, SWA — with the paper's sparse
MHA as a drop-in execution mode (SPTConfig.sparse_mha).

Modes:
  train    — full-sequence causal (or bidirectional for encoders)
  prefill  — train-mode compute + populate the KV(+PQ-codes) cache
  decode   — one token against the cache; sparse MHA selects top-L over the
             cached keys' PQ codes (paper Alg. 1 applied at serving time)

The KV cache stores absolute slot positions so a plain causal cache and a
ring-buffer sliding-window cache share one code path.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import dispatch as kdispatch
from repro.core import lora, pq
from repro.core import sparse_attention as sa
from repro.core.params import ParamDef
from repro.models import layers
from repro.sharding import shard


def _pq_config(cfg: ModelConfig) -> pq.PQConfig:
    return pq.PQConfig(head_dim=cfg.resolved_head_dim,
                       code_dim=cfg.spt.pq_code_dim,
                       num_codewords=cfg.spt.pq_codewords,
                       update_interval=cfg.spt.pq_update_interval)


def _sa_config(cfg: ModelConfig) -> sa.SparseAttentionConfig:
    return sa.SparseAttentionConfig(
        pq=_pq_config(cfg),
        top_fraction=cfg.spt.attn_top_fraction,
        min_l=cfg.spt.attn_min_l,
        pad_l_to=cfg.spt.attn_pad_l_to,
        chunk_q=cfg.spt.chunk_q,
        select_granularity=cfg.spt.select_granularity,
        qerr_loss_weight=cfg.spt.qerr_loss_weight)


def sparse_applicable(cfg: ModelConfig) -> bool:
    return cfg.spt.sparse_mha and cfg.resolved_head_dim % cfg.spt.pq_code_dim == 0


def attn_defs(cfg: ModelConfig) -> dict:
    d, hq, hk = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    lc = cfg.spt.lora
    defs = {
        "wq": lora.linear_defs(d, hq * hd, lc, "embed", "heads"),
        "wk": lora.linear_defs(d, hk * hd, lc, "embed", "kv_heads"),
        "wv": lora.linear_defs(d, hk * hd, lc, "embed", "kv_heads"),
        "wo": lora.linear_defs(hq * hd, d, lc, "heads", "embed"),
    }
    if cfg.qk_norm:
        defs["q_norm"] = layers.norm_defs(hd, "rmsnorm", None)
        defs["k_norm"] = layers.norm_defs(hd, "rmsnorm", None)
    if sparse_applicable(cfg):
        defs["pq"] = pq.param_defs(_pq_config(cfg))
    return defs


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               window: Optional[int] = None) -> Dict[str, jax.Array]:
    """Cache sized to the SWA window when present (ring buffer)."""
    size = max_len if window is None else min(max_len, window)
    hk, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    cache = {
        "k": jnp.zeros((batch, hk, size, hd), cfg.dtype),
        "v": jnp.zeros((batch, hk, size, hd), cfg.dtype),
        "slot_pos": jnp.full((batch, size), -1, jnp.int32),
    }
    if sparse_applicable(cfg):
        m = _pq_config(cfg).num_books
        cache["codes"] = jnp.zeros((batch, hk, size, m), jnp.int8)
    return cache


def init_paged_cache(cfg: ModelConfig, num_pages: int) -> Dict[str, jax.Array]:
    """Paged pool layout (serving/kv_pages.py): the per-slot (B, size, ...)
    strips become a global (num_pages, page_size, ...) pool addressed via
    the engine's slot->page table.  Same keys as init_cache so the rest of
    the layer code walks both layouts."""
    ps = cfg.spt.kv_page_size
    hk, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    cache = {
        "k": jnp.zeros((num_pages, hk, ps, hd), cfg.dtype),
        "v": jnp.zeros((num_pages, hk, ps, hd), cfg.dtype),
        "slot_pos": jnp.full((num_pages, ps), -1, jnp.int32),
    }
    if sparse_applicable(cfg):
        m = _pq_config(cfg).num_books
        cache["codes"] = jnp.zeros((num_pages, hk, ps, m), jnp.int8)
    return cache


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int,
                   window: Optional[int] = None) -> Dict[str, jax.ShapeDtypeStruct]:
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        jax.eval_shape(lambda: init_cache(cfg, batch, max_len, window)))


def _project(p: dict, x: jax.Array, lc, heads: int, hd: int,
             axis: str) -> jax.Array:
    y = lora.linear(x, p, lc)
    b, s, _ = y.shape
    y = shard(y, "batch", None, axis)
    y = y.reshape(b, s, heads, hd).transpose(0, 2, 1, 3)
    return shard(y, "batch", axis, None, None)


def _qkv(p: dict, x: jax.Array, kv_x: jax.Array, cfg: ModelConfig,
         pos_q: jax.Array, pos_k: jax.Array, rope: bool
         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    lc = cfg.spt.lora
    hd = cfg.resolved_head_dim
    q = _project(p["wq"], x, lc, cfg.num_heads, hd, "heads")
    k = _project(p["wk"], kv_x, lc, cfg.num_kv_heads, hd, "kv_heads")
    v = _project(p["wv"], kv_x, lc, cfg.num_kv_heads, hd, "kv_heads")
    if cfg.qk_norm:
        q = layers.apply_norm(p["q_norm"], q, "rmsnorm")
        k = layers.apply_norm(p["k_norm"], k, "rmsnorm")
    if rope and cfg.rope_theta is not None:
        q = layers.apply_rope(q, pos_q, cfg.rope_theta)
        k = layers.apply_rope(k, pos_k, cfg.rope_theta)
    return q, k, v


def write_cache(cache: dict, cfg: ModelConfig, p: dict, k: jax.Array,
                v: jax.Array, pos_k: jax.Array) -> dict:
    """pos_k: (S_new,) shared positions, or (B, S_new) per-sequence positions
    (continuous-batching decode, where slots sit at ragged depths)."""
    size = cache["k"].shape[2]
    s_new = k.shape[2]
    if pos_k.ndim == 2:
        b = cache["k"].shape[0]
        slots = (pos_k % size).astype(jnp.int32)          # (B, S_new)
        bidx = jnp.arange(b, dtype=jnp.int32)[:, None]
        new = dict(cache)
        # advanced-index scatter: target view is (B, S_new, Hk, hd)
        new["k"] = cache["k"].at[bidx, :, slots].set(
            k.transpose(0, 2, 1, 3).astype(cache["k"].dtype))
        new["v"] = cache["v"].at[bidx, :, slots].set(
            v.transpose(0, 2, 1, 3).astype(cache["v"].dtype))
        new["slot_pos"] = cache["slot_pos"].at[bidx, slots].set(
            pos_k.astype(jnp.int32))
        if "codes" in cache:
            codes = pq.assign(k, p["pq"]["codebooks"])    # (B, Hk, S_new, M)
            new["codes"] = cache["codes"].at[bidx, :, slots].set(
                codes.transpose(0, 2, 1, 3).astype(jnp.int8))
        return new
    if s_new > size:
        k, v, pos_k = k[:, :, -size:], v[:, :, -size:], pos_k[-size:]
        s_new = size
    slots = (pos_k % size).astype(jnp.int32)
    new = dict(cache)
    new["k"] = cache["k"].at[:, :, slots].set(k.astype(cache["k"].dtype))
    new["v"] = cache["v"].at[:, :, slots].set(v.astype(cache["v"].dtype))
    b = cache["slot_pos"].shape[0]
    # The barrier keeps this scatter's updates a different instruction from
    # its indices: for one row from position 0 XLA folds both to the same
    # iota, and the TPU compiler then aborts while fusing the scatter
    # (scatter_emitter "operand_indices.size() == 1").
    new["slot_pos"] = cache["slot_pos"].at[:, slots].set(
        jnp.broadcast_to(jax.lax.optimization_barrier(pos_k)[None],
                         (b, s_new)).astype(jnp.int32))
    if "codes" in cache:
        codes = pq.assign(k, p["pq"]["codebooks"])        # (B, Hk, S_new, M)
        new["codes"] = cache["codes"].at[:, :, slots].set(
            codes.astype(jnp.int8))
    return new


def write_cache_paged(cache: dict, cfg: ModelConfig, p: dict, k: jax.Array,
                      v: jax.Array, pos: jax.Array,
                      page_table: jax.Array) -> dict:
    """Decode-time paged scatter: one new token per slot at absolute
    position ``pos`` (B,), routed to physical page page_table[b, pos//ps]
    row pos%ps.  Slots whose page is unallocated (retired slots decoding
    dead air inside a chunk) drop the write."""
    from repro.serving import kv_pages
    ps = cache["k"].shape[2]
    pos = pos.astype(jnp.int32)
    new = dict(cache)
    new["k"] = kv_pages.scatter_row(cache["k"], page_table, pos,
                                    k[:, :, 0], ps)
    new["v"] = kv_pages.scatter_row(cache["v"], page_table, pos,
                                    v[:, :, 0], ps)
    new["slot_pos"] = kv_pages.scatter_row(cache["slot_pos"], page_table,
                                           pos, pos, ps)
    if "codes" in cache:
        codes = pq.assign(k, p["pq"]["codebooks"])        # (B, Hk, 1, M)
        new["codes"] = kv_pages.scatter_row(cache["codes"], page_table,
                                            pos, codes[:, :, 0], ps)
    return new


def kv_valid_mask(cache: dict, q_pos: jax.Array,
                  window: Optional[int]) -> jax.Array:
    """(B, S) — slot holds a token visible to a query at q_pos (per batch)."""
    sp = cache["slot_pos"]                                # (B, S)
    q = jnp.reshape(q_pos, (-1, 1))
    ok = (sp >= 0) & (sp <= q)
    if window is not None:
        ok &= sp > q - window
    return ok


def attend(p: dict, cfg: ModelConfig, q: jax.Array, k: jax.Array,
           v: jax.Array, causal: bool, window: Optional[int],
           q_offset: int = 0, seq_lengths: Optional[jax.Array] = None
           ) -> Tuple[jax.Array, dict]:
    """Full-sequence attention (train/prefill), sparse or dense.

    seq_lengths: per-row real lengths (B,) for batched ragged prefill —
    sparse MHA then selects with each row's exact-length top-L budget.
    The fused prefill kernel has no per-row budgets, so the kernel and
    masked tiers run it in the masked form (the kernel's compute graph in
    XLA, no gathered K/V), the jnp tier in the gather form.  Dense
    attention needs nothing: the causal mask already hides right-pad keys
    from every real query."""
    scale = cfg.resolved_head_dim ** -0.5
    aux: dict = {}
    if sparse_applicable(cfg):
        scfg = _sa_config(cfg)
        impl = cfg.spt.attn_impl
        if impl == "pallas" and kdispatch.kernels_disabled():
            impl = "sparse_jnp"                  # REPRO_DISABLE_KERNELS=1
        if seq_lengths is not None:
            ragged = (sa.sparse_mha_masked
                      if impl in ("pallas", "sparse_masked")
                      else sa.sparse_mha)
            out, aux = ragged(q, k, v, p["pq"]["codebooks"], scfg, scale,
                              causal=causal, window=window,
                              q_offset=q_offset, seq_lengths=seq_lengths)
        elif impl == "pallas":
            from repro.kernels.sparse_attention import ops as sa_ops
            out, aux = sa_ops.sparse_mha(q, k, v, p["pq"]["codebooks"], scfg,
                                         scale, causal=causal, window=window,
                                         q_offset=q_offset)
        elif impl == "sparse_masked":
            out, aux = sa.sparse_mha_masked(q, k, v, p["pq"]["codebooks"],
                                            scfg, scale, causal=causal,
                                            window=window, q_offset=q_offset)
        else:
            out, aux = sa.sparse_mha(q, k, v, p["pq"]["codebooks"], scfg,
                                     scale, causal=causal, window=window,
                                     q_offset=q_offset)
    else:
        out = sa.dense_attention(q, k, v, scale, causal=causal, window=window,
                                 q_offset=q_offset, chunk_q=cfg.spt.chunk_q)
    return out, aux


def _tel_decode_counters(cfg: ModelConfig, valid: jax.Array) -> dict:
    """Jit-pure sparsity counters for one decode step (telemetry layer).

    Derived from the validity mask alone — the decode paths select
    top-L = top_l(mask_width) slots out of the valid ones, so per row
    kept = min(L, n_valid) and eligible = n_valid.  No scores are
    recomputed; cost is one mask reduction per attention layer."""
    n_valid = valid.sum(axis=-1).astype(jnp.float32)          # (B,)
    l = sa.top_l(valid.shape[-1], _sa_config(cfg), None)
    return {"tel_attn_kept": jnp.minimum(float(l), n_valid),
            "tel_attn_elig": n_valid}


def attn_apply(p: dict, x: jax.Array, cfg: ModelConfig, *,
               mode: str = "train", causal: bool = True,
               window: Optional[int] = None,
               cache: Optional[dict] = None,
               pos: Optional[jax.Array] = None,
               kv_x: Optional[jax.Array] = None,
               rope: bool = True,
               kv_valid: Optional[jax.Array] = None,
               page_table: Optional[jax.Array] = None,
               seq_lengths: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, Optional[dict], dict]:
    """Returns (y, new_cache, aux).  x: (B, S, d_model).

    pos: absolute position of x[:, 0] — a scalar when batches are aligned,
    or a (B,) vector when serving slots sit at ragged depths.
    seq_lengths: train/prefill only — per-row real lengths (B,) of a
    right-padded ragged batch (see ``attend``).
    kv_x: source for K/V (cross-attention); defaults to x.
    kv_valid: decode-mode only — a caller-tracked (B, cache_size) slot
    validity mask (the serving engine derives it once per step from slot
    positions); when absent, or for ring-buffer SWA caches whose slot
    semantics the caller can't see, it is recomputed from the cache's
    slot_pos.
    page_table: decode-mode only — (B, max_pages) int32 slot->page map
    signalling that ``cache`` is a paged pool (serving/kv_pages.py).
    Ring-buffer SWA caches ignore it (they are already window-bounded).
    """
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    lc = cfg.spt.lora
    start = jnp.asarray(0 if pos is None else pos, jnp.int32)
    if start.ndim == 1:
        pos_q = start[:, None] + jnp.arange(s, dtype=jnp.int32)   # (B, s)
    else:
        pos_q = start + jnp.arange(s, dtype=jnp.int32)            # (s,)
    kv_src = x if kv_x is None else kv_x
    pos_k = (jnp.arange(kv_src.shape[1], dtype=jnp.int32)
             if kv_x is not None else pos_q)
    q, k, v = _qkv(p, x, kv_src, cfg, pos_q, pos_k, rope)
    aux: dict = {}
    new_cache = cache

    if mode in ("train", "prefill"):
        out, aux = attend(p, cfg, q, k, v, causal, window,
                          seq_lengths=seq_lengths)
        if mode == "prefill":
            assert cache is not None
            new_cache = write_cache(cache, cfg, p, k, v, pos_k)
    elif mode == "decode" and page_table is not None and window is None:
        # paged pool: scatter the new token into its slot's page, then
        # attend.  Kernel tier: the decode kernels address (page_id,
        # offset) tiles straight out of the pools via the scalar-
        # prefetched page table — no gathered per-slot view exists.
        # Fallback tier (jnp oracle, kill switch, or a caller without a
        # view-coordinate validity mask): models/paged_fallback.py
        # gathers the view and runs the contiguous decode paths over it.
        from repro.models import paged_fallback
        assert cache is not None and pos is not None
        pos_b = jnp.broadcast_to(start, (b,)).astype(jnp.int32)
        new_cache = write_cache_paged(cache, cfg, p, k, v, pos_b, page_table)
        ps = new_cache["k"].shape[2]
        s_view = page_table.shape[1] * ps
        scale = hd ** -0.5
        sparse = sparse_applicable(cfg)
        engine_valid = kv_valid is not None and kv_valid.shape[-1] == s_view
        if sparse and engine_valid and kdispatch.use_telemetry_counters(cfg):
            aux.update(_tel_decode_counters(cfg, kv_valid))
        native = (engine_valid and kdispatch.use_paged_native_decode(cfg)
                  and (not sparse or kdispatch.use_sparse_decode_kernel(cfg)))
        if native:
            from repro.kernels.sparse_attention import ops as sa_ops
            if sparse:
                out = sa_ops.sparse_mha_decode_paged(
                    q, new_cache["k"], new_cache["v"], new_cache["codes"],
                    p["pq"]["codebooks"], _sa_config(cfg), scale, kv_valid,
                    page_table)
            else:
                out = sa_ops.dense_mha_decode_paged(
                    q, new_cache["k"], new_cache["v"], scale, kv_valid,
                    page_table)
        else:
            out = paged_fallback.decode_attend_gathered(
                p, cfg, q, new_cache, page_table, pos_b, kv_valid, scale)
    elif mode == "decode":
        assert cache is not None and pos is not None
        new_cache = write_cache(cache, cfg, p, k, v, pos_q)
        size = new_cache["k"].shape[2]
        if (kv_valid is not None and window is None
                and kv_valid.shape[-1] == size):
            valid = kv_valid                              # engine-tracked
        else:
            valid = kv_valid_mask(new_cache, start, window)   # (B, S_cache)
        scale = hd ** -0.5
        if sparse_applicable(cfg):
            if kdispatch.use_telemetry_counters(cfg):
                aux.update(_tel_decode_counters(cfg, valid))
            if kdispatch.use_sparse_decode_kernel(cfg):
                from repro.kernels.sparse_attention import ops as sa_ops
                out = sa_ops.sparse_mha_decode(
                    q, new_cache["k"], new_cache["v"], new_cache["codes"],
                    p["pq"]["codebooks"], _sa_config(cfg), scale, valid,
                    fuse=kdispatch.use_fused_decode_attn(cfg))
            else:
                out = sa.sparse_mha_decode(
                    q, new_cache["k"], new_cache["v"], new_cache["codes"],
                    p["pq"]["codebooks"], _sa_config(cfg), scale, valid)
        else:
            out = sa.dense_attention(q, new_cache["k"], new_cache["v"], scale,
                                     causal=False, kv_valid=valid, chunk_q=1)
    else:
        raise ValueError(mode)

    out = out.transpose(0, 2, 1, 3).reshape(b, s, cfg.num_heads * hd)
    out = shard(out, "batch", None, "heads")
    y = lora.linear(out, p["wo"], lc)
    return shard(y, "batch", None, None), new_cache, aux
