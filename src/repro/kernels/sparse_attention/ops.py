"""Public fused sparse MHA ops: Pallas forward, jnp backward.

Train/prefill (`sparse_mha`): pq_assign kernel + bucket-histogram kernel +
fused attention kernel.  Backward differentiates the masked jnp form
(``core.sparse_attention.sparse_mha_masked``), which selects the identical
top-L set (the same PQ codes, both computing distances in float32 at
HIGHEST; the same integer thresholds and tie rule), so the gradient is
consistent with the fused forward up to float associativity — the same
contract the paper's unit tests check (§A.2, Figure 11).  The masked form
and not the gather form: the gather's gradient scatter-adds every selected
(query, key) pair's K and V rows, (B, Hq, n, L, d) of them, where the
masked form's is dense matmuls.

Serving decode (`sparse_mha_decode`): decode-threshold kernel + fused
single-token attention kernel over the KV cache; inference-only, no VJP.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import pq
from repro.core import sparse_attention as sa
from repro.kernels import resolve_interpret
from repro.kernels.pq_quantize.ops import pq_assign
from repro.kernels.sparse_attention.sparse_attention import (
    dense_decode_attention_paged_kernel, fused_sparse_decode_attention_kernel,
    fused_sparse_decode_attention_paged_kernel, sparse_attention_kernel,
    sparse_decode_attention_kernel)
from repro.kernels.topl_select.topl_select import (
    decode_topl_thresholds_kernel, topl_thresholds_kernel)


def _fused_forward(q, k, v, codebooks, cfg: sa.SparseAttentionConfig,
                   scale, causal, window, q_offset, interpret):
    b, hq, nq, dh = q.shape
    _, hk, nk, _ = k.shape
    r = hq // hk
    l = sa.top_l(nk, cfg, window)
    codes_q = pq_assign(q, codebooks, interpret=interpret)
    codes_k = pq_assign(k, codebooks, interpret=interpret)
    qf = q.reshape(b * hq, nq, dh)
    kf = k.reshape(b * hk, nk, dh)
    vf = v.reshape(b * hk, nk, dh)
    cqf = codes_q.reshape(b * hq, nq, -1)
    ckf = codes_k.reshape(b * hk, nk, -1)

    def kv_map(g):  # q group (b*Hq + h) -> kv group (b*Hk + h // r)
        return (g // hq) * hk + (g % hq) // r

    # PQ codes per q-head against its kv head's codes -> thresholds
    ck_for_q = jnp.repeat(codes_k, r, axis=1).reshape(b * hq, nk, -1)
    thr = topl_thresholds_kernel(
        cqf, ck_for_q, l=l, max_score=cfg.pq.num_books, causal=causal,
        window=window, q_offset=q_offset, tile_q=min(cfg.chunk_q, nq),
        interpret=interpret)
    out = sparse_attention_kernel(
        qf, kf, vf, cqf, ckf, thr, scale=scale, causal=causal, window=window,
        q_offset=q_offset, kv_map=kv_map, tile_q=min(cfg.chunk_q, nq),
        interpret=interpret)
    return out.reshape(b, hq, nq, dh)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _sparse_mha_op(q, k, v, codebooks, cfg, scale, causal, window, q_offset,
                   interpret):
    return _fused_forward(q, k, v, codebooks, cfg, scale, causal, window,
                          q_offset, interpret)


def _fwd(q, k, v, codebooks, cfg, scale, causal, window, q_offset, interpret):
    out = _fused_forward(q, k, v, codebooks, cfg, scale, causal, window,
                         q_offset, interpret)
    return out, (q, k, v, codebooks)


def _bwd(cfg, scale, causal, window, q_offset, interpret, res, g):
    q, k, v, codebooks = res

    def ref(q_, k_, v_, cb_):
        out, _ = sa.sparse_mha_masked(q_, k_, v_, cb_, cfg, scale,
                                      causal=causal, window=window,
                                      q_offset=q_offset)
        return out

    _, vjp = jax.vjp(ref, q, k, v, codebooks)
    return vjp(g)


_sparse_mha_op.defvjp(_fwd, _bwd)


def sparse_mha(q, k, v, codebooks, cfg: sa.SparseAttentionConfig,
               scale: float, causal: bool = True,
               window: Optional[int] = None, q_offset: int = 0,
               interpret: Optional[bool] = None
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Drop-in replacement for core.sparse_attention.sparse_mha.

    interpret=None derives from the backend (resolved here, before the
    custom_vjp, so forward and backward agree on the mode)."""
    out = _sparse_mha_op(q, k, v, codebooks, cfg, scale, causal, window,
                         q_offset, resolve_interpret(interpret))
    aux = {"l": jnp.asarray(sa.top_l(k.shape[2], cfg, window), jnp.int32)}
    if cfg.qerr_loss_weight > 0:
        aux["qerr"] = (pq.quantization_error(q, codebooks)
                       + pq.quantization_error(k, codebooks))
    return out, aux


@functools.partial(jax.jit, static_argnames=("cfg", "scale", "tile_k",
                                             "interpret", "fuse"))
def sparse_mha_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                      codes_cache: jax.Array, codebooks: jax.Array,
                      cfg: sa.SparseAttentionConfig, scale: float,
                      kv_valid: jax.Array, *, tile_k: int = 512,
                      interpret: Optional[bool] = None,
                      fuse: bool = True) -> jax.Array:
    """Drop-in replacement for core.sparse_attention.sparse_mha_decode.

    fuse=True (default): ONE Pallas kernel — grid step 0 derives the
    [t, need] thresholds from the whole code cache (pinned int8 codes
    block, histogram folded tile by tile, same integer math as the
    standalone threshold kernel) straight into VMEM scratch, and the
    attention sweep
    pairs key tiles two-per-step, so the launch count, the thresholds HBM
    round-trip, AND half the grid steps disappear.  fuse=False: the
    original two-pass pipeline (decode-threshold kernel + attention
    kernel), kept as the bisection / fallback tier; both tiers produce
    bit-identical output (they share the attention-tile body and visit
    the same key tiles in the same newest-first order).

    q: (B, Hq, 1, d); caches: (B, Hk, S, d); codes_cache: (B, Hk, S, M);
    kv_valid: (B, S) bool.  Inference-only — no VJP (the jnp fallback stays
    the oracle; tests/test_sparse_decode.py asserts parity).
    interpret=None derives the mode from the backend (compiled on TPU,
    interpreter elsewhere), so the serving path needs no plumbing.

    The 1-token query codes are assigned on the jnp path (O(B*Hq*M*E), far
    below kernel-launch granularity and bit-identical to the fallback's);
    all O(S) work — code matching, threshold histogram, attention — runs in
    Pallas, with the R query heads of each kv group packed on the sublane
    axis so no cache tensor is repeated across query heads.

    A cache length that is not a multiple of tile_k is zero-padded up to
    one — and, on the fused tier, up to an EVEN tile count so the kernel
    can pair tiles (padded slots carry kv_valid=0, which the selection
    treats exactly like any dead slot; dead tiles leave every accumulator
    untouched, so the tiers stay bit-identical across their different pad
    lengths) — keeping the kernels' Tk tiling at arbitrary serving
    max_len.
    """
    interpret = resolve_interpret(interpret)
    b, hq, _, d = q.shape
    _, hk, s, _ = k_cache.shape
    r = hq // hk
    m = codebooks.shape[0]
    l = sa.top_l(s, cfg, None)
    sum_rows = cfg.select_granularity == "kvgroup"
    max_score = cfg.pq.num_books * (r if sum_rows else 1)
    codes_q = pq.assign(q, codebooks)                     # (B, Hq, 1, M)
    cqg = codes_q.reshape(b * hk, r, m)
    ckg = codes_cache.reshape(b * hk, s, m)               # int8 stays int8
    qg = q.reshape(b * hk, r, d)
    kg = k_cache.reshape(b * hk, s, d)
    vg = v_cache.reshape(b * hk, s, d)
    kvv = kv_valid.astype(jnp.int32)                      # (B, S)
    tk = min(tile_k, s)
    ntile = -(-s // tk)
    if fuse and ntile > 1 and ntile % 2:
        ntile += 1          # fused kernel pairs key tiles: even tile count
    pad = ntile * tk - s
    if pad:
        zkv = ((0, 0), (0, pad), (0, 0))
        kg, vg, ckg = (jnp.pad(t, zkv) for t in (kg, vg, ckg))
        kvv = jnp.pad(kvv, ((0, 0), (0, pad)))            # padded -> invalid
    if fuse:
        out = fused_sparse_decode_attention_kernel(
            qg, kg, vg, cqg, ckg, kvv, scale=scale, l=l,
            max_score=max_score, sum_rows=sum_rows, heads_per_batch=hk,
            tile_k=tk, interpret=interpret)
        return out.reshape(b, hq, 1, d)
    thr = decode_topl_thresholds_kernel(
        cqg, ckg, kvv, l=l, max_score=max_score, sum_rows=sum_rows,
        heads_per_batch=hk, tile_k=tk, interpret=interpret)
    out = sparse_decode_attention_kernel(
        qg, kg, vg, cqg, ckg, thr, kvv, scale=scale, sum_rows=sum_rows,
        heads_per_batch=hk, tile_k=tk, interpret=interpret)
    return out.reshape(b, hq, 1, d)


@functools.partial(jax.jit, static_argnames=("cfg", "scale", "tile_k",
                                             "interpret"))
def sparse_mha_decode_paged(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, codes_pool: jax.Array,
                            codebooks: jax.Array,
                            cfg: sa.SparseAttentionConfig, scale: float,
                            kv_valid: jax.Array, page_table: jax.Array, *,
                            tile_k: int = 512,
                            interpret: Optional[bool] = None) -> jax.Array:
    """Paged-pool counterpart of ``sparse_mha_decode``: the fused one-pass
    kernel reads K/V/code tiles straight out of the global page pools via
    the scalar-prefetched page table — no gathered (B, Hk, S, .) view is
    ever built, so per-step HBM traffic drops from pool-gather + kernel
    read to the kernel read alone.

    q: (B, Hq, 1, d); pools: (num_pages, Hk, page_size, .); page_table:
    (B, MP) int32 with -1 = unallocated (clamped to page 0 here — the
    repo-wide convention; those garbage rows carry kv_valid == 0);
    kv_valid: (B, MP*page_size) bool in view coordinates.  The top-L
    budget is computed over the view length, matching the gathered-view
    path exactly; with equal tile_k the output is bit-identical to
    ``sparse_mha_decode`` over ``kv_pages.gather_pages`` views.
    The view length is a page multiple, so no padding is ever needed.
    """
    interpret = resolve_interpret(interpret)
    b, hq, _, d = q.shape
    _, hk, ps, _ = k_pool.shape
    mp = page_table.shape[1]
    view = mp * ps
    r = hq // hk
    m = codebooks.shape[0]
    l = sa.top_l(view, cfg, None)
    sum_rows = cfg.select_granularity == "kvgroup"
    max_score = cfg.pq.num_books * (r if sum_rows else 1)
    codes_q = pq.assign(q, codebooks)                     # (B, Hq, 1, M)
    cqg = codes_q.reshape(b * hk, r, m)
    qg = q.reshape(b * hk, r, d)
    kvv = kv_valid.astype(jnp.int32)                      # (B, MP*ps)
    pt = jnp.maximum(page_table, 0)
    out = fused_sparse_decode_attention_paged_kernel(
        pt, qg, k_pool, v_pool, cqg, codes_pool, kvv, scale=scale, l=l,
        max_score=max_score, sum_rows=sum_rows, heads_per_batch=hk,
        tile_k=tile_k, interpret=interpret)
    return out.reshape(b, hq, 1, d)


@functools.partial(jax.jit, static_argnames=("scale", "tile_k", "interpret"))
def dense_mha_decode_paged(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, scale: float,
                           kv_valid: jax.Array, page_table: jax.Array, *,
                           tile_k: int = 512,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Dense decode attention straight off the paged KV pool (same
    (page_id, offset) scalar-prefetch addressing as the sparse route) —
    online softmax over the valid view slots, GQA query heads packed on
    the sublane axis.  q: (B, Hq, 1, d); pools: (num_pages, Hk, ps, d);
    kv_valid: (B, MP*ps); page_table: (B, MP) int32 (-1 clamped here)."""
    interpret = resolve_interpret(interpret)
    b, hq, _, d = q.shape
    _, hk, _, _ = k_pool.shape
    r = hq // hk
    qg = q.reshape(b * hk, r, d)
    kvv = kv_valid.astype(jnp.int32)                      # (B, MP*ps)
    pt = jnp.maximum(page_table, 0)
    out = dense_decode_attention_paged_kernel(
        pt, qg, k_pool, v_pool, kvv, scale=scale, heads_per_batch=hk,
        tile_k=tile_k, interpret=interpret)
    return out.reshape(b, hq, 1, d)
