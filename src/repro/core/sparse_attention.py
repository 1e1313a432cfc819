"""Sparse multi-head attention (paper §4.1, Algorithm 1).

Pipeline per attention layer:
  1. quantize Q and K with the layer's PQ codebooks      (core/pq.py)
  2. integer match-count scores s(q,k) in [0, M]          (Eq. 6)
  3. select the top-L keys per query under the attention
     mask (causal and/or sliding-window)
  4. attention restricted to the selected pairs, softmax
     renormalized over the L selected keys               (revised softmax)

TPU adaptation (DESIGN.md §2): the GPU CSR SDDMM/SpMM pair becomes a
fixed-L gather + dense MXU compute.  The selection is *exactly L per row*
(structurally rectangular sparsity), so the (n, L) index matrix is the CSR
``Indices`` array with an implicit ``Indptr = [0, L, 2L, ...]``.

Canonical tie-break (shared with the Pallas kernels so index sets match
bit-exactly): prefer higher score, then the more recent key (higher index).

Everything here is pure jnp — memory-bounded by chunking the query axis —
and doubles as the oracle for kernels/sparse_attention.  The fused Pallas
kernel is selected with attn_impl="pallas" in the model layer.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import pq


@dataclasses.dataclass(frozen=True)
class SparseAttentionConfig:
    pq: pq.PQConfig
    top_fraction: float = 0.125    # L = top_fraction * n (paper default 1/8)
    min_l: int = 16                # floor so tiny test shapes stay sane
    pad_l_to: int = 1              # pad L up (128 on TPU for MXU alignment)
    chunk_q: int = 256             # query-chunk for score/gather streaming
    select_granularity: str = "qhead"  # "qhead" (faithful) | "kvgroup" (GQA opt)
    qerr_loss_weight: float = 0.0  # optional DKM quantization-error aux loss


def top_l(seq_len: int, cfg: SparseAttentionConfig,
          window: Optional[int] = None) -> int:
    """L for a given sequence length (bounded by the SWA window if any)."""
    horizon = seq_len if window is None else min(seq_len, window)
    l = max(cfg.min_l, int(round(horizon * cfg.top_fraction)))
    l = -(-l // cfg.pad_l_to) * cfg.pad_l_to
    return min(l, horizon)


def top_l_dyn(horizon: jax.Array, cfg: SparseAttentionConfig,
              window: Optional[int] = None) -> jax.Array:
    """Traced counterpart of ``top_l`` for per-row lengths (B,) int32 —
    batched ragged prefill gives every row the selection budget its exact
    length would have had.  Matches the host formula bit-for-bit when
    ``top_fraction`` is exactly representable in float32 (jnp.round and
    Python round are both half-to-even); every config in the repo uses
    dyadic fractions."""
    h = jnp.asarray(horizon, jnp.int32)
    if window is not None:
        h = jnp.minimum(h, window)
    l = jnp.maximum(cfg.min_l, jnp.round(
        h.astype(jnp.float32) * cfg.top_fraction).astype(jnp.int32))
    l = -(-l // cfg.pad_l_to) * cfg.pad_l_to
    return jnp.minimum(l, h)


def _combined_score(scores: jax.Array, key_pos: jax.Array,
                    mask: jax.Array, nk: int) -> jax.Array:
    """Fold the tie-break into one sortable f32: score*nk + key_index.
    Exact for score*nk + j < 2^24 (checked by callers' shapes)."""
    comb = scores * float(nk) + key_pos.astype(jnp.float32)
    neg = jnp.asarray(-1.0, jnp.float32)  # any masked value < 0 works
    return jnp.where(mask, comb, neg)


def select_topl(scores: jax.Array, l: int, mask: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """Top-L selection with canonical tie-break (sort-based reference).

    scores: (..., nq, nk) f32 integer-valued; mask: (..., nq, nk) bool
    returns indices (..., nq, L) int32, valid (..., nq, L) bool
    """
    nk = scores.shape[-1]
    key_pos = jnp.arange(nk, dtype=jnp.int32)
    comb = _combined_score(scores, key_pos, mask, nk)
    top, idx = jax.lax.top_k(comb, l)
    return idx.astype(jnp.int32), top >= 0.0


def bucket_select(scores: jax.Array, valid: jax.Array, l: int,
                  max_score: int, l_dyn: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Sort-free top-L: the paper's bucket-sort (Algorithm 3) in vector form.

    scores: (..., nk) integer-valued in [0, max_score]; valid: (..., nk).
    Selected set == select_topl's (score desc, then most-recent key); the
    emitted index order is ascending key position.

    Twice TPU-native: the integer bucket trick avoids float sort (paper's
    GPU motivation) AND stays partition-friendly under SPMD — lax.top_k
    lowers to a sort that forces an all-gather of the (.., nq, nk) score
    tensor (measured: 17 GB/device at grok scale), while this form is
    histograms + cumsums, all elementwise along the key axis.

    l_dyn: optional traced budgets broadcastable to scores.shape[:-1]
    (e.g. (B, 1, 1) per-row budgets for ragged prefill); must be <= l,
    which stays the static output width.
    Returns (idx (..., L) int32 ascending, sel_valid (..., L) bool).
    """
    s = jnp.where(valid, scores.astype(jnp.int32), -1)
    nk = s.shape[-1]
    budget = jnp.asarray(l if l_dyn is None else l_dyn, jnp.int32)
    counts = jnp.stack([jnp.sum((s == v).astype(jnp.int32), axis=-1)
                        for v in range(max_score + 1)], axis=-1)
    ge = jnp.cumsum(counts[..., ::-1], axis=-1)[..., ::-1]  # #(s >= v)
    meets = (ge >= budget[..., None]).astype(jnp.int32)  # non-increasing in v
    t = jnp.maximum(jnp.sum(meets, axis=-1) - 1, 0)         # threshold bucket
    ge_pad = jnp.concatenate([ge, jnp.zeros_like(ge[..., :1])], axis=-1)
    n_above = jnp.take_along_axis(ge_pad, (t + 1)[..., None], axis=-1)[..., 0]
    need_at_t = budget - n_above
    above = s > t[..., None]
    at_t = s == t[..., None]
    rev_rank = jnp.cumsum(at_t[..., ::-1].astype(jnp.int32),
                          axis=-1)[..., ::-1]    # 1 = most recent tie
    eligible = above | (at_t & (rev_rank <= need_at_t[..., None]))
    cs = jnp.cumsum(eligible.astype(jnp.int32), axis=-1)
    n_sel = cs[..., -1]
    # Compact eligible positions into L slots WITHOUT a scatter: slot i holds
    # the (i+1)-th set bit of `eligible` = binary search over the cumsum.
    # Batched take_along_axis gathers keep every lead dim sharded (a
    # flatten+scatter formulation materializes a (rows, nk) iota and drops
    # the head sharding — 51 GB/device at grok scale).
    targets = jnp.arange(1, l + 1, dtype=jnp.int32)      # (L,)
    lo = jnp.zeros((*s.shape[:-1], l), jnp.int32)
    hi = jnp.full_like(lo, nk)
    steps = max(1, nk.bit_length())   # ceil(log2(nk + 1)) search iterations
    for _ in range(steps):
        mid = (lo + hi) // 2
        cs_mid = jnp.take_along_axis(cs, jnp.minimum(mid, nk - 1), axis=-1)
        go_right = cs_mid < targets
        lo = jnp.where(go_right, mid + 1, lo)
        hi = jnp.where(go_right, hi, mid)
    idx = jnp.minimum(lo, nk - 1).astype(jnp.int32)
    sel_valid = targets <= n_sel[..., None]
    return idx, sel_valid


def attention_mask(q_pos: jax.Array, k_pos: jax.Array, causal: bool,
                   window: Optional[int]) -> jax.Array:
    """(nq, nk) bool validity mask built from positions (never materialize
    an (n, n) mask at full sequence — callers pass chunked q_pos)."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        m &= k_pos[None, :] > q_pos[:, None] - window
    return m


def attention_from_indices(q: jax.Array, k: jax.Array, v: jax.Array,
                           indices: jax.Array, valid: jax.Array,
                           scale: float) -> jax.Array:
    """Gather-based sparse attention (SDDMM -> softmax -> SpMM analogue).

    q: (B, Hq, nq, d); k, v: (B, Hk, nk, d); Hq = R * Hk
    indices/valid: (B, Hq, nq, L) — key positions per *query head* (the
    layout stays query-head-major throughout so TP sharding of Hq never has
    to split the (Hk, R) product across the mesh axis).
    """
    from repro.sharding import shard
    b, hq, nq, d = q.shape
    _, hk, nk, _ = k.shape
    r = hq // hk
    l = indices.shape[-1]
    # Repeat KV to query heads, then take_along_axis with (B, Hq) as true
    # batch dims: both the forward gather AND its VJP scatter stay batched,
    # so SPMD keeps batch+head sharding in both directions.  (Flattening Hq
    # into the gather row merges a sharded dim and replicates the backward
    # scatter indices — 206 GB/device at grok scale; see §Dry-run calib.)
    k_rep = shard(jnp.repeat(k, r, axis=1), "batch", "heads", None, None)
    v_rep = shard(jnp.repeat(v, r, axis=1), "batch", "heads", None, None)
    flat = indices.reshape(b, hq, nq * l, 1)
    k_sel = jnp.take_along_axis(k_rep, flat, axis=2).reshape(b, hq, nq, l, d)
    v_sel = jnp.take_along_axis(v_rep, flat, axis=2).reshape(b, hq, nq, l, d)
    k_sel = shard(k_sel, "batch", "heads", None, None, None)
    v_sel = shard(v_sel, "batch", "heads", None, None, None)
    logits = jnp.einsum("bhnd,bhnld->bhnl", q, k_sel,
                        preferred_element_type=jnp.float32) * scale
    logits = shard(logits, "batch", "heads", None, None)
    logits = jnp.where(valid, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    w = jnp.where(valid, w, 0.0)                         # all-invalid rows -> 0
    out = jnp.einsum("bhnl,bhnld->bhnd", w.astype(v_sel.dtype), v_sel)
    return shard(out, "batch", "heads", None, None)


def sparse_mha(q: jax.Array, k: jax.Array, v: jax.Array,
               codebooks: jax.Array, cfg: SparseAttentionConfig,
               scale: float, causal: bool = True,
               window: Optional[int] = None,
               q_offset: int = 0,
               seq_lengths: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full Algorithm 1 for a (possibly GQA) attention layer, training form.

    q: (B, Hq, nq, d); k, v: (B, Hk, nk, d).  q_offset is the absolute
    position of q[..., 0, :] (for decode/prefill continuation).

    seq_lengths: optional per-row real lengths (B,) int32 for batched
    ragged prefill — each row's top-L budget is top_l(seq_lengths[b])
    instead of top_l(nk), so a right-padded row selects exactly the set
    its exact-length batch-1 prefill would (the causal mask already hides
    the pad keys from every real query).  The static gather width stays
    top_l(nk) >= every per-row budget.

    Selection, gather, and attention all happen inside one query-chunk loop
    so the live gather buffer is (B, H, chunk, L, d) — the O(n L d) memory
    claim holds chunk-wise (the fused Pallas kernel does the same per tile).
    Returns (out (B, Hq, nq, d), aux{qerr, l}).
    """
    from repro.core.chunking import maybe_map
    b, hq, nq, d = q.shape
    _, hk, nk, _ = k.shape
    r = hq // hk
    l = top_l(nk, cfg, window)
    l_dyn = (None if seq_lengths is None
             else top_l_dyn(seq_lengths, cfg, window).reshape(b, 1, 1))
    codes_q = pq.assign(q, codebooks)                    # (B, Hq, nq, M)
    codes_k = pq.assign(k, codebooks)                    # (B, Hk, nk, M)
    k_pos = jnp.arange(nk, dtype=jnp.int32)

    from repro.sharding import shard

    def chunk_fn(start):
        q_pos = q_offset + start + jnp.arange(chunk, dtype=jnp.int32)
        mask = attention_mask(q_pos, k_pos, causal, window)   # (chunk, nk)
        if cfg.select_granularity == "kvgroup":
            # one selection per kv head, reused by its R query heads
            cqc = jax.lax.dynamic_slice_in_dim(
                codes_q, start, chunk, axis=2).reshape(b, hk, r, chunk, -1)
            s = pq.match_scores(cqc, codes_k[:, :, None],
                                cfg.pq.num_codewords)
            s = jnp.sum(s, axis=2)                       # (B, Hk, chunk, nk)
            s = shard(s, "batch", "kv_heads", None, None)
        else:
            cqc = jax.lax.dynamic_slice_in_dim(codes_q, start, chunk, axis=2)
            ckq = jnp.repeat(codes_k, r, axis=1)         # (B, Hq, nk, M) int
            ckq = shard(ckq, "batch", "heads", None, None)
            s = pq.match_scores(cqc, ckq, cfg.pq.num_codewords)
            s = shard(s, "batch", "heads", None, None)
        max_s = cfg.pq.num_books * (r if cfg.select_granularity == "kvgroup"
                                    else 1)
        idx, vld = bucket_select(s, mask[None, None], l, max_s, l_dyn=l_dyn)
        if cfg.select_granularity == "kvgroup":
            idx = jnp.repeat(idx, r, axis=1)             # broadcast to q heads
            vld = jnp.repeat(vld, r, axis=1)
        qc = jax.lax.dynamic_slice_in_dim(q, start, chunk, axis=2)
        return attention_from_indices(qc, k, v, idx, vld, scale)

    chunk = min(cfg.chunk_q, nq)
    if nq % chunk != 0:
        chunk = nq
    starts = jnp.arange(0, nq, chunk)
    # checkpoint: the (chunk, L, d) gathers are recomputed in backward
    # instead of being stacked across all chunks (O(n L d) live, not O(n^2)).
    outs = maybe_map(jax.checkpoint(chunk_fn, prevent_cse=False), starts)
    out = jnp.moveaxis(outs, 0, 2).reshape(b, hq, nq, d)
    aux = {"l": jnp.asarray(l, jnp.int32)}
    if cfg.qerr_loss_weight > 0:
        aux["qerr"] = (pq.quantization_error(q, codebooks, codes_q)
                       + pq.quantization_error(k, codebooks, codes_k))
    return out, aux


def _eligibility(s: jax.Array, valid: jax.Array, l,
                 max_score: int) -> jax.Array:
    """The top-L set as a boolean mask (no indices): threshold bucket +
    most-recent tie budget — the selection semantics of bucket_select in
    mask form.  All ops elementwise along the key axis (partition-friendly).
    l: the budget, an int or traced budgets broadcastable to
    s.shape[:-1] (per-row ragged-prefill budgets, as bucket_select's
    l_dyn)."""
    budget = jnp.asarray(l, jnp.int32)
    sm = jnp.where(valid, s.astype(jnp.int32), -1)
    counts = jnp.stack([jnp.sum((sm == v).astype(jnp.int32), axis=-1)
                        for v in range(max_score + 1)], axis=-1)
    ge = jnp.cumsum(counts[..., ::-1], axis=-1)[..., ::-1]
    t = jnp.maximum(jnp.sum((ge >= budget[..., None]).astype(jnp.int32),
                            axis=-1) - 1, 0)
    ge_pad = jnp.concatenate([ge, jnp.zeros_like(ge[..., :1])], axis=-1)
    n_above = jnp.take_along_axis(ge_pad, (t + 1)[..., None], axis=-1)[..., 0]
    need = (budget - n_above)[..., None]
    above = sm > t[..., None]
    at_t = sm == t[..., None]
    rev_rank = jnp.cumsum(at_t[..., ::-1].astype(jnp.int32),
                          axis=-1)[..., ::-1]
    return above | (at_t & (rev_rank <= need))


def sparse_mha_masked(q: jax.Array, k: jax.Array, v: jax.Array,
                      codebooks: jax.Array, cfg: SparseAttentionConfig,
                      scale: float, causal: bool = True,
                      window: Optional[int] = None, q_offset: int = 0,
                      seq_lengths: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Fused-kernel-equivalent execution (and its roofline analysis proxy):
    the top-L set is applied as a MASK on dense per-chunk logits — no (n, L)
    index matrix, no gathered K/V copies.  Selection semantics are identical
    to sparse_mha/bucket_select (seq_lengths as there); HBM traffic per
    chunk is O(chunk * nk) instead of O(chunk * L * d) for the gather form
    (~d/8x less at L=n/8), and its gradient is dense matmuls where the
    gather form's is a scatter-add.  The Pallas kernel additionally skips
    ineligible key tiles on the MXU, which XLA cannot express here — so
    this form's *compute* term is an upper bound on the kernel's."""
    from repro.core.chunking import maybe_map
    from repro.sharding import shard
    b, hq, nq, d = q.shape
    _, hk, nk, _ = k.shape
    r = hq // hk
    l = top_l(nk, cfg, window)
    if seq_lengths is not None:
        l = top_l_dyn(seq_lengths, cfg, window).reshape(b, 1, 1)
    codes_q = pq.assign(q, codebooks)
    codes_k = pq.assign(k, codebooks)
    ckq = shard(jnp.repeat(codes_k, r, axis=1), "batch", "heads", None, None)
    k_rep = shard(jnp.repeat(k, r, axis=1), "batch", "heads", None, None)
    v_rep = shard(jnp.repeat(v, r, axis=1), "batch", "heads", None, None)
    k_pos = jnp.arange(nk, dtype=jnp.int32)

    def chunk_fn(start):
        q_pos = q_offset + start + jnp.arange(chunk, dtype=jnp.int32)
        mask = attention_mask(q_pos, k_pos, causal, window)
        cqc = jax.lax.dynamic_slice_in_dim(codes_q, start, chunk, axis=2)
        s = pq.match_scores(cqc, ckq, cfg.pq.num_codewords)
        s = shard(s, "batch", "heads", None, None)
        if cfg.select_granularity == "kvgroup":
            # one selection per kv head, shared by its R query heads
            sg = jnp.sum(s.reshape(b, hk, r, chunk, nk), axis=2)
            eligible = _eligibility(sg, mask[None, None], l,
                                    cfg.pq.num_books * r)
            eligible = jnp.repeat(eligible, r, axis=1)
        else:
            eligible = _eligibility(s, mask[None, None], l,
                                    cfg.pq.num_books)
        qc = jax.lax.dynamic_slice_in_dim(q, start, chunk, axis=2)
        logits = jnp.einsum("bhnd,bhmd->bhnm", qc, k_rep,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(eligible, logits, -jnp.inf)
        w = jax.nn.softmax(logits, axis=-1)
        w = jnp.where(eligible, w, 0.0)
        return jnp.einsum("bhnm,bhmd->bhnd", w.astype(v.dtype), v_rep)

    chunk = min(cfg.chunk_q, nq)
    if nq % chunk != 0:
        chunk = nq
    starts = jnp.arange(0, nq, chunk)
    outs = maybe_map(jax.checkpoint(chunk_fn, prevent_cse=False), starts)
    out = jnp.moveaxis(outs, 0, 2).reshape(b, hq, nq, d)
    aux = {"l": jnp.asarray(l, jnp.int32)}
    if cfg.qerr_loss_weight > 0:
        aux["qerr"] = (pq.quantization_error(q, codebooks, codes_q)
                       + pq.quantization_error(k, codebooks, codes_k))
    return out, aux


def _decode_attention_from_indices(q: jax.Array, k: jax.Array, v: jax.Array,
                                   indices: jax.Array, valid: jax.Array,
                                   scale: float) -> jax.Array:
    """Single-token gather attention, grouped by kv head so no (B, Hq, S, d)
    repeat of the cache ever materializes (the train-time
    attention_from_indices repeats KV for SPMD scatter reasons that don't
    apply to the inference-only decode path).

    q: (B, Hq, 1, d); k, v: (B, Hk, S, d); indices/valid: (B, Hsel, 1, L)
    with Hsel = Hk ("kvgroup" shared selection) or Hq (per-head).
    """
    from repro.sharding import shard
    b, hq, _, d = q.shape
    _, hk, _, _ = k.shape
    r = hq // hk
    l = indices.shape[-1]
    rsel = indices.shape[1] // hk                        # 1 | R
    idx = indices.reshape(b, hk, rsel * l, 1)
    k_sel = jnp.take_along_axis(k, idx, axis=2).reshape(b, hk, rsel, l, d)
    v_sel = jnp.take_along_axis(v, idx, axis=2).reshape(b, hk, rsel, l, d)
    k_sel = shard(k_sel, "batch", "kv_heads", None, None, None)
    v_sel = shard(v_sel, "batch", "kv_heads", None, None, None)
    qg = q.reshape(b, hk, r, d)
    vld = valid.reshape(b, hk, rsel, l)
    if rsel == 1:                                        # selection shared by
        k_sel, v_sel = k_sel[:, :, 0], v_sel[:, :, 0]    # the group's R heads
        logits = jnp.einsum("bgrd,bgld->bgrl", qg, k_sel,
                            preferred_element_type=jnp.float32) * scale
    else:
        logits = jnp.einsum("bgrd,bgrld->bgrl", qg, k_sel,
                            preferred_element_type=jnp.float32) * scale
    logits = jnp.where(vld, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    w = jnp.where(vld, w, 0.0)                           # all-invalid rows -> 0
    eq = "bgrl,bgld->bgrd" if rsel == 1 else "bgrl,bgrld->bgrd"
    out = jnp.einsum(eq, w.astype(v_sel.dtype), v_sel)
    return shard(out.reshape(b, hq, 1, d), "batch", "heads", None, None)


def sparse_mha_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                      codes_cache: jax.Array, codebooks: jax.Array,
                      cfg: SparseAttentionConfig, scale: float,
                      kv_valid: jax.Array) -> jax.Array:
    """One-token decode: select top-L over the cached keys' codes.

    q: (B, Hq, 1, d); caches: (B, Hk, S, d); codes_cache: (B, Hk, S, M)
    kv_valid: (B, S) bool — which cache slots participate (covers both plain
    causal caches and ring-buffer sliding-window caches).

    This is the jnp fallback and the parity oracle for the fused Pallas
    decode kernel (kernels/sparse_attention/ops.sparse_mha_decode).  All
    GQA broadcasting is by reshape — no cache tensor is jnp.repeat-ed
    across query heads, so the fallback stays usable at long S.
    """
    b, hq, _, d = q.shape
    _, hk, s, _ = k_cache.shape
    r = hq // hk
    l = top_l(s, cfg, None)
    codes_q = pq.assign(q, codebooks)                    # (B, Hq, 1, M)
    ck = codes_cache.astype(jnp.int32)                   # (B, Hk, S, M)
    cq = codes_q.reshape(b, hk, r, 1, -1)
    scores = pq.match_scores(cq, ck[:, :, None], cfg.pq.num_codewords)
    if cfg.select_granularity == "kvgroup":
        scores = jnp.sum(scores, axis=2)                 # (B, Hk, 1, S)
    else:
        scores = scores.reshape(b, hq, 1, s)             # (B, Hq, 1, S)
    valid = kv_valid[:, None, None, :]                   # (B, 1, 1, S)
    max_s = cfg.pq.num_books * (r if cfg.select_granularity == "kvgroup"
                                else 1)
    idx, vld = bucket_select(scores, valid, l, max_s)
    return _decode_attention_from_indices(q, k_cache, v_cache, idx, vld,
                                          scale)


def sparse_mha_decode_masked(q: jax.Array, k_cache: jax.Array,
                             v_cache: jax.Array, codes_cache: jax.Array,
                             codebooks: jax.Array,
                             cfg: SparseAttentionConfig, scale: float,
                             kv_valid: jax.Array) -> jax.Array:
    """Fused-kernel-equivalent decode execution: the top-L set is applied as
    a MASK on grouped dense logits — no (1, L) index row, no gathered K/V,
    no bucket_select compaction.  Same selection semantics as
    sparse_mha_decode; this is the XLA-executable stand-in for the Pallas
    decode kernel's compute graph (benchmarks/decode_attention.py) — the
    kernel additionally skips ineligible key tiles and never writes the
    (S,) score row to HBM."""
    b, hq, _, d = q.shape
    _, hk, s, _ = k_cache.shape
    r = hq // hk
    l = top_l(s, cfg, None)
    codes_q = pq.assign(q, codebooks)
    ck = codes_cache.astype(jnp.int32)
    cq = codes_q.reshape(b, hk, r, 1, -1)
    scores = pq.match_scores(cq, ck[:, :, None], cfg.pq.num_codewords)
    valid = kv_valid[:, None, None, None, :]             # (B, 1, 1, 1, S)
    if cfg.select_granularity == "kvgroup":
        ssum = jnp.sum(scores, axis=2, keepdims=True)    # (B, Hk, 1, 1, S)
        eligible = _eligibility(ssum, valid, l, cfg.pq.num_books * r)
    else:
        eligible = _eligibility(scores, valid, l, cfg.pq.num_books)
    qg = q.reshape(b, hk, r, 1, d)
    logits = jnp.einsum("bgrnd,bgsd->bgrns", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(eligible, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    w = jnp.where(eligible, w, 0.0)
    out = jnp.einsum("bgrns,bgsd->bgrnd", w.astype(v_cache.dtype), v_cache)
    return out.reshape(b, hq, 1, d)


def dense_attention(q: jax.Array, k: jax.Array, v: jax.Array, scale: float,
                    causal: bool = True, window: Optional[int] = None,
                    q_offset: int = 0, kv_valid: Optional[jax.Array] = None,
                    chunk_q: int = 512) -> jax.Array:
    """Dense (Full/LoRA baseline) attention, query-chunked so the (n, n)
    weight matrix never materializes at once.  GQA-aware.
    kv_valid: optional (B, nk) bool for decode-style masking."""
    b, hq, nq, d = q.shape
    _, hk, nk, _ = k.shape
    r = hq // hk
    qf = q.reshape(b, hk, r, nq, d)
    k_pos = jnp.arange(nk, dtype=jnp.int32)

    def chunk_fn(start):
        qc = jax.lax.dynamic_slice_in_dim(qf, start, chunk, axis=3)
        q_pos = q_offset + start + jnp.arange(chunk, dtype=jnp.int32)
        mask = attention_mask(q_pos, k_pos, causal, window)   # (chunk, nk)
        if kv_valid is not None:
            mask = mask[None] & kv_valid[:, None, :]          # (B, chunk, nk)
            mask = mask[:, None, None]                        # (B,1,1,chunk,nk)
        logits = jnp.einsum("bgrnd,bgmd->bgrnm", qc, k,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(mask, logits, -jnp.inf)
        w = jax.nn.softmax(logits, axis=-1)
        w = jnp.where(jnp.isfinite(logits).any(-1, keepdims=True), w, 0.0)
        return jnp.einsum("bgrnm,bgmd->bgrnd", w.astype(v.dtype), v)

    chunk = min(chunk_q, nq)
    if nq % chunk != 0:
        chunk = nq
    starts = jnp.arange(0, nq, chunk)
    from repro.core.chunking import maybe_map
    outs = maybe_map(chunk_fn, starts)                   # (nc, b, hk, r, chunk, d)
    out = jnp.moveaxis(outs, 0, 3).reshape(b, hk, r, nq, d)
    return out.reshape(b, hq, nq, d)


def selection_recall(q: jax.Array, k: jax.Array, codebooks: jax.Array,
                     cfg: SparseAttentionConfig, causal: bool = True,
                     window: Optional[int] = None) -> jax.Array:
    """Diagnostic (paper §4.1 reports ~90%): fraction of the true top-L
    q.k pairs that PQ selection recovers.  O(n^2) — small shapes only."""
    b, hq, nq, d = q.shape
    _, hk, nk, _ = k.shape
    r = hq // hk
    l = top_l(nk, cfg, window)
    q_pos = jnp.arange(nq, dtype=jnp.int32)
    k_pos = jnp.arange(nk, dtype=jnp.int32)
    mask = attention_mask(q_pos, k_pos, causal, window)
    k_rep = jnp.repeat(k, r, axis=1)                     # (B, Hq, nk, d)
    exact = jnp.einsum("bhnd,bhmd->bhnm", q, k_rep,
                       preferred_element_type=jnp.float32)
    exact = jnp.where(mask, exact, -jnp.inf)
    true_top, true_idx = jax.lax.top_k(exact, l)
    codes_q = pq.assign(q, codebooks)
    codes_k = pq.assign(k, codebooks)
    s = pq.match_scores(codes_q.reshape(b, hk, r, nq, -1),
                        codes_k[:, :, None], cfg.pq.num_codewords)
    s = s.reshape(b, hq, nq, nk)
    comb = _combined_score(s, k_pos, mask, nk)
    sel_top, sel_idx = jax.lax.top_k(comb, l)
    true_ok = jnp.isfinite(true_top)[..., None]
    sel_ok = (sel_top >= 0.0)[..., None]
    true_sets = jnp.minimum(
        (jax.nn.one_hot(true_idx, nk, dtype=jnp.float32) * true_ok).sum(-2), 1.0)
    sel_sets = jnp.minimum(
        (jax.nn.one_hot(sel_idx, nk, dtype=jnp.float32) * sel_ok).sum(-2), 1.0)
    inter = jnp.sum(true_sets * sel_sets, axis=-1)
    denom = jnp.minimum(jnp.sum(mask, -1), l).astype(jnp.float32)
    denom = jnp.broadcast_to(denom, inter.shape)
    return jnp.mean(jnp.where(denom > 0, inter / jnp.maximum(denom, 1.0), 1.0))
