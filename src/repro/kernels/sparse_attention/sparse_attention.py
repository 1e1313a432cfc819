"""Fused sparse MHA kernel: SDDMM -> masked softmax -> SpMM in one pass
(paper §5.1), reshaped for the TPU memory hierarchy.

The GPU implementation materializes a CSR attention matrix and calls
cuSPARSE SDDMM/SpMM.  On TPU we fuse *through the selection*: for each
(Tq) query tile the kernel streams (Tk) key/value tiles through VMEM —
newest tile first — computes the integer PQ match scores in VREGs, masks
to the top-L-eligible set using the per-query [threshold, tie-budget] from
the bucket-histogram kernel, and folds the surviving logits into an online
(flash-style) softmax accumulator.  Neither the (n, L) index matrix nor any
gathered K/V copy ever exists: HBM traffic is O(n d) per query tile instead
of O(n L d) for the gather formulation (the measured ~60x memory-term gap
in EXPERIMENTS.md §Perf).

Key-tile skip: a tile with no eligible pair skips its MXU work via pl.when
— with top-1/8 sparsity most off-diagonal tiles skip, which is where the
FLOP-side win appears on real hardware.

Grid: (G, nq/Tq, nk/Tk) with the key axis minor (sequential) and REVERSED
so the most-recent-ties-first budget is consumed in order.
Scratch (VMEM, f32): m (Tq,1), l (Tq,1), acc (Tq, dh), ties taken (Tq,1).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

from repro.kernels.topl_select.topl_select import (
    causal_mask, hist_counts, hist_reduce, lane_row, match_scores,
    suffix_count, tile_positions, vmem)


def _attn_kernel(q_ref, k_ref, v_ref, cq_ref, ck_ref, thr_ref, o_ref,
                 m_ref, l_ref, acc_ref, tie_ref, *,
                 scale, causal, window, q_offset, tq, tk, nkt):
    qi = pl.program_id(1)
    kj = pl.program_id(2)                 # 0 .. nkt-1, tiles visited newest->oldest
    ki = nkt - 1 - kj                     # actual key-tile index

    @pl.when(kj == 0)
    def _init():
        _softmax_init(m_ref, l_ref, acc_ref, tie_ref)

    s = match_scores(cq_ref[0], ck_ref[0])                # (Tq, Tk)
    q_pos, k_pos = tile_positions(q_offset + qi * tq, ki * tk, tq, tk)
    sm = jnp.where(causal_mask(q_pos, k_pos, causal, window), s, -1)
    _attend_tile(sm, thr_ref[0], lambda: q_ref[0], lambda: k_ref[0],
                 lambda: v_ref[0], m_ref, l_ref, acc_ref, tie_ref,
                 scale=scale)

    @pl.when(kj == nkt - 1)
    def _finish():
        _write_out(o_ref, l_ref, acc_ref)


def sparse_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array,
                            codes_q: jax.Array, codes_k: jax.Array,
                            thresholds: jax.Array, *, scale: float,
                            causal: bool, window: Optional[int],
                            q_offset: int = 0, kv_map=None,
                            tile_q: int = 256, tile_k: int = 512,
                            interpret: Optional[bool] = None) -> jax.Array:
    """q: (Gq, nq, dh); k/v/codes_k: (Gk, nk, ...); thresholds: (Gq, nq, 2).

    kv_map: callable mapping a q-group index -> kv-group index (GQA);
    identity if None.
    """
    interpret = resolve_interpret(interpret)
    gq, nq, dh = q.shape
    gk, nk, _ = k.shape
    m = codes_q.shape[-1]
    tq = min(tile_q, nq)
    if nq % tq:
        tq = nq
    tk = min(tile_k, nk)
    if nk % tk:
        tk = nk
    nkt = nk // tk
    kvm = kv_map if kv_map is not None else (lambda g: g)
    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, tq=tq, tk=tk, nkt=nkt)
    return pl.pallas_call(
        kernel,
        grid=(gq, nq // tq, nkt),
        in_specs=[
            pl.BlockSpec((1, tq, dh), lambda g, qi, kj: (g, qi, 0)),
            pl.BlockSpec((1, tk, dh),
                         lambda g, qi, kj: (kvm(g), nkt - 1 - kj, 0)),
            pl.BlockSpec((1, tk, dh),
                         lambda g, qi, kj: (kvm(g), nkt - 1 - kj, 0)),
            pl.BlockSpec((1, tq, m), lambda g, qi, kj: (g, qi, 0)),
            pl.BlockSpec((1, tk, m),
                         lambda g, qi, kj: (kvm(g), nkt - 1 - kj, 0)),
            pl.BlockSpec((1, tq, 2), lambda g, qi, kj: (g, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, tq, dh), lambda g, qi, kj: (g, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((gq, nq, dh), q.dtype),
        scratch_shapes=[
            vmem((tq, 1), jnp.float32),
            vmem((tq, 1), jnp.float32),
            vmem((tq, dh), jnp.float32),
            vmem((tq, 1), jnp.int32),
        ],
        interpret=interpret,
    )(q, k, v, codes_q, codes_k, thresholds)


# ---------------------------------------------------------------- decode
def _softmax_init(m_ref, l_ref, acc_ref, tie_ref):
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    tie_ref[...] = jnp.zeros_like(tie_ref)


def _attend_tile(sm, thr, q_get, k_get, v_get,
                 m_ref, l_ref, acc_ref, tie_ref, *, scale):
    """One newest-first key tile of the thresholded online-softmax decode
    attention — shared verbatim between the two-pass kernel and the fused
    one-pass kernel's phase 2, so the two dispatch tiers stay bit-identical.

    sm: (R_out, Tk) masked scores (-1 = dead slot); thr: (R_out, 2)
    [t, need]; q/k/v_get: thunks returning the (R, dh)/(Tk, dh)/(Tk, dh)
    tiles (deferred so a fully ineligible tile skips the VMEM reads and
    MXU work via pl.when)."""
    t = thr[:, 0:1]
    need = thr[:, 1:2]
    above = sm > t
    at_t = sm == t
    # ties more recent (higher slot index) than position b: taken so far in
    # previously visited (newer) tiles + ties right of b inside this tile
    rev_excl = suffix_count(at_t) - at_t.astype(jnp.int32)
    elig_t = at_t & ((tie_ref[...] + rev_excl) < need)
    eligible = above | elig_t             # (R_out, Tk)
    tie_ref[...] += jnp.sum(elig_t.astype(jnp.int32), axis=1, keepdims=True)

    @pl.when(jnp.any(eligible))
    def _block():
        _online_softmax_tile(q_get, k_get, v_get, eligible, m_ref, l_ref,
                             acc_ref, scale=scale)


def _online_softmax_tile(q_get, k_get, v_get, keep, m_ref, l_ref, acc_ref, *,
                         scale):
    """Fold one key tile into the flash-style accumulators; ``keep``
    ((R or 1, Tk) bool) masks the logits.  Statistics are (R, 1) columns
    so every reduction keeps its lane dim (Mosaic-friendly)."""
    q = q_get().astype(jnp.float32)                   # (R, dh)
    k = k_get().astype(jnp.float32)                   # (Tk, dh)
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (R, Tk)
    logits = jnp.where(keep, logits, -jnp.inf)        # bcast if kvgroup
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    finite = m_new > -jnp.inf
    m_safe = jnp.where(finite, m_new, 0.0)
    alpha = jnp.where(finite, jnp.exp(m_prev - m_safe), 1.0)
    p = jnp.where(finite & keep, jnp.exp(logits - m_safe), 0.0)
    v = v_get().astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new


def _write_out(o_ref, l_ref, acc_ref):
    l = l_ref[...]
    out = acc_ref[...] / jnp.maximum(l, 1e-30)
    out = jnp.where(l > 0, out, 0.0)
    o_ref[0] = out.astype(o_ref.dtype)


def _decode_attn_kernel(q_ref, k_ref, v_ref, cq_ref, ck_ref, thr_ref,
                        valid_ref, o_ref, m_ref, l_ref, acc_ref, tie_ref, *,
                        scale, sum_rows, nkt):
    kj = pl.program_id(1)                 # tiles visited newest slot first

    @pl.when(kj == 0)
    def _init():
        _softmax_init(m_ref, l_ref, acc_ref, tie_ref)

    sm = _mask_scores(cq_ref[0], ck_ref[0], valid_ref[0], sum_rows)
    _attend_tile(sm, thr_ref[0],
                 lambda: q_ref[0], lambda: k_ref[0], lambda: v_ref[0],
                 m_ref, l_ref, acc_ref, tie_ref, scale=scale)

    @pl.when(kj == nkt - 1)
    def _finish():
        _write_out(o_ref, l_ref, acc_ref)


def sparse_decode_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array,
                                   codes_q: jax.Array, codes_k: jax.Array,
                                   thresholds: jax.Array,
                                   kv_valid: jax.Array, *, scale: float,
                                   sum_rows: bool, heads_per_batch: int,
                                   tile_k: int = 512,
                                   interpret: Optional[bool] = None
                                   ) -> jax.Array:
    """Fused single-token sparse-MHA decode (PQ score -> threshold mask ->
    online-softmax attention) over the KV cache, one pass per key tile.

    GQA layout: the R query heads of one kv head ride the sublane axis —
    q/codes_q: (G, R, ...) with G = B*Hk — so key/value/code tiles are
    streamed ONCE per kv group instead of being jnp.repeat-ed per query
    head.  k/v: (G, S, dh); codes_k: (G, S, M).

    thresholds: (G, R_out, 2) [t, need] from decode_topl_thresholds_kernel
    (R_out = 1 under the shared "kvgroup" selection, R per-head).
    kv_valid: (B, S) nonzero = cache slot participates; both plain causal
    caches and ring-buffer sliding-window caches reduce to this mask.  It
    rides as (B, 1, S), whose unit sublane dim lets a (1, 1, Tk) block
    meet the TPU's (8, 128)-or-full tiling rule.

    Key tiles are visited newest-slot-first so the most-recent-ties-first
    budget is consumed in canonical order; tiles with no eligible key skip
    their MXU work via pl.when.  Memory: O(Tk) VMEM tiles + (R, dh)
    accumulators — no (S,) score row ever reaches HBM.
    """
    interpret = resolve_interpret(interpret)
    g, r, dh = q.shape
    _, nk, _ = k.shape
    m = codes_q.shape[-1]
    r_out = thresholds.shape[1]
    tk = min(tile_k, nk)
    if nk % tk:
        tk = nk
    nkt = nk // tk
    hpb = heads_per_batch
    kernel = functools.partial(_decode_attn_kernel, scale=scale,
                               sum_rows=sum_rows, nkt=nkt)
    return pl.pallas_call(
        kernel,
        grid=(g, nkt),
        in_specs=[
            pl.BlockSpec((1, r, dh), lambda gi, kj: (gi, 0, 0)),
            pl.BlockSpec((1, tk, dh), lambda gi, kj: (gi, nkt - 1 - kj, 0)),
            pl.BlockSpec((1, tk, dh), lambda gi, kj: (gi, nkt - 1 - kj, 0)),
            pl.BlockSpec((1, r, m), lambda gi, kj: (gi, 0, 0)),
            pl.BlockSpec((1, tk, m), lambda gi, kj: (gi, nkt - 1 - kj, 0)),
            pl.BlockSpec((1, r_out, 2), lambda gi, kj: (gi, 0, 0)),
            pl.BlockSpec((1, 1, tk),
                         lambda gi, kj: (gi // hpb, 0, nkt - 1 - kj)),
        ],
        out_specs=pl.BlockSpec((1, r, dh), lambda gi, kj: (gi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, r, dh), q.dtype),
        scratch_shapes=[
            vmem((r, 1), jnp.float32),
            vmem((r, 1), jnp.float32),
            vmem((r, dh), jnp.float32),
            vmem((r_out, 1), jnp.int32),
        ],
        interpret=interpret,
    )(q, k, v, codes_q, codes_k, thresholds, lane_row(kv_valid))


# ----------------------------------------------- fused one-pass decode
def _decode_scratch(r, r_out, dh):
    """m / l / acc / tie / thr — the fused kernel owns the [t, need] pair
    as VMEM scratch, so the thresholds tensor never round-trips through
    HBM (the histogram itself lives in registers of the first grid step)."""
    return [
        vmem((r, 1), jnp.float32),
        vmem((r, 1), jnp.float32),
        vmem((r, dh), jnp.float32),
        vmem((r_out, 1), jnp.int32),
        vmem((r_out, 2), jnp.int32),
    ]


def _pair_of(nkt: int) -> int:
    """Key tiles folded into one grid step: 2 when the tile count is even
    (one double-width block read, two attention sub-tiles in newest-first
    order — halves the grid without touching the accumulation order), else
    1 (ragged tile counts fall back to the single-tile schedule)."""
    return 2 if nkt > 1 and nkt % 2 == 0 else 1


def _mask_scores(cq, ck, valid, sum_rows):
    """Codes (R, M) x (N, M) + validity (1, N) -> (R_out, N) masked match
    scores (-1 = dead)."""
    s = match_scores(cq, ck)
    if sum_rows:
        s = jnp.sum(s, axis=0, keepdims=True)
    return jnp.where(valid != 0, s, -1)


def _row_hist(n_tiles, tile_scores, r_out, max_score):
    """Score histogram of a whole cache row, folded one tile at a time so
    no (R, N) score row is ever live: tile_scores(i) -> (R_out, Tk)."""
    def body(i, hist):
        return hist + hist_counts(tile_scores(i), max_score)

    return jax.lax.fori_loop(0, n_tiles, body,
                             jnp.zeros((r_out, max_score + 1), jnp.int32))


def _fused_decode_kernel(q_ref, k_ref, v_ref, cq_ref, ck_ref, valid_ref,
                         o_ref, m_ref, l_ref, acc_ref, tie_ref, thr_ref, *,
                         scale, l, max_score, sum_rows, tk, pair, nsteps):
    kj = pl.program_id(1)
    cq = cq_ref[0]

    def tile_scores(ti):                  # key tile ti of the pinned row
        start = pl.multiple_of(ti * tk, tk)
        return _mask_scores(cq, ck_ref[0, pl.ds(start, tk), :],
                            valid_ref[0, :, pl.ds(start, tk)], sum_rows)

    @pl.when(kj == 0)
    def _init():
        # the FULL-cache threshold: identical integer math to the
        # standalone threshold kernel, so the two-pass tier stays
        # bit-identical
        _softmax_init(m_ref, l_ref, acc_ref, tie_ref)
        thr_ref[...] = hist_reduce(_row_hist(nsteps * pair, tile_scores,
                                             thr_ref.shape[0], max_score), l)

    base = (nsteps - 1 - kj) * pair       # oldest tile of this step's block
    for h in reversed(range(pair)):       # newest slot first
        sl = slice(h * tk, (h + 1) * tk)
        _attend_tile(tile_scores(base + h), thr_ref[...], lambda: q_ref[0],
                     lambda sl=sl: k_ref[0, sl], lambda sl=sl: v_ref[0, sl],
                     m_ref, l_ref, acc_ref, tie_ref, scale=scale)

    @pl.when(kj == nsteps - 1)
    def _finish():
        _write_out(o_ref, l_ref, acc_ref)


def fused_sparse_decode_attention_kernel(
        q: jax.Array, k: jax.Array, v: jax.Array, codes_q: jax.Array,
        codes_k: jax.Array, kv_valid: jax.Array, *, scale: float, l: int,
        max_score: int, sum_rows: bool, heads_per_batch: int,
        tile_k: int = 512, interpret: Optional[bool] = None) -> jax.Array:
    """One-pass decode: thresholds fused into the attention kernel, and
    the key axis swept at HALF the two-pass grid length.

    The whole (padded) int8 code row and validity row ride as pinned
    blocks — M int8 lanes per slot vs 2*dh f32/bf16 lanes of K+V, so they
    are the cheap operands — and grid step 0 folds the full score
    histogram one key tile at a time into the (R_out, 2) [t, need]
    thresholds in VMEM scratch: no prologue steps, no thresholds HBM
    round-trip, no second kernel launch.
    Each step then reads one (pair*Tk) K/V block and replays its `pair`
    sub-tiles newest-slot-first through the two-pass kernels' attention
    body, so the eligibility rule, tie budget, and online-softmax
    accumulation ORDER are identical and the output stays bit-identical to
    the two-pass tier (the sweep visits the same Tk tiles in the same
    order — only the number visited per grid step changes).

    Grid (G, nkt / pair), pair = 2 when nkt is even.  vs the two-pass
    pipeline: one launch instead of two, thresholds never exist in HBM,
    and half the grid steps (double-width K/V DMA per step).

    Shapes as sparse_decode_attention_kernel; nk must be a multiple of
    pair*tile_k (the ops wrapper zero-pads, dead slots carry kv_valid=0).
    The pinned int8 codes block pads its M lanes to 128 in VMEM: 128 B per
    slot, 4 MiB at S=32k (double-buffered 8 MiB).
    """
    interpret = resolve_interpret(interpret)
    g, r, dh = q.shape
    _, nk, _ = k.shape
    m = codes_q.shape[-1]
    r_out = 1 if sum_rows else r
    tk = min(tile_k, nk)
    if nk % tk:
        tk = nk
    nkt = nk // tk
    pair = _pair_of(nkt)
    nsteps = nkt // pair
    hpb = heads_per_batch
    kernel = functools.partial(_fused_decode_kernel, scale=scale, l=l,
                               max_score=max_score, sum_rows=sum_rows,
                               tk=tk, pair=pair, nsteps=nsteps)
    return pl.pallas_call(
        kernel,
        grid=(g, nsteps),
        in_specs=[
            pl.BlockSpec((1, r, dh), lambda gi, kj: (gi, 0, 0)),
            pl.BlockSpec((1, pair * tk, dh),
                         lambda gi, kj: (gi, nsteps - 1 - kj, 0)),
            pl.BlockSpec((1, pair * tk, dh),
                         lambda gi, kj: (gi, nsteps - 1 - kj, 0)),
            pl.BlockSpec((1, r, m), lambda gi, kj: (gi, 0, 0)),
            pl.BlockSpec((1, nk, m), lambda gi, kj: (gi, 0, 0)),
            pl.BlockSpec((1, 1, nk), lambda gi, kj: (gi // hpb, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, r, dh), lambda gi, kj: (gi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, r, dh), q.dtype),
        scratch_shapes=_decode_scratch(r, r_out, dh),
        interpret=interpret,
    )(q, k, v, codes_q, codes_k, lane_row(kv_valid))


# ----------------------------------------------- kernel-native paging
def _fused_decode_paged_kernel(pt_ref, q_ref, k_ref, v_ref, cq_ref,
                               ckh_ref, vh_ref, cka_ref, va_ref, o_ref,
                               m_ref, l_ref, acc_ref, tie_ref, thr_ref,
                               hist_ref, *, scale, l, max_score, sum_rows,
                               tk, pair, nsteps, mp):
    kj = pl.program_id(1)
    cq = cq_ref[0]

    @pl.when(kj == 0)
    def _init():
        _softmax_init(m_ref, l_ref, acc_ref, tie_ref)
        hist_ref[...] = jnp.zeros_like(hist_ref)

    @pl.when(kj < mp)                     # histogram phase: page kj
    def _hist():
        hist_ref[...] += hist_counts(
            _mask_scores(cq, ckh_ref[0, 0], vh_ref[0], sum_rows), max_score)

    @pl.when(kj == mp - 1)
    def _thresholds():
        thr_ref[...] = hist_reduce(hist_ref[...], l)

    @pl.when(kj >= mp)                    # attention phase, newest first
    def _attend():
        for h in reversed(range(pair)):
            sl = slice(h * tk, (h + 1) * tk)
            sm = _mask_scores(cq, cka_ref[0, 0, sl], va_ref[0, :, sl],
                              sum_rows)
            _attend_tile(sm, thr_ref[...], lambda: q_ref[0],
                         lambda sl=sl: k_ref[0, 0, sl],
                         lambda sl=sl: v_ref[0, 0, sl],
                         m_ref, l_ref, acc_ref, tie_ref, scale=scale)

    @pl.when(kj == mp + nsteps - 1)
    def _finish():
        _write_out(o_ref, l_ref, acc_ref)


def fused_sparse_decode_attention_paged_kernel(
        page_table: jax.Array, q: jax.Array, k_pool: jax.Array,
        v_pool: jax.Array, codes_q: jax.Array, codes_pool: jax.Array,
        kv_valid: jax.Array, *, scale: float, l: int, max_score: int,
        sum_rows: bool, heads_per_batch: int, tile_k: int = 512,
        interpret: Optional[bool] = None) -> jax.Array:
    """Fused one-pass decode reading the paged KV pool DIRECTLY: the
    per-slot page table rides as a scalar-prefetch operand and the K/V/code
    BlockSpec index_maps translate each logical view block to
    (page_table[slot, block // blocks_per_page], head, offset) — no
    gathered (B, Hk, S, .) view of the pool ever materializes.

    Grid (G, MP + nsteps).  The first MP steps stream the slot's own code
    pages, one (page_size, M) block per step, and fold each into a score
    histogram in VMEM scratch; the last of them derives the [t, need]
    thresholds (identical integer math to the standalone threshold
    kernel).  Fast memory holds one page of codes whatever the pool size.
    The remaining nsteps steps each read one (pair*Tk) K/V/code block of a
    single page and replay its sub-tiles newest-slot-first through the
    shared attention body — pair = 2 when tiles_per_page is even, so
    blocks never straddle a page.  During the histogram phase the K/V
    index_maps already point at the first attention block, so the
    pipeline fetches it once.

    page_table: (B, MP) int32, clamped non-negative by the caller (the
    repo-wide convention: unallocated -> page 0, whose garbage rows carry
    kv_valid == 0).  q/codes_q: (G, R, .) with G = B*Hk; pools:
    (P, Hk, page_size, .); kv_valid: (B, MP*page_size) in view
    coordinates.  The tile size divides page_size so no tile straddles a
    page boundary; the sweep visits the same Tk tiles in the same
    newest-first order as the contiguous kernel, so given equal tile_k the
    output is bit-identical to running the contiguous fused kernel (or the
    two-pass pair) over the gathered view.
    """
    interpret = resolve_interpret(interpret)
    g, r, dh = q.shape
    _, hk, ps, _ = k_pool.shape
    mp = page_table.shape[1]
    m = codes_q.shape[-1]
    r_out = 1 if sum_rows else r
    tk = min(tile_k, ps)
    if ps % tk:
        tk = ps
    ppt = ps // tk                        # tiles per page
    pair = _pair_of(ppt)                  # pairs never straddle a page
    nsteps = (mp * ps) // (pair * tk)
    bpp = ppt // pair                     # (pair*tk)-blocks per page
    hpb = heads_per_batch
    kernel = functools.partial(_fused_decode_paged_kernel, scale=scale, l=l,
                               max_score=max_score, sum_rows=sum_rows,
                               tk=tk, pair=pair, nsteps=nsteps, mp=mp)

    def view_block(kj):                   # newest-first attention block
        return nsteps - 1 - jnp.maximum(kj - mp, 0)

    def pool_idx(gi, kj, pt):
        bt = view_block(kj)
        return (pt[gi // hpb, bt // bpp], gi % hpb, bt % bpp, 0)

    def hist_page(gi, kj, pt):            # page kj of the slot (phase 1)
        return (pt[gi // hpb, jnp.minimum(kj, mp - 1)], gi % hpb, 0, 0)

    block = pair * tk
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g, mp + nsteps),
        in_specs=[
            pl.BlockSpec((1, r, dh), lambda gi, kj, pt: (gi, 0, 0)),
            pl.BlockSpec((1, 1, block, dh), pool_idx),
            pl.BlockSpec((1, 1, block, dh), pool_idx),
            pl.BlockSpec((1, r, m), lambda gi, kj, pt: (gi, 0, 0)),
            pl.BlockSpec((1, 1, ps, m), hist_page),
            pl.BlockSpec((1, 1, ps), lambda gi, kj, pt: (
                gi // hpb, 0, jnp.minimum(kj, mp - 1))),
            pl.BlockSpec((1, 1, block, m), pool_idx),
            pl.BlockSpec((1, 1, block), lambda gi, kj, pt: (
                gi // hpb, 0, view_block(kj))),
        ],
        out_specs=pl.BlockSpec((1, r, dh), lambda gi, kj, pt: (gi, 0, 0)),
        scratch_shapes=_decode_scratch(r, r_out, dh) + [
            vmem((r_out, max_score + 1), jnp.int32)],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g, r, dh), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), q, k_pool, v_pool, codes_q, codes_pool,
      lane_row(kv_valid), codes_pool, lane_row(kv_valid))


def _dense_decode_paged_kernel(pt_ref, q_ref, k_ref, v_ref, valid_ref, o_ref,
                               m_ref, l_ref, acc_ref, *, scale, nkt):
    del pt_ref
    kj = pl.program_id(1)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = valid_ref[0] != 0             # (1, Tk)

    @pl.when(jnp.any(valid))
    def _block():
        _online_softmax_tile(lambda: q_ref[0], lambda: k_ref[0, 0],
                             lambda: v_ref[0, 0], valid, m_ref, l_ref,
                             acc_ref, scale=scale)

    @pl.when(kj == nkt - 1)
    def _finish():
        _write_out(o_ref, l_ref, acc_ref)


def dense_decode_attention_paged_kernel(
        page_table: jax.Array, q: jax.Array, k_pool: jax.Array,
        v_pool: jax.Array, kv_valid: jax.Array, *, scale: float,
        heads_per_batch: int, tile_k: int = 512,
        interpret: Optional[bool] = None) -> jax.Array:
    """Dense single-token decode attention over the paged KV pool with the
    same scalar-prefetched (page_id, offset) tile addressing as the sparse
    paged kernel — the dense serving route also stops paying the per-step
    gather.  Online softmax over valid slots; dead/garbage rows masked to
    -inf.  Tiles stream forward (no tie budget, so order is free).
    """
    interpret = resolve_interpret(interpret)
    g, r, dh = q.shape
    _, hk, ps, _ = k_pool.shape
    mp = page_table.shape[1]
    tk = min(tile_k, ps)
    if ps % tk:
        tk = ps
    ppt = ps // tk
    nkt = (mp * ps) // tk
    hpb = heads_per_batch
    kernel = functools.partial(_dense_decode_paged_kernel, scale=scale,
                               nkt=nkt)

    def pool_idx(gi, kj, pt):
        return (pt[gi // hpb, kj // ppt], gi % hpb, kj % ppt, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g, nkt),
        in_specs=[
            pl.BlockSpec((1, r, dh), lambda gi, kj, pt: (gi, 0, 0)),
            pl.BlockSpec((1, 1, tk, dh), pool_idx),
            pl.BlockSpec((1, 1, tk, dh), pool_idx),
            pl.BlockSpec((1, 1, tk), lambda gi, kj, pt: (gi // hpb, 0, kj)),
        ],
        out_specs=pl.BlockSpec((1, r, dh), lambda gi, kj, pt: (gi, 0, 0)),
        scratch_shapes=[
            vmem((r, 1), jnp.float32),
            vmem((r, 1), jnp.float32),
            vmem((r, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g, r, dh), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), q, k_pool, v_pool, lane_row(kv_valid))
