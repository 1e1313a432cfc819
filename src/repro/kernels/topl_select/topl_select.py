"""Streaming bucket-threshold kernel — paper Algorithm 3 on TPU.

The paper's bucket sort puts each key into one of M+1 integer-score buckets
(shared memory, thread-per-query) and reads buckets high-to-low until L keys
are collected.  The TPU form computes, for a (Tq) tile of queries, the
per-query score *histogram* by streaming (Tk) key-code tiles through VMEM,
then derives the equivalent of "where reading stops": the threshold bucket
``t`` and the residual tie budget ``need`` (# keys to take at score == t,
most recent first).  Downstream consumers (the fused attention kernel, or
the jnp emit step) never sort anything.

Grid: (G, nq/Tq, nk/Tk), key axis minor => the histogram scratch carries
across key tiles.  VMEM: codes tiles (Tq+Tk) x M int32 + hist (Tq, M+1).
Output: (G, nq, 2) int32 = [t, need] per query.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def match_scores(cq, ck):
    """(Tq, M) x (Tk, M) integer codes -> (Tq, Tk) int32 match counts
    (Eq. 6).  The key codes are turned lane-major with an identity matmul
    on the MXU (codes < 2^8 are exact in any matmul precision), so every
    per-book compare is a sublane-broadcast against a lane-broadcast —
    no in-register transpose, which Mosaic cannot lower at these widths."""
    m = cq.shape[1]
    cq = cq.astype(jnp.int32).astype(jnp.float32)
    ck = ck.astype(jnp.int32).astype(jnp.float32)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (m, m), 1))
    ck_t = jax.lax.dot_general(eye.astype(jnp.float32), ck,
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)  # (M, Tk)
    s = jnp.zeros((cq.shape[0], ck.shape[0]), jnp.int32)
    for i in range(m):
        s = s + (cq[:, i:i + 1] == ck_t[i:i + 1, :]).astype(jnp.int32)
    return s


def suffix_count(x):
    """(R, N) bool -> (R, N) int32: at column b, how many of columns
    b..N-1 are set.  A matmul against an upper-triangular ones matrix
    (0/1 operands and f32 accumulation are exact at any matmul precision;
    f32 operands, since Mosaic refuses bf16 ones under "highest") —
    Mosaic has no lowering for the reversed cumsum this replaces."""
    n = x.shape[1]
    upper = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
             >= jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jax.lax.dot_general(
        x.astype(jnp.float32), upper.astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.int32)


def lane_row(kv_valid):
    """(B, S) -> (B, 1, S): a (1, 1, Tk) block of this meets the TPU's
    (8, 128)-or-full tiling rule, a (1, Tk) block of (B, S) does not."""
    return kv_valid.reshape(kv_valid.shape[0], 1, -1)


def tile_positions(q0, k0, tq, tk):
    """(Tq, Tk) absolute query / key positions of one score tile."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    return q_pos, k_pos


def causal_mask(q_pos, k_pos, causal, window):
    m = jnp.ones(q_pos.shape, bool)
    if causal:
        m &= k_pos <= q_pos
    if window is not None:
        m &= k_pos > q_pos - window
    return m


def _hist_kernel(cq_ref, ck_ref, thr_ref, hist_ref, *, max_score, l,
                 causal, window, q_offset, tq, tk, nkt):
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    s = match_scores(cq_ref[0], ck_ref[0])
    q_pos, k_pos = tile_positions(q_offset + qi * tq, ki * tk, tq, tk)
    sm = jnp.where(causal_mask(q_pos, k_pos, causal, window), s, -1)
    hist_accumulate(hist_ref, sm, max_score)

    @pl.when(ki == nkt - 1)
    def _finish():
        thr_ref[0] = hist_reduce(hist_ref[...], l)


def topl_thresholds_kernel(codes_q: jax.Array, codes_k: jax.Array, *,
                           l: int, max_score: int, causal: bool,
                           window: Optional[int], q_offset: int = 0,
                           tile_q: int = 256, tile_k: int = 512,
                           interpret: Optional[bool] = None) -> jax.Array:
    """codes_q: (G, nq, M); codes_k: (G, nk, M) -> (G, nq, 2) [t, need]."""
    interpret = resolve_interpret(interpret)
    g, nq, m = codes_q.shape
    _, nk, _ = codes_k.shape
    tq = min(tile_q, nq)
    if nq % tq:
        tq = nq
    tk = min(tile_k, nk)
    if nk % tk:
        tk = nk
    nkt = nk // tk
    grid = (g, nq // tq, nkt)
    kernel = functools.partial(
        _hist_kernel, max_score=max_score, l=l, causal=causal, window=window,
        q_offset=q_offset, tq=tq, tk=tk, nkt=nkt)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tq, m), lambda gi, qi, ki: (gi, qi, 0)),
            pl.BlockSpec((1, tk, m), lambda gi, qi, ki: (gi, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, tq, 2), lambda gi, qi, ki: (gi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((g, nq, 2), jnp.int32),
        scratch_shapes=[vmem((tq, max_score + 1), jnp.int32)],
        interpret=interpret,
    )(codes_q, codes_k)


def vmem(shape, dtype):
    """VMEM scratch allocation (works under interpret=True on CPU too)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)


# ---------------------------------------------------------------- decode
def hist_counts(sm, max_score):
    """(R_out, N) masked scores (-1 = dead slot) -> (R_out, max_score+1)
    bucket counts.  N is arbitrary: the threshold kernels fold one Tk tile
    at a time into scratch, the fused one-pass decode kernels fold the
    whole cache row in their first grid step.  Integer counts are
    order-independent, so both routes derive bit-identical thresholds.
    Each bucket is a lane reduction placed by a lane-iota select, the
    form Mosaic lowers (no stack/concatenate of 1-D vectors)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (sm.shape[0], max_score + 1),
                                    1)
    hist = jnp.zeros(lane.shape, jnp.int32)
    for v in range(max_score + 1):
        count = jnp.sum((sm == v).astype(jnp.int32), axis=1, keepdims=True)
        hist = hist + jnp.where(lane == v, count, 0)
    return hist


def hist_accumulate(hist_ref, sm, max_score):
    """Fold one (R_out, Tk) masked-score tile into the bucket histogram
    scratch."""
    hist_ref[...] += hist_counts(sm, max_score)


def hist_reduce(hist, l):
    """Histogram (R_out, max_score+1) -> (R_out, 2) int32 [threshold bucket
    t, tie budget need]: the bucket where high-to-low reading stops at L
    keys, and how many score==t keys (most recent first) still fit.

    ge[v] = #keys scoring >= v; t = the highest v with ge[v] >= L (0 if
    none); need = L - ge[t+1] (ge past the top bucket is 0).  Written as
    per-bucket lane reductions: the bucket count is tiny (M+1, or R*M+1
    under kvgroup) and Mosaic lowers neither reversed cumsums nor
    take_along_axis."""
    r, nb = hist.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (r, nb), 1)
    ge = [jnp.sum(jnp.where(lane >= v, hist, 0), axis=1, keepdims=True)
          for v in range(nb)]                                # nb x (R, 1)
    n_meet = sum((g >= l).astype(jnp.int32) for g in ge)
    t = jnp.maximum(n_meet - 1, 0)
    n_above = sum(jnp.where(t + 1 == v, ge[v], 0) for v in range(1, nb))
    col = jax.lax.broadcasted_iota(jnp.int32, (r, 2), 1)
    return jnp.where(col == 0, t, l - n_above).astype(jnp.int32)


def _decode_hist_kernel(cq_ref, ck_ref, valid_ref, thr_ref, hist_ref, *,
                        max_score, l, sum_rows, nkt):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        hist_ref[...] = jnp.zeros_like(hist_ref)

    s = match_scores(cq_ref[0], ck_ref[0])        # (R, Tk)
    if sum_rows:                                  # GQA-shared ("kvgroup"):
        s = jnp.sum(s, axis=0, keepdims=True)     # one selection per kv head
    sm = jnp.where(valid_ref[0] != 0, s, -1)      # (R_out, Tk)
    hist_accumulate(hist_ref, sm, max_score)

    @pl.when(ki == nkt - 1)
    def _finish():
        thr_ref[0] = hist_reduce(hist_ref[...], l)


def decode_topl_thresholds_kernel(codes_q: jax.Array, codes_k: jax.Array,
                                  kv_valid: jax.Array, *, l: int,
                                  max_score: int, sum_rows: bool,
                                  heads_per_batch: int, tile_k: int = 512,
                                  interpret: Optional[bool] = None
                                  ) -> jax.Array:
    """Decode-shaped threshold pass: one query token per group.

    codes_q: (G, R, M) — the R query heads sharing one kv head (G = B*Hk);
    codes_k: (G, S, M) cached key codes; kv_valid: (B, S) nonzero = slot
    participates (plain causal caches and ring-buffer SWA caches both
    reduce to this mask — no positional logic in-kernel).  It rides as
    (B, 1, S), so a (1, 1, Tk) block meets the TPU's (8, 128)-or-full
    tiling rule.

    sum_rows=True is the "kvgroup" granularity: the R heads' match counts
    are summed (score in [0, R*M]) and ONE threshold is emitted per kv head;
    sum_rows=False ("qhead") keeps a per-row histogram.  No jnp.repeat of
    codes across query heads in either mode.

    Returns (G, R_out, 2) int32 [threshold bucket, tie budget],
    R_out = 1 if sum_rows else R.
    """
    interpret = resolve_interpret(interpret)
    g, r, m = codes_q.shape
    _, nk, _ = codes_k.shape
    tk = min(tile_k, nk)
    if nk % tk:
        tk = nk
    nkt = nk // tk
    r_out = 1 if sum_rows else r
    hpb = heads_per_batch
    kernel = functools.partial(_decode_hist_kernel, max_score=max_score, l=l,
                               sum_rows=sum_rows, nkt=nkt)
    return pl.pallas_call(
        kernel,
        grid=(g, nkt),
        in_specs=[
            pl.BlockSpec((1, r, m), lambda gi, ki: (gi, 0, 0)),
            pl.BlockSpec((1, tk, m), lambda gi, ki: (gi, ki, 0)),
            pl.BlockSpec((1, 1, tk), lambda gi, ki: (gi // hpb, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, r_out, 2), lambda gi, ki: (gi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, r_out, 2), jnp.int32),
        scratch_shapes=[vmem((r_out, max_score + 1), jnp.int32)],
        interpret=interpret,
    )(codes_q, codes_k, lane_row(kv_valid))
