"""Fused cdist+argmin PQ assignment kernel (paper §5.1, Algorithm 2).

The paper fuses the CUDA cdist and argmin kernels so the (n, E) distance
matrix never reaches global memory; we do the same for HBM.  Only the codes
are written back.

Layout: tokens on lanes.  The wrapper lays the (M, E, d') codebooks out as
one block-diagonal matrix ``W`` of shape (E·Mp, d): row e·Mp + m holds
codeword e of book m in columns m·d' … (m+1)·d' and zeros elsewhere, with
Mp = M rounded up to a multiple of 8 so that each codeword's block of rows
is sublane-aligned (the padding rows are zero and their codes dropped).
One MXU contraction W·xᵀ over the full head dim gives every sub-book's
dots at once, (E·Mp, Tn); the argmin over codewords is a running strict-<
min/select across the E sublane-aligned row blocks (elementwise, first
index on ties, as ``jnp.argmin``).  The (Mp, Tn) int32 result is a
lane-dense store.  Distances stay float32 at ``Precision.HIGHEST``.

Grid: (batch*heads, n / Tn), Tn the whole n up to 2,048 (fewer, larger
steps).  VMEM per step at Tn = 1,024, d = 128, E = 16, Mp = 16: x 256 KB
(bf16) double-buffered, W 128 KB, dots and distances 1 MB each, codes
64 KB — well under the 16 MB scoped limit.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

MAX_TILE_N = 2048


def _kernel(w_ref, c2_ref, x_ref, codes_ref):
    mp = codes_ref.shape[1]
    e = w_ref.shape[0] // mp
    x = x_ref[0].astype(jnp.float32)             # (Tn, d)
    dots = jax.lax.dot_general(                  # (E·Mp, Tn), tokens on lanes
        w_ref[...], x, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    c2 = c2_ref[...]                             # (E·Mp, 1)

    def dist(i):                                 # ||x||^2 constant in argmin
        return c2[i * mp:(i + 1) * mp] - 2.0 * dots[i * mp:(i + 1) * mp]

    best = dist(0)
    idx = jnp.zeros(best.shape, jnp.int32)
    for i in range(1, e):
        d_i = dist(i)
        lt = d_i < best                          # strict: first index on ties
        best = jnp.where(lt, d_i, best)
        idx = jnp.where(lt, i, idx)
    codes_ref[0] = idx


def _codebook_matrix(codebooks: jax.Array):
    """(M, E, d') codebooks -> block-diagonal W (E·Mp, M·d') float32 and
    the squared norms c2 (E·Mp, 1), rows e·Mp + m; padding rows zero."""
    m, e, dp = codebooks.shape
    mp = -(-m // 8) * 8
    cb = codebooks.astype(jnp.float32)
    eye = jnp.eye(m, dtype=jnp.float32)
    # (E, M, M, d') by an elementwise product, not a dot: no MXU rounding
    w = jnp.transpose(cb, (1, 0, 2))[:, :, None, :] * eye[None, :, :, None]
    w = jnp.pad(w, ((0, 0), (0, mp - m), (0, 0), (0, 0)))
    c2 = jnp.pad(jnp.sum(cb * cb, axis=-1).T, ((0, 0), (0, mp - m)))
    return w.reshape(e * mp, m * dp), c2.reshape(e * mp, 1)


def _tile(n: int, tile_n: Optional[int]) -> int:
    if tile_n is not None:
        tn = min(tile_n, n)
        return tn if n % tn == 0 else n
    if n <= MAX_TILE_N:
        return n
    for tn in range(MAX_TILE_N, 0, -128):
        if n % tn == 0:
            return tn
    return n


def pq_assign_kernel(x: jax.Array, codebooks: jax.Array, *,
                     tile_n: Optional[int] = None,
                     interpret: Optional[bool] = None) -> jax.Array:
    """x: (G, n, d); codebooks: (M, E, d') -> codes (G, Mp, n) int32, rows
    M and beyond padding.  ``tile_n`` overrides the tile (tests only)."""
    interpret = resolve_interpret(interpret)
    g, n, d = x.shape
    m, e, dp = codebooks.shape
    assert d == m * dp, (x.shape, codebooks.shape)
    w, c2 = _codebook_matrix(codebooks)
    mp = w.shape[0] // e
    tn = _tile(n, tile_n)
    return pl.pallas_call(
        _kernel,
        grid=(g, n // tn),
        in_specs=[
            pl.BlockSpec((e * mp, d), lambda gi, i: (0, 0)),
            pl.BlockSpec((e * mp, 1), lambda gi, i: (0, 0)),
            pl.BlockSpec((1, tn, d), lambda gi, i: (gi, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, mp, tn), lambda gi, i: (gi, 0, i)),
        out_shape=jax.ShapeDtypeStruct((g, mp, n), jnp.int32),
        name="pq_assign",
        interpret=interpret,
    )(w, c2, x)
