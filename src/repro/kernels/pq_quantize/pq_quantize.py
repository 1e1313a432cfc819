"""Fused cdist+argmin PQ assignment kernel (paper §5.1, Algorithm 2).

The paper fuses the CUDA cdist and argmin kernels so the (n, E) distance
matrix never reaches global memory; we do the same for HBM: each grid step
loads one (Tn, d) slab of vectors plus the full (M, E, d') codebooks into
VMEM, computes per-subspace distances via a -2 x cᵀ MXU matmul, and argmins
in VREGs.  Only the (Tn, M) int32 codes are written back.

Grid: (batch*heads, n / Tn).  VMEM per step (defaults Tn=256, d<=256,
E=16): x 256 KB + codebooks ~16 KB + codes 16 KB — comfortably < 16 MB.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _kernel(x_ref, cb_ref, codes_ref):
    x = x_ref[0].astype(jnp.float32)            # (Tn, d)
    m, e, dp = cb_ref.shape
    outs = []
    for i in range(m):
        sub = x[:, i * dp:(i + 1) * dp]          # (Tn, d')
        cb = cb_ref[i].astype(jnp.float32)       # (E, d')
        dots = jnp.dot(sub, cb.T, preferred_element_type=jnp.float32)
        c2 = jnp.sum(cb * cb, axis=1)
        dist = c2[None, :] - 2.0 * dots          # ||x||^2 constant in argmin
        outs.append(jnp.argmin(dist, axis=1).astype(jnp.int32))
    codes_ref[0] = jnp.stack(outs, axis=1)


def pq_assign_kernel(x: jax.Array, codebooks: jax.Array, *, tile_n: int = 256,
                     interpret: Optional[bool] = None) -> jax.Array:
    """x: (G, n, d); codebooks: (M, E, d') -> codes (G, n, M) int32."""
    interpret = resolve_interpret(interpret)
    g, n, d = x.shape
    m, e, dp = codebooks.shape
    assert d == m * dp, (x.shape, codebooks.shape)
    tn = min(tile_n, n)
    if n % tn != 0:
        tn = n
    grid = (g, n // tn)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tn, d), lambda gi, i: (gi, i, 0)),
            pl.BlockSpec((m, e, dp), lambda gi, i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tn, m), lambda gi, i: (gi, i, 0)),
        out_shape=jax.ShapeDtypeStruct((g, n, m), jnp.int32),
        name="pq_assign",
        interpret=interpret,
    )(x, codebooks)
