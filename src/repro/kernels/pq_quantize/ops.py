"""Public jit wrapper for the fused PQ assignment kernel.

Codes are integer outputs (no gradient); the codebooks train through the
DKM quantization-error loss on the jnp path, so no custom VJP is needed —
the op is non-differentiable by construction (like the paper's).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.pq_quantize.pq_quantize import pq_assign_kernel


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def pq_assign(x: jax.Array, codebooks: jax.Array, *,
              tile_n: Optional[int] = None,
              interpret: Optional[bool] = None) -> jax.Array:
    """x: (..., n, d); codebooks (M, E, d') -> (..., n, M) int32.

    The kernel writes codes with tokens on lanes, (G, Mp, n); this drops
    the padding rows and transposes.  ``tile_n`` overrides the kernel's
    tile, for tests.  interpret=None derives from the backend (interpret
    off TPU, compiled on TPU) — see kernels.resolve_interpret.
    """
    lead = x.shape[:-2]
    n, m = x.shape[-2], codebooks.shape[0]
    xg = x.reshape(-1, *x.shape[-2:])
    codes = pq_assign_kernel(xg, codebooks, tile_n=tile_n,
                             interpret=interpret)
    return jnp.transpose(codes[:, :m, :], (0, 2, 1)).reshape(*lead, n, m)
