"""Product quantization (PQ) for sparse-MHA candidate selection (paper §4.1, §5.1).

A head-dimension vector x in R^d is chopped into M sub-vectors of size
d' = d/M; sub-vector m is assigned to its nearest codeword (L2) in codebook
C^m of E codewords.  The query/key similarity is the *integer* number of
shared codewords (paper Eq. 6):

    s(q, k) = sum_m  1[t_q^m == t_k^m]        in {0, ..., M}

Codebooks are maintained by interval EMA k-means (the paper uses DKM and
updates every 20 mini-batches; we keep the interval and use the streaming EMA
form of k-means, which is the TPU-friendly equivalent — no in-kernel sort,
no host sync).

Defaults follow the paper: codeword dim d' = 8, E = 16 codewords/book.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.params import ParamDef


@dataclasses.dataclass(frozen=True)
class PQConfig:
    head_dim: int
    code_dim: int = 8           # d' — dimension per codebook
    num_codewords: int = 16     # E
    update_interval: int = 20   # DKM/EMA codebook refresh cadence (steps)
    ema: float = 0.05           # EMA step for codebook update

    @property
    def num_books(self) -> int:  # M
        assert self.head_dim % self.code_dim == 0, (
            f"head_dim {self.head_dim} not divisible by code_dim {self.code_dim}")
        return self.head_dim // self.code_dim


def param_defs(cfg: PQConfig) -> dict:
    """Codebooks shared by Q and K of one attention layer: (M, E, d')."""
    return {
        "codebooks": ParamDef(
            shape=(cfg.num_books, cfg.num_codewords, cfg.code_dim),
            dtype=jnp.float32,
            axes=("codebook", "codeword", "code_dim"),
            init="normal:1.0",
            trainable=True,  # updated by EMA k-means, grads zeroed by optimizer mask
        )
    }


def assign(x: jax.Array, codebooks: jax.Array) -> jax.Array:
    """Assign each sub-vector to its nearest codeword.

    x:          (..., n, d)  with d = M * d'
    codebooks:  (M, E, d')
    returns codes: (..., n, M) int32 in [0, E)

    Distances are float32 at ``Precision.HIGHEST``, as the Pallas kernel
    (``kernels/pq_quantize``) computes them: the fused forward's codes and
    those this function recomputes for the backward, the decode cache and
    the oracle must be the same codes, and the TPU's default precision
    rounds the codebooks to bfloat16.
    """
    m, e, dp = codebooks.shape
    *lead, n, d = x.shape
    assert d == m * dp, (x.shape, codebooks.shape)
    xs = x.reshape(*lead, n, m, dp).astype(jnp.float32)
    # ||x - c||^2 = ||x||^2 - 2 x.c + ||c||^2 ; ||x||^2 constant over argmin.
    dots = jnp.einsum("...nmd,med->...nme", xs, codebooks,
                      precision=jax.lax.Precision.HIGHEST)
    c2 = jnp.sum(codebooks * codebooks, axis=-1)  # (M, E)
    dist = c2 - 2.0 * dots
    return jnp.argmin(dist, axis=-1).astype(jnp.int32)  # (..., n, M)


def quantization_error(x: jax.Array, codebooks: jax.Array,
                       codes: Optional[jax.Array] = None) -> jax.Array:
    """Mean squared distance between vectors and their codewords (DKM error)."""
    m, e, dp = codebooks.shape
    *lead, n, d = x.shape
    if codes is None:
        codes = assign(x, codebooks)
    xs = x.reshape(*lead, n, m, dp).astype(jnp.float32)
    sel = jnp.take_along_axis(
        codebooks[None], codes.reshape(-1, m)[..., None, None], axis=-2)
    sel = sel.reshape(*lead, n, m, dp)
    return jnp.mean(jnp.sum((xs - sel) ** 2, axis=-1))


def match_scores(codes_q: jax.Array, codes_k: jax.Array,
                 num_codewords: int) -> jax.Array:
    """Integer similarity s(q,k) = #matching codewords (Eq. 6), MXU-friendly.

    codes_q: (..., nq, M) int32; codes_k: (..., nk, M) int32
    returns (..., nq, nk) float32 counts in [0, M].

    Implemented as a one-hot inner product so the O(nq*nk) term runs on the
    MXU as a (nq, M*E) x (M*E, nk) matmul instead of M broadcast compares.
    """
    e = num_codewords
    oh_q = jax.nn.one_hot(codes_q, e, dtype=jnp.bfloat16)   # (..., nq, M, E)
    oh_k = jax.nn.one_hot(codes_k, e, dtype=jnp.bfloat16)   # (..., nk, M, E)
    *lead_q, nq, m, _ = oh_q.shape
    *lead_k, nk, _, _ = oh_k.shape
    scores = jnp.einsum(
        "...qz,...kz->...qk",
        oh_q.reshape(*lead_q, nq, m * e),
        oh_k.reshape(*lead_k, nk, m * e),
        preferred_element_type=jnp.float32)
    return scores


def ema_update(codebooks: jax.Array, x: jax.Array,
               codes: Optional[jax.Array] = None,
               ema: float = 0.05) -> jax.Array:
    """One EMA k-means step: move each codeword toward the mean of its
    assigned sub-vectors.  Pure function — caller applies it every
    ``update_interval`` steps (paper §5.1: every 20 mini-batches).
    """
    m, e, dp = codebooks.shape
    d = m * dp
    xs = x.reshape(-1, m, dp).astype(jnp.float32)           # (N, M, d')
    if codes is None:
        codes = assign(x.reshape(-1, d), codebooks)         # (N, M)
    else:
        codes = codes.reshape(-1, m)
    oh = jax.nn.one_hot(codes, e, dtype=jnp.float32)        # (N, M, E)
    counts = jnp.sum(oh, axis=0)                            # (M, E)
    sums = jnp.einsum("nme,nmd->med", oh, xs)               # (M, E, d')
    means = sums / jnp.maximum(counts[..., None], 1.0)
    # codewords with no assignment stay put
    upd = jnp.where(counts[..., None] > 0, means, codebooks)
    return (1.0 - ema) * codebooks + ema * upd


def init_codebooks_from_data(x: jax.Array, cfg: PQConfig,
                             key: jax.Array) -> jax.Array:
    """k-means++-lite init: random sample of sub-vectors as codewords."""
    m, e, dp = cfg.num_books, cfg.num_codewords, cfg.code_dim
    xs = x.reshape(-1, m, dp).astype(jnp.float32)
    n = xs.shape[0]
    idx = jax.random.choice(key, n, (e,), replace=n < e)
    return jnp.transpose(xs[idx], (1, 0, 2))  # (M, E, d')
