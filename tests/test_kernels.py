"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret=True),
over shapes and dtypes (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pq
from repro.core import sparse_attention as sa
from repro.core import routed_ffn as rf
from repro.core import lora as lora_mod
from repro.core.params import init_tree

# interpret-mode shape/dtype sweeps (~2-3 min): excluded from ci_fast.sh
pytestmark = pytest.mark.slow


def _cb(head_dim, code_dim=8, e=16, seed=0):
    cfg = pq.PQConfig(head_dim=head_dim, code_dim=code_dim, num_codewords=e)
    return cfg, init_tree(pq.param_defs(cfg), jax.random.PRNGKey(seed))["codebooks"]


# ------------------------------------------------------------ pq_quantize
@pytest.mark.parametrize("shape,dtype", [
    ((1, 1, 32, 16), jnp.float32),
    ((2, 3, 64, 32), jnp.float32),
    ((2, 2, 48, 64), jnp.bfloat16),
    ((1, 4, 128, 24), jnp.float32),
])
def test_pq_assign_kernel_matches_ref(shape, dtype):
    from repro.kernels.pq_quantize.ops import pq_assign
    from repro.kernels.pq_quantize.ref import pq_assign_ref
    cfg, cb = _cb(shape[-1], code_dim=8)
    x = jax.random.normal(jax.random.PRNGKey(1), shape).astype(dtype)
    got = pq_assign(x, cb, interpret=True)
    want = pq_assign_ref(x, cb)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pq_assign_kernel_tiling_invariance():
    from repro.kernels.pq_quantize.ops import pq_assign
    cfg, cb = _cb(32)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 2, 128, 32))
    a = pq_assign(x, cb, tile_n=32, interpret=True)
    b = pq_assign(x, cb, tile_n=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------ topl_select
@pytest.mark.parametrize("nq,nk,l,causal,window", [
    (32, 32, 8, True, None),
    (64, 64, 16, True, 24),
    (16, 48, 12, False, None),
    (64, 128, 32, True, None),
])
def test_topl_kernel_matches_ref(nq, nk, l, causal, window):
    from repro.kernels.topl_select.ops import topl_select, topl_thresholds
    from repro.kernels.topl_select.ref import thresholds_ref, topl_select_ref
    key = jax.random.PRNGKey(3)
    m = 4
    cq = jax.random.randint(key, (3, nq, m), 0, 16)
    ck = jax.random.randint(jax.random.PRNGKey(4), (3, nk, m), 0, 16)
    kw = dict(l=l, max_score=m, causal=causal, window=window)
    np.testing.assert_array_equal(
        np.asarray(topl_thresholds(cq, ck, interpret=True, **kw)),
        np.asarray(thresholds_ref(cq, ck, **kw)))
    ik, vk = topl_select(cq, ck, interpret=True, **kw)
    ir, vr = topl_select_ref(cq, ck, **kw)
    np.testing.assert_array_equal(np.asarray(ik), np.asarray(ir))
    np.testing.assert_array_equal(np.asarray(vk), np.asarray(vr))


# ------------------------------------------------------ sparse attention
@pytest.mark.parametrize("b,hq,hk,n,d,frac,causal,window,dtype", [
    (2, 4, 2, 64, 32, 0.25, True, None, jnp.float32),
    (1, 2, 2, 64, 16, 0.125, True, None, jnp.float32),
    (2, 4, 1, 32, 32, 0.5, True, 16, jnp.float32),
    (1, 4, 4, 128, 64, 0.25, True, None, jnp.bfloat16),
    (1, 2, 2, 48, 24, 0.25, False, None, jnp.float32),
])
def test_fused_sparse_attention_matches_ref(b, hq, hk, n, d, frac, causal,
                                            window, dtype):
    from repro.kernels.sparse_attention.ops import sparse_mha as k_mha
    pcfg, cb = _cb(d)
    scfg = sa.SparseAttentionConfig(pq=pcfg, top_fraction=frac, min_l=4,
                                    chunk_q=16)
    q = jax.random.normal(jax.random.PRNGKey(5), (b, hq, n, d)).astype(dtype)
    k = jax.random.normal(jax.random.PRNGKey(6), (b, hk, n, d)).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(7), (b, hk, n, d)).astype(dtype)
    out_k, _ = k_mha(q, k, v, cb, scfg, d ** -0.5, causal=causal,
                     window=window, interpret=True)
    out_r, _ = sa.sparse_mha(q, k, v, cb, scfg, d ** -0.5, causal=causal,
                             window=window)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=tol, atol=tol)


def test_fused_sparse_attention_backward_matches_ref():
    from repro.kernels.sparse_attention.ops import sparse_mha as k_mha
    pcfg, cb = _cb(32)
    scfg = sa.SparseAttentionConfig(pq=pcfg, top_fraction=0.25, min_l=4,
                                    chunk_q=16)
    q = jax.random.normal(jax.random.PRNGKey(8), (1, 2, 32, 32))
    k = jax.random.normal(jax.random.PRNGKey(9), (1, 2, 32, 32))
    v = jax.random.normal(jax.random.PRNGKey(10), (1, 2, 32, 32))

    def fk(q, k, v):
        return jnp.sum(k_mha(q, k, v, cb, scfg, 32 ** -0.5,
                             interpret=True)[0] ** 2)

    def fr(q, k, v):
        return jnp.sum(sa.sparse_mha(q, k, v, cb, scfg, 32 ** -0.5)[0] ** 2)

    gk = jax.grad(fk, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("granularity", ["qhead", "kvgroup"])
@pytest.mark.parametrize("ragged", [False, True], ids=["full", "ragged"])
def test_masked_form_matches_gather_form(granularity, ragged):
    """The masked form (the fused op's backward, and ragged prefill on the
    kernel tier) selects the gather form's top-L set — per-row budgets and
    kvgroup selection included — so outputs and gradients agree."""
    pcfg, cb = _cb(32)
    scfg = sa.SparseAttentionConfig(pq=pcfg, top_fraction=0.25, min_l=4,
                                    chunk_q=16,
                                    select_granularity=granularity)
    q = jax.random.normal(jax.random.PRNGKey(8), (2, 4, 32, 32))
    k = jax.random.normal(jax.random.PRNGKey(9), (2, 2, 32, 32))
    v = jax.random.normal(jax.random.PRNGKey(10), (2, 2, 32, 32))
    lens = jnp.asarray([32, 19], jnp.int32) if ragged else None

    def loss(form):
        return lambda q, k, v: jnp.sum(form(
            q, k, v, cb, scfg, 32 ** -0.5, seq_lengths=lens)[0] ** 2)

    for a, b in zip(jax.value_and_grad(loss(sa.sparse_mha_masked),
                                       argnums=(0, 1, 2))(q, k, v),
                    jax.value_and_grad(loss(sa.sparse_mha),
                                       argnums=(0, 1, 2))(q, k, v)):
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------ routed ffn
@pytest.mark.parametrize("bsz,s,d,dff,g,act_g,gated,act", [
    (2, 16, 32, 64, 4, 2, False, "relu"),
    (1, 24, 32, 64, 4, 2, True, "gelu"),
    (2, 16, 48, 96, 8, 4, True, "silu"),
    (1, 32, 64, 128, 4, 3, False, "gelu"),
])
def test_routed_ffn_kernel_matches_ref(bsz, s, d, dff, g, act_g, gated, act):
    from repro.kernels.routed_ffn.ops import routed_ffn as k_rffn
    lcfg = lora_mod.LoRAConfig(rank=4, alpha=4.0)
    rcfg = rf.RoutedFFNConfig(d_model=d, d_ff=dff, num_groups=g,
                              active_groups=act_g, capacity_factor=4.0,
                              gated=gated, activation=act)
    p = init_tree(rf.param_defs(rcfg, lcfg), jax.random.PRNGKey(11))
    x = jax.random.normal(jax.random.PRNGKey(12), (bsz, s, d))
    yk, _ = k_rffn(x, p, rcfg, lcfg, interpret=True)
    yr, _ = rf.routed_ffn(x, p, rcfg, lcfg, impl="grouped")
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr),
                               rtol=2e-2, atol=2e-3)


def test_routed_ffn_kernel_backward_matches_ref():
    from repro.kernels.routed_ffn.ops import routed_ffn as k_rffn
    lcfg = lora_mod.LoRAConfig(rank=4, alpha=4.0)
    rcfg = rf.RoutedFFNConfig(d_model=32, d_ff=64, num_groups=4,
                              active_groups=2, capacity_factor=4.0,
                              gated=True, activation="gelu")
    p = init_tree(rf.param_defs(rcfg, lcfg), jax.random.PRNGKey(13))
    x = jax.random.normal(jax.random.PRNGKey(14), (2, 16, 32))

    def fk(p):
        return jnp.sum(k_rffn(x, p, rcfg, lcfg, interpret=True)[0] ** 2)

    def fr(p):
        return jnp.sum(rf.routed_ffn(x, p, rcfg, lcfg, impl="grouped")[0] ** 2)

    gk = jax.grad(fk)(p)
    gr = jax.grad(fr)(p)
    flat_k = jax.tree_util.tree_leaves_with_path(gk)
    flat_r = {jax.tree_util.keystr(kp): v
              for kp, v in jax.tree_util.tree_leaves_with_path(gr)}
    for kp, v in flat_k:
        np.testing.assert_allclose(
            np.asarray(v), np.asarray(flat_r[jax.tree_util.keystr(kp)]),
            rtol=2e-2, atol=2e-3, err_msg=jax.tree_util.keystr(kp))
