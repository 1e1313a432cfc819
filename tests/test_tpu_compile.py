"""Compile every main-path Pallas kernel for a described TPU v5e chip at
qwen3-0.6b widths (head_dim 128, 16/8 heads, d_ff 3072, PQ M=16).

Nothing runs: each kernel is lowered and compiled by the TPU compiler for
one chip of a ``v5e:2x2`` topology that is described, not attached.  The
compiler refuses what interpret mode accepts — blocks off the (8, 128)
tiling, unaligned DMA slices, primitives Mosaic cannot lower, fast-memory
overruns — so these tests guard the chip build on a CPU-only machine.
The topology is described inside a fixture, never at import time.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core import pq
from repro.core import routed_ffn as rf
from repro.core.params import init_tree
from repro.kernels.pq_quantize import ops as pq_ops
from repro.kernels.routed_ffn import ops as rffn_ops
from repro.kernels.routed_ffn.routed_ffn import (decode_ffn_kernel,
                                                 grouped_ffn_kernel)
from repro.kernels.sparse_attention import ops as sa_ops
from repro.models import attention, ffn, transformer
from repro.train.state import model_defs

CFG = configs.get_config("qwen3-0.6b")
HQ, HK, DH = CFG.num_heads, CFG.num_kv_heads, CFG.resolved_head_dim
D, M = CFG.d_model, DH // CFG.spt.pq_code_dim
PS = CFG.spt.kv_page_size


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory placed on one described chip; compiles in
    this module read nothing from (or write to) the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


def _codebooks(spec):
    shape = jax.eval_shape(lambda: init_tree(
        pq.param_defs(attention._pq_config(CFG)),
        jax.random.PRNGKey(0)))["codebooks"]
    return spec(shape.shape, shape.dtype)


def _ffn_params(spec):
    tree = jax.eval_shape(lambda: init_tree(
        rf.param_defs(ffn._routed_cfg(CFG), CFG.spt.lora),
        jax.random.PRNGKey(0)))
    return jax.tree_util.tree_map(lambda s: spec(s.shape, s.dtype), tree)


def test_pq_assign(spec):
    _compile(lambda x, cb: pq_ops.pq_assign(x, cb, interpret=False),
             spec((4, HK, 1024, DH), CFG.dtype), _codebooks(spec))


def test_sparse_prefill(spec):
    """Fine-tuning forward: pq_assign + threshold + fused attention."""
    b, s = 4, 1024

    def fwd(q, k, v, cb):
        return sa_ops.sparse_mha(q, k, v, cb, attention._sa_config(CFG),
                                 DH ** -0.5, causal=True,
                                 interpret=False)[0]

    _compile(fwd, spec((b, HQ, s, DH), CFG.dtype),
             spec((b, HK, s, DH), CFG.dtype), spec((b, HK, s, DH), CFG.dtype),
             _codebooks(spec))


def test_sparse_prefill_float32_highest_precision(spec):
    """The kernel checks of chip_smoke.py run the prefill kernels on
    float32 inputs under "highest" matmul precision."""
    b, s, f32 = 1, 1024, jnp.float32

    def fwd(q, k, v, cb):
        return sa_ops.sparse_mha(q, k, v, cb, attention._sa_config(CFG),
                                 DH ** -0.5, causal=True,
                                 interpret=False)[0]

    with jax.default_matmul_precision("highest"):
        _compile(fwd, spec((b, HQ, s, DH), f32), spec((b, HK, s, DH), f32),
                 spec((b, HK, s, DH), f32), _codebooks(spec))


@pytest.mark.parametrize("fuse", [True, False], ids=["fused", "two_pass"])
@pytest.mark.parametrize("s", [2048, 32768])
def test_sparse_decode(spec, fuse, s):
    b = 8

    def dec(q, k, v, codes, cb, valid):
        return sa_ops.sparse_mha_decode(
            q, k, v, codes, cb, attention._sa_config(CFG), DH ** -0.5,
            valid, interpret=False, fuse=fuse)

    _compile(dec, spec((b, HQ, 1, DH), CFG.dtype),
             spec((b, HK, s, DH), CFG.dtype), spec((b, HK, s, DH), CFG.dtype),
             spec((b, HK, s, M), jnp.int8), _codebooks(spec),
             spec((b, s), jnp.bool_))


# A pool that fills most of one chip's KV budget: 28 layers x 800 pages x
# (K + V + codes) ~ 12 GB of v5e's 16 GB; the kernel sees one layer's pool.
@pytest.mark.parametrize("pool,mp", [(64, 8), (800, 256)])
def test_sparse_decode_paged(spec, pool, mp):
    b = 8

    def dec(q, k, v, codes, cb, valid, pt):
        return sa_ops.sparse_mha_decode_paged(
            q, k, v, codes, cb, attention._sa_config(CFG), DH ** -0.5,
            valid, pt, interpret=False)

    _compile(dec, spec((b, HQ, 1, DH), CFG.dtype),
             spec((pool, HK, PS, DH), CFG.dtype),
             spec((pool, HK, PS, DH), CFG.dtype),
             spec((pool, HK, PS, M), jnp.int8), _codebooks(spec),
             spec((b, mp * PS), jnp.bool_), spec((b, mp), jnp.int32))


def test_sparse_decode_paged_highest_precision(spec):
    """Under "highest" matmul precision every in-kernel dot carries fp32
    contract precision, which Mosaic refuses for bf16 operands."""
    with jax.default_matmul_precision("highest"):
        test_sparse_decode_paged(spec, 64, 8)


def test_dense_decode_paged(spec):
    b, pool, mp = 8, 800, 256

    def dec(q, k, v, valid, pt):
        return sa_ops.dense_mha_decode_paged(q, k, v, DH ** -0.5, valid, pt,
                                             interpret=False)

    _compile(dec, spec((b, HQ, 1, DH), CFG.dtype),
             spec((pool, HK, PS, DH), CFG.dtype),
             spec((pool, HK, PS, DH), CFG.dtype),
             spec((b, mp * PS), jnp.bool_), spec((b, mp), jnp.int32))


def test_routed_ffn_grouped(spec):
    def fwd(p, x):
        return rffn_ops.routed_ffn(x, p, ffn._routed_cfg(CFG), CFG.spt.lora,
                                   interpret=False)[0]

    _compile(fwd, _ffn_params(spec), spec((4, 1024, D), CFG.dtype))


def test_routed_ffn_decode(spec):
    def dec(p, x):
        return rffn_ops.routed_ffn_decode(x, p, ffn._routed_cfg(CFG),
                                          CFG.spt.lora, interpret=False)[0]

    _compile(dec, _ffn_params(spec), spec((8, 1, D), CFG.dtype))


@pytest.mark.parametrize("arch", ["recurrentgemma-9b", "grok-1-314b"])
def test_routed_ffn_wide(spec, arch):
    """The widest routed FFN (d 4096) and MoE experts (d 6144, F 32768)
    run the same kernels; their tiles must shrink to fit VMEM."""
    cfg = configs.get_config(arch)
    d, r, bf16 = cfg.d_model, CFG.spt.lora.rank, jnp.bfloat16
    g = cfg.num_experts or cfg.spt.ffn_groups
    f = cfg.d_ff if cfg.num_experts else cfg.d_ff // g
    w = lambda *shape: spec(shape, bf16)
    lp = {"lora_inner": {"b": w(d, r), "c": w(g, r, f)},
          "lora_gate": {"b": w(d, r), "c": w(g, r, f)},
          "lora_outer": {"b": w(g, f, r), "c": w(r, d)}}
    weights = (w(g, d, f), w(g, f, d), w(g, d, f), lp)
    _compile(lambda x, i, wi, wo, wg, lp: grouped_ffn_kernel(
        x, i, wi, wo, wg, lp, act=cfg.activation, interpret=False),
        w(2, 1024, d), spec((2, g, 256), jnp.int32), *weights)
    _compile(lambda x, c, gt, wi, wo, wg, lp: decode_ffn_kernel(
        x, c, gt, wi, wo, wg, lp, act=cfg.activation, interpret=False),
        w(8, d), spec((8, 2), jnp.int32), spec((8, 2), jnp.float32),
        *weights)


def test_ragged_prefill_batch_one(spec):
    """The serving prefill of ONE row compiles.  From position 0 XLA folds
    the cache scatter's indices and updates into one iota, and the TPU
    compiler aborts fusing such a scatter unless they stay apart
    (attention.write_cache)."""
    cfg = configs.get_smoke("qwen3-0.6b")
    params = jax.tree_util.tree_map(
        lambda s: spec(s.shape, s.dtype),
        jax.eval_shape(lambda: init_tree(model_defs(cfg),
                                         jax.random.PRNGKey(0))))
    jax.jit(lambda p, t, n: transformer.lm_prefill_ragged(
        p, cfg, {"tokens": t}, n, 1088)).lower(
            params, spec((1, 128), jnp.int32), spec((1,), jnp.int32)).compile()
