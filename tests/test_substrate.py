"""Training substrate: checkpointing, optimizer, straggler monitor, loss,
gradient compression, adapter function-preservation."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import adapter
from repro.core.params import init_tree, partition, combine, trainable_mask
from repro.optim.adamw import OptimizerConfig, adamw_init, adamw_update
from repro.optim.compress import (CompressionConfig, compress_tree,
                                  decompress_tree)
from repro.train import checkpoint
from repro.train.loss import lm_cross_entropy
from repro.train.state import init_state, model_defs
from repro.train.straggler import StepTimeMonitor, StragglerConfig


# ------------------------------------------------------------ checkpoint
def test_checkpoint_roundtrip_and_gc(tmp_path):
    cfg = configs.get_smoke("qwen3-0.6b")
    state = init_state(cfg, jax.random.PRNGKey(0))
    d = str(tmp_path / "ck")
    for step in (10, 20, 30, 40):
        checkpoint.save(state, step, d, keep=2)
    assert checkpoint.latest_step(d) == 40
    kept = sorted(os.listdir(d))
    assert kept == ["step_00000030", "step_00000040"]
    restored = checkpoint.restore(d)
    flat_a = jax.tree_util.tree_leaves(state)
    flat_b = jax.tree_util.tree_leaves(restored)
    assert len(flat_a) == len(flat_b)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_detects_corruption(tmp_path):
    cfg = configs.get_smoke("mamba2-780m")
    state = init_state(cfg, jax.random.PRNGKey(0))
    d = str(tmp_path / "ck")
    path = checkpoint.save(state, 1, d)
    npz = os.path.join(path, "arrays.npz")
    with open(npz, "r+b") as f:
        f.seek(100)
        f.write(b"\x00\x01\x02")
    with pytest.raises(IOError):
        checkpoint.restore(d)


def test_partition_combine_roundtrip():
    cfg = configs.get_smoke("gemma-7b")
    defs = model_defs(cfg)
    params = init_tree(defs, jax.random.PRNGKey(0))
    mask = trainable_mask(defs)
    train, frozen = partition(params, mask)
    back = combine(train, frozen)
    for (kp, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(params),
            jax.tree_util.tree_leaves_with_path(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # frozen tree holds no LoRA; train tree holds no base weights
    train_paths = [jax.tree_util.keystr(kp) for kp, _ in
                   jax.tree_util.tree_leaves_with_path(train)]
    assert all(("lora" in p or "router" in p or "codebooks" in p)
               for p in train_paths)


# ------------------------------------------------------------- optimizer
def test_adamw_decreases_quadratic():
    w = {"a": jnp.array([3.0, -2.0]), "b": jnp.array([[1.5]])}
    opt = adamw_init(w)
    cfg = OptimizerConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                          total_steps=100, schedule="constant")

    def loss(w):
        return jnp.sum(w["a"] ** 2) + jnp.sum(w["b"] ** 2)

    l0 = float(loss(w))
    for i in range(50):
        g = jax.grad(loss)(w)
        w, opt, _ = adamw_update(w, g, opt, jnp.asarray(i), cfg)
    assert float(loss(w)) < l0 * 0.05


def test_grad_clip_caps_update_norm():
    w = {"a": jnp.array([1.0])}
    opt = adamw_init(w)
    cfg = OptimizerConfig(lr=1.0, grad_clip=1e-3, weight_decay=0.0,
                          warmup_steps=0, schedule="constant")
    g = {"a": jnp.array([1e6])}
    _, _, m = adamw_update(w, g, opt, jnp.asarray(0), cfg)
    assert float(m["grad_norm"]) > 1e5  # raw norm reported


# ------------------------------------------------------------ compression
@pytest.mark.parametrize("scheme", ["int8", "topk", "none"])
def test_compression_roundtrip(scheme):
    tree = {"x": jnp.asarray(np.random.default_rng(0).normal(
        size=(32, 16)).astype(np.float32))}
    cfg = CompressionConfig(scheme=scheme, topk_fraction=0.5)
    out = decompress_tree(compress_tree(tree, cfg), cfg)
    x, y = np.asarray(tree["x"]), np.asarray(out["x"])
    if scheme == "none":
        np.testing.assert_array_equal(x, y)
    elif scheme == "int8":
        assert np.abs(x - y).max() <= np.abs(x).max() / 127.0 + 1e-6
    else:  # topk keeps the largest half exactly
        kept = np.abs(x).ravel() >= np.median(np.abs(x))
        np.testing.assert_allclose(y.ravel()[kept], x.ravel()[kept],
                                   rtol=1e-6)


# ------------------------------------------------------------- straggler
def test_straggler_monitor_flags_outliers():
    mon = StepTimeMonitor(StragglerConfig(window=50, z_threshold=3.0,
                                          min_samples=10, act_density=0.15))
    for i in range(30):
        assert not mon.record(i, 0.10 + 0.001 * (i % 3))
    flagged = mon.record(31, 1.5)
    assert flagged and mon.events
    assert not mon.should_act()
    for i in range(10):
        mon.record(40 + i, 1.5 + 0.1 * i)
    assert mon.should_act()


# ------------------------------------------------------------------ loss
def test_chunked_loss_equals_direct():
    cfg = configs.get_smoke("qwen3-0.6b")
    from repro.models import transformer
    params = init_tree(transformer.lm_defs(cfg), jax.random.PRNGKey(0))
    hidden = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model)
                               ).astype(jnp.bfloat16)
    labels = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                                cfg.vocab_size)
    labels = labels.at[0, :3].set(-1)  # masked positions
    l_chunk, m = lm_cross_entropy(params, cfg, hidden, labels, chunk=4)
    l_full, _ = lm_cross_entropy(params, cfg, hidden, labels, chunk=16)
    np.testing.assert_allclose(float(l_chunk), float(l_full), rtol=1e-5)
    assert float(m["tokens"]) == 2 * 16 - 3


# ---------------------------------------------------------------- adapter
def test_adapter_preserves_function_at_identity_settings():
    """Dense model == adapted SPT model when sparsity is a no-op:
    top_fraction=1 (all keys kept) and active_groups == groups (all blocks
    active), LoRA zero-init.  This is the paper's Model Adapter contract."""
    from repro.configs import apply_variant
    from repro.models import transformer
    base = configs.get_smoke("h2o-danube-1.8b")
    base = dataclasses.replace(base, window=None)
    spt_cfg = base.with_spt(attn_top_fraction=1.0, attn_min_l=1,
                            ffn_active_groups=base.spt.ffn_groups,
                            ffn_capacity_factor=8.0)
    dense_cfg = apply_variant(base, "full")
    dense_params = init_tree(transformer.lm_defs(dense_cfg),
                             jax.random.PRNGKey(0))
    adapted = adapter.adapt(dense_params, dense_cfg, spt_cfg,
                            jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                                base.vocab_size)
    h_dense, _ = transformer.lm_hidden(dense_params, dense_cfg,
                                       {"tokens": tokens}, remat=False)
    h_spt, _ = transformer.lm_hidden(adapted, spt_cfg,
                                     {"tokens": tokens}, remat=False)
    np.testing.assert_allclose(np.asarray(h_dense, np.float32),
                               np.asarray(h_spt, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_compile_cache_env_wins_else_fixed_ignored_path(monkeypatch):
    """Entry points keep the persistent compile cache where
    JAX_COMPILATION_CACHE_DIR says, setting nothing in code; without it,
    at one fixed path in the checkout that .gitignore lists."""
    import pathlib

    from repro.launch import compile_cache as cc
    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "cache-from-env")
        assert cc.use_compile_cache() == "cache-from-env"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert cc.use_compile_cache() == str(cc.CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(cc.CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    root = pathlib.Path(__file__).resolve().parents[1]
    assert cc.CACHE_DIR.parent == root
    assert f"{cc.CACHE_DIR.name}/" in (root / ".gitignore").read_text().split()
