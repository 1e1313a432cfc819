"""End-to-end system tests: fine-tune -> checkpoint -> resume -> serve,
decode/train parity, and paper-claims validation at CPU scale."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import pq
from repro.core import sparse_attention as sa
from repro.core.params import init_tree
from repro.data.pipeline import DataConfig, synthetic_dataset
from repro.models import transformer
from repro.optim.adamw import OptimizerConfig
from repro.serving.engine import Engine
from repro.train.state import model_defs
from repro.train.trainer import Trainer, TrainerConfig


def _tiny_cfg():
    return dataclasses.replace(
        configs.get_smoke("qwen3-0.6b"), num_layers=2, d_model=64,
        num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256)


@pytest.mark.slow
def test_training_reduces_loss():
    cfg = _tiny_cfg()
    steps = 60
    data = synthetic_dataset(
        DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=8,
                   branching=2, seed=3), steps=steps + 1)
    t = Trainer(cfg, OptimizerConfig(lr=5e-3, warmup_steps=5,
                                     total_steps=steps),
                TrainerConfig(total_steps=steps, log_interval=1))
    rep = t.run(data)
    losses = [m["loss"] for m in rep["metrics"]]
    assert losses[-1] < losses[0] - 0.1, (losses[0], losses[-1])
    assert all(np.isfinite(l) for l in losses)


@pytest.mark.slow
def test_resume_continues_from_checkpoint(tmp_path):
    cfg = _tiny_cfg()
    d = str(tmp_path / "ck")
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=4)
    t1 = Trainer(cfg, OptimizerConfig(total_steps=20),
                 TrainerConfig(total_steps=10, ckpt_dir=d, ckpt_interval=5))
    t1.run(synthetic_dataset(dcfg, steps=11))
    t2 = Trainer(cfg, OptimizerConfig(total_steps=20),
                 TrainerConfig(total_steps=20, ckpt_dir=d, ckpt_interval=5))
    assert t2.start_step == 10
    rep = t2.run(synthetic_dataset(dcfg, steps=11))
    assert rep["final_step"] == 20


def test_trainer_places_state_by_state_specs():
    """On a mesh, every state leaf carries the NamedSharding of
    state_specs after init and after a step: a change that dropped the
    placement would leave the state on one device on a multi-chip mesh."""
    from jax.sharding import NamedSharding

    from repro.launch.mesh import make_mesh
    from repro.sharding import axis_rules, rules_for_mesh
    from repro.train.state import state_specs

    cfg = _tiny_cfg()
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = rules_for_mesh(mesh)
    want = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), state_specs(cfg, rules),
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))

    def check(state):
        got = [a.sharding for a in jax.tree_util.tree_leaves(state)]
        assert got == jax.tree_util.tree_leaves(want)

    with mesh, axis_rules(rules):
        t = Trainer(cfg, OptimizerConfig(total_steps=1),
                    TrainerConfig(total_steps=1), mesh=mesh, rules=rules)
        check(t.state)
        rep = t.run(synthetic_dataset(
            DataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                       global_batch=2), steps=2))
    assert rep["final_step"] == 1
    check(t.state)


def test_serve_after_training_deterministic():
    cfg = _tiny_cfg()
    params = init_tree(model_defs(cfg), jax.random.PRNGKey(0))
    engine = Engine(cfg, params, max_len=48, jit=True)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                          cfg.vocab_size, dtype=jnp.int32)}
    a = engine.generate(batch, steps=4)
    engine2 = Engine(cfg, params, max_len=48, jit=True)
    b = engine2.generate(batch, steps=4)
    assert a.tokens == b.tokens


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["qwen3-0.6b", "mamba2-780m",
                                  "recurrentgemma-9b"])
def test_decode_matches_train_forward(arch):
    """Logits from prefill+decode equal the full-sequence forward at the
    same positions (teacher-forcing parity) — the serving-path contract."""
    cfg = configs.get_smoke(arch)
    if cfg.window is not None:
        cfg = dataclasses.replace(cfg, window=None)
    # capacity drops are train-path-only (decode always fits): give the
    # dispatcher full slack so the parity check isolates the serving path
    cfg = cfg.with_spt(ffn_capacity_factor=8.0)
    params = init_tree(transformer.lm_defs(cfg), jax.random.PRNGKey(0))
    s = 24
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, s), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    hidden, _ = transformer.lm_hidden(params, cfg, {"tokens": tokens},
                                      remat=False)
    full_logits = transformer.logits_of(params, cfg, hidden)
    caches, logits_p = transformer.lm_prefill(
        params, cfg, {"tokens": tokens[:, :s - 2]}, max_len=s + 4)
    np.testing.assert_allclose(
        np.asarray(logits_p[:, -1], np.float32),
        np.asarray(full_logits[:, s - 3], np.float32), rtol=6e-2, atol=6e-2)
    caches, logits_d = transformer.lm_decode_step(
        params, cfg, caches, tokens[:, s - 2],
        jnp.asarray(s - 2, jnp.int32))
    np.testing.assert_allclose(
        np.asarray(logits_d[:, -1], np.float32),
        np.asarray(full_logits[:, s - 2], np.float32), rtol=6e-2, atol=6e-2)


# ------------------------------------------------- paper-claims validation
def test_paper_claim_attention_weight_concentration():
    """Fig. 3 analogue: top-15% softmax weights carry >> 50% of the mass
    for trained-ish (correlated) q/k; we check the skew exists even with
    random data at low temperature."""
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (64, 32)) * 2.0
    k = q + 0.5 * jax.random.normal(jax.random.fold_in(key, 1), (64, 32))
    w = jax.nn.softmax(q @ k.T / np.sqrt(32), axis=-1)
    ws = np.sort(np.asarray(w), axis=-1)[:, ::-1]
    top15 = ws[:, :int(0.15 * 64)].sum(-1).mean()
    assert top15 > 0.5, top15


def test_paper_claim_pq_recall_with_trained_codebooks():
    """§4.1: PQ top-L recall ~90% with codebooks matched to the data.
    We EMA-train codebooks on the key distribution and require >=60%
    recall at top-1/4 on correlated data (untrained floor is ~35%)."""
    key = jax.random.PRNGKey(0)
    pcfg = pq.PQConfig(head_dim=32, code_dim=8, num_codewords=16)
    base = jax.random.normal(key, (8, 32))        # 8 latent clusters
    assign_idx = jax.random.randint(jax.random.fold_in(key, 1), (1, 2, 128),
                                    0, 8)
    noise = 0.3 * jax.random.normal(jax.random.fold_in(key, 2),
                                    (1, 2, 128, 32))
    k = base[assign_idx] + noise
    q = base[assign_idx] + 0.3 * jax.random.normal(
        jax.random.fold_in(key, 3), (1, 2, 128, 32))
    cb = pq.init_codebooks_from_data(k, pcfg, jax.random.fold_in(key, 4))
    for _ in range(20):
        cb = pq.ema_update(cb, k.reshape(-1, 32), ema=0.3)
    scfg = sa.SparseAttentionConfig(pq=pcfg, top_fraction=0.25, min_l=4)
    rec = float(sa.selection_recall(q, k, cb, scfg, causal=True))
    assert rec >= 0.6, rec


def test_paper_claim_routed_ffn_flop_fraction():
    """§4.2/Table 4: routed FFN computes ~beta of the dense FFN FLOPs.
    Verified structurally: exactly G' of G blocks active per token."""
    from repro.core import routed_ffn as rf
    from repro.core import lora as lora_mod
    rcfg = rf.RoutedFFNConfig(d_model=32, d_ff=64, num_groups=8,
                              active_groups=3, capacity_factor=8.0)
    p = init_tree(rf.param_defs(rcfg, lora_mod.LoRAConfig(enabled=False)),
                  jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 32))
    choice, gate, probs = rf.route(x, p["router"], rcfg)
    assert choice.shape == (1, 16, 3)
    # distinct blocks per token
    c = np.asarray(choice)
    for tkn in c.reshape(-1, 3):
        assert len(set(tkn.tolist())) == 3
    plan_tokens = 16 * 3
    from repro.core import dispatch
    cap = dispatch.capacity(16, 8, 3, 8.0)
    plan = dispatch.make_plan(choice, gate, 8, cap)
    assert int(np.asarray(plan.slot_ok).sum()) == plan_tokens


def test_paper_claim_sparse_mha_memory_scaling():
    """§4.1: attention state scales O(nL), not O(n^2): the selection output
    is exactly (B, H, n, L) indices — 8x smaller at top-1/8."""
    n, frac = 256, 0.125
    pcfg = pq.PQConfig(head_dim=16, code_dim=8, num_codewords=16)
    scfg = sa.SparseAttentionConfig(pq=pcfg, top_fraction=frac, min_l=1)
    assert sa.top_l(n, scfg, None) == int(n * frac)
