"""Quickstart: build a small SPT model, run the Model Adapter workflow,
fine-tune a few steps, and compare Full / LoRA / SPT step costs.

    PYTHONPATH=src python examples/quickstart.py
"""
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.core import adapter
from repro.core.params import count_params, init_tree, trainable_mask
from repro.data.pipeline import DataConfig, synthetic_dataset
from repro.configs import apply_variant
from repro.models import transformer
from repro.optim.adamw import OptimizerConfig
from repro.train.state import model_defs
from repro.train.trainer import Trainer, TrainerConfig


def main():
    cfg = configs.get_smoke("qwen3-0.6b")
    print(f"arch: {cfg.name} (reduced)  layers={cfg.num_layers} "
          f"d={cfg.d_model} heads={cfg.num_heads}/{cfg.num_kv_heads}")

    # --- the paper's Model Adapter workflow: dense -> SPT ------------
    dense_cfg = apply_variant(cfg, "full")
    dense_params = init_tree(transformer.lm_defs(dense_cfg),
                             jax.random.PRNGKey(0))
    spt_params = adapter.adapt(dense_params, dense_cfg, cfg,
                               jax.random.PRNGKey(1))
    print(adapter.upgrade_report(dense_params, spt_params)[:400], "...")

    # --- parameter accounting ----------------------------------------
    defs = model_defs(cfg)
    total = count_params(defs)
    trainable = count_params(defs, only_trainable=True)
    print(f"params: total={total/1e6:.2f}M  trainable (LoRA/router/PQ)="
          f"{trainable/1e6:.3f}M  ({100*trainable/total:.2f}%)")

    # --- short fine-tune on the synthetic corpus ---------------------
    steps = 30
    data = synthetic_dataset(
        DataConfig(vocab_size=cfg.vocab_size, seq_len=128, global_batch=8),
        steps=steps + 1)
    trainer = Trainer(cfg, OptimizerConfig(lr=3e-3, total_steps=steps),
                      TrainerConfig(total_steps=steps, log_interval=10))
    report = trainer.run(data)
    for m in report["metrics"]:
        print(f"  step {m['step']:>3}  loss={m['loss']:.3f} "
              f"acc={m['accuracy']:.3f}")

    # --- Full vs LoRA vs SPT one-step wall time (CPU, compiled) ------
    for variant in ("full", "lora", "spt"):
        vcfg = apply_variant(cfg, variant)
        t = Trainer(vcfg, OptimizerConfig(), TrainerConfig(total_steps=3))
        d = synthetic_dataset(
            DataConfig(vocab_size=cfg.vocab_size, seq_len=128,
                       global_batch=8), steps=4)
        batches = list(d)
        t.run(iter(batches[:1]))        # compile
        t0 = time.time()
        t.run(iter(batches[1:3]))
        print(f"  {variant:>5}: {(time.time()-t0)/2*1e3:.0f} ms/step (CPU)")


if __name__ == "__main__":
    main()
