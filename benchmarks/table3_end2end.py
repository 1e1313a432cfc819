"""Table 3 analogue: end-to-end fine-tuning of a scaled-down OPT-2.7B-family
model under Full / LoRA / SPT — wall time per step, quality (loss) after a
short budget, and the max-sequence-without-blowup proxy via compiled temps."""
import dataclasses

import jax
import jax.numpy as jnp

from benchmarks.common import compiled_temp_bytes, emit, time_fn
from repro.configs.paper_blocks import opt_2_7b
from repro.data.pipeline import DataConfig, synthetic_dataset
from repro.configs import apply_variant
from repro.optim.adamw import OptimizerConfig
from repro.train.trainer import Trainer, TrainerConfig


def _mini(variant: str):
    cfg = dataclasses.replace(
        opt_2_7b(num_layers=4), d_model=320, num_heads=4, num_kv_heads=4,
        head_dim=80, d_ff=1280, vocab_size=2048, max_position=4096)
    return apply_variant(cfg, variant)


def main(fast: bool = True) -> None:
    steps = 10 if fast else 40
    for variant in ("full", "lora", "spt"):
        cfg = _mini(variant)
        data = list(synthetic_dataset(
            DataConfig(vocab_size=cfg.vocab_size, seq_len=128,
                       global_batch=4, branching=2), steps=steps + 2))
        t = Trainer(cfg, OptimizerConfig(lr=2e-3, total_steps=steps),
                    TrainerConfig(total_steps=steps, log_interval=steps))
        import time
        t.run(iter(data[:1]))                     # compile
        t.tcfg = dataclasses.replace(t.tcfg, total_steps=steps)
        t0 = time.time()
        rep = t.run(iter(data[1:steps + 1]))
        dt = (time.time() - t0) / max(1, steps - 1) * 1e6
        last = rep["metrics"][-1] if rep["metrics"] else {"loss": float("nan")}
        emit(f"table3.{variant}", dt, f"loss={last['loss']:.3f}")


if __name__ == "__main__":
    main(fast=False)
