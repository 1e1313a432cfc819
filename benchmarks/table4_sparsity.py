"""Table 4 analogue: MHA/FFN time + memory at different sparsity strengths
(MHA non-zero fraction 1/4 vs 1/8; FFN active fraction 3/4 vs 1/2)."""
import dataclasses

from benchmarks.blocks import bench_block, reduced
from benchmarks.common import emit


def main(fast: bool = True) -> None:
    scale = 8 if fast else 4
    kw = dict(scale=scale, batch=2 if fast else 4, seq=128 if fast else 256)
    r = bench_block("opt-2048", "lora", module="mha", **kw)
    emit("table4.mha.lora", r["us"], f"temp_mb={r['temp_mb']:.1f}")
    for frac, tag in ((0.25, "1_4"), (0.125, "1_8")):
        import benchmarks.blocks as B
        cfg = B.reduced("opt-2048", scale, "spt").with_spt(
            attn_top_fraction=frac)
        step, params = B.block_step(cfg, "mha")
        import jax, jax.numpy as jnp
        from benchmarks.common import compiled_temp_bytes, time_fn
        x = jax.random.normal(jax.random.PRNGKey(1),
                              (kw["batch"], kw["seq"], cfg.d_model)
                              ).astype(jnp.bfloat16)
        us = time_fn(jax.jit(step), params, x, iters=3, warmup=1)
        ax = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        mem = compiled_temp_bytes(step, ax,
                                  jax.ShapeDtypeStruct(x.shape, x.dtype))
        emit(f"table4.mha.spt_{tag}", us, f"temp_mb={(mem or 0) / 1e6:.1f}")
    r = bench_block("opt-2048", "lora", module="ffn", **kw)
    emit("table4.ffn.lora", r["us"], f"temp_mb={r['temp_mb']:.1f}")
    for active, tag in ((6, "3_4"), (4, "1_2")):
        import benchmarks.blocks as B
        cfg = B.reduced("opt-2048", scale, "spt").with_spt(
            ffn_active_groups=active)
        step, params = B.block_step(cfg, "ffn")
        import jax, jax.numpy as jnp
        from benchmarks.common import compiled_temp_bytes, time_fn
        x = jax.random.normal(jax.random.PRNGKey(1),
                              (kw["batch"], kw["seq"], cfg.d_model)
                              ).astype(jnp.bfloat16)
        us = time_fn(jax.jit(step), params, x, iters=3, warmup=1)
        ax = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        mem = compiled_temp_bytes(step, ax,
                                  jax.ShapeDtypeStruct(x.shape, x.dtype))
        emit(f"table4.ffn.spt_{tag}", us, f"temp_mb={(mem or 0) / 1e6:.1f}")


if __name__ == "__main__":
    main(fast=False)
