"""The system under test as a configuration file names it.

A configuration's "program" block names the registry architecture, the
fields it overrides, the fine-tuning variant, the SPT settings and the
dtype. ``model_config`` builds the program's ModelConfig from it and checks
it against the file's "model" block, which the reference reads, so the two
cannot drift apart unseen.
"""
from __future__ import annotations

import dataclasses

from bench import common

# reference field -> program field (ModelConfig, or its SPTConfig as spt.*)
_SAME = {
    "layers": "num_layers", "d_model": "d_model", "heads": "num_heads",
    "kv_heads": "num_kv_heads", "head_dim": "resolved_head_dim",
    "d_ff": "d_ff", "vocab_rows": "padded_vocab", "norm": "norm",
    "qk_norm": "qk_norm", "rope_theta": "rope_theta", "gated": "gated_ffn",
    "activation": "activation", "tie_embeddings": "tie_embeddings",
    "code_dim": "spt.pq_code_dim", "codewords": "spt.pq_codewords",
    "top_fraction": "spt.attn_top_fraction", "min_l": "spt.attn_min_l",
    "pad_l_to": "spt.attn_pad_l_to", "groups": "spt.ffn_groups",
    "active_groups": "spt.ffn_active_groups",
    "capacity_factor": "spt.ffn_capacity_factor",
    "capacity_pad": "spt.dispatch_pad", "lora_rank": "spt.lora.rank",
    "lora_alpha": "spt.lora.alpha", "lb_weight": "spt.lb_loss_weight",
}


def _get(obj, dotted: str):
    for part in dotted.split("."):
        obj = getattr(obj, part)
    return obj


def model_config(config: dict):
    """The program's ModelConfig for a configuration file."""
    common.add_program_path()
    import jax.numpy as jnp
    from repro import configs
    from repro.core.lora import LoRAConfig

    prog = config["program"]
    cfg = configs.get_config(prog["arch"])
    cfg = dataclasses.replace(cfg, **prog.get("overrides", {}))
    cfg = configs.apply_variant(cfg, prog["variant"])
    spt = dict(prog.get("spt", {}))
    if "lora" in spt:
        spt["lora"] = LoRAConfig(**spt["lora"])
    cfg = cfg.with_spt(**spt)
    cfg = dataclasses.replace(cfg, dtype=jnp.dtype(prog["dtype"]).type)
    model = config["model"]
    for ref_name, prog_name in _SAME.items():
        got = _get(cfg, prog_name)
        if got != model[ref_name]:
            raise ValueError(f"program {prog_name}={got!r} but the "
                             f"configuration's model says {ref_name}="
                             f"{model[ref_name]!r}")
    if (cfg.positional == "learned") != model["learned_pos"]:
        raise ValueError("learned positions differ from the configuration")
    return cfg
