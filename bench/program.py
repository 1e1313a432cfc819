"""The system under test as a configuration file names it.

A configuration's "program" block names the registry architecture, the
fields it overrides, the fine-tuning variant, the SPT settings and the
dtype. ``model_config`` builds the program's ModelConfig from it and checks
it against the file's "model" block, which the reference reads, field by
field as the configuration's reference module maps them
(``PROGRAM_FIELDS``), so the two cannot drift apart unseen.
"""
from __future__ import annotations

import dataclasses
import pathlib

from bench import common


def _get(obj, field):
    """A program field: a dotted attribute path, or a function of the
    ModelConfig."""
    if callable(field):
        return field(obj)
    for part in field.split("."):
        obj = getattr(obj, part)
    return obj


def model_config(config: dict, bench_dir: pathlib.Path = common.BENCH):
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
    ref = common.named_module(config, "reference", bench_dir)
    for ref_name, prog_name in ref.PROGRAM_FIELDS.items():
        got = _get(cfg, prog_name)
        if got != model[ref_name]:
            raise ValueError(
                f"program {getattr(prog_name, '__name__', prog_name)}="
                f"{got!r} but the configuration's model says {ref_name}="
                f"{model[ref_name]!r}")
    return cfg
