"""Step builders + sharding spec assembly for pjit lowering.

Everything the dry-run, the trainer, and the server share lives here:
  * build_train_step(cfg, ocfg)   — fwd + bwd + AdamW on the LoRA subtree
  * prefill / decode step fns     — serving-side lowerables
  * *_shardings helpers           — NamedSharding trees from logical rules
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import ModelConfig, ShapeSpec
from repro.core import params as P
from repro.optim.adamw import OptimizerConfig, adamw_update
from repro.serving import engine
from repro.sharding.context import spec_for
from repro.train import state as S
from repro.train.loss import lm_cross_entropy


# ------------------------------------------------------------- steps
def build_train_step(cfg: ModelConfig, ocfg: OptimizerConfig,
                     loss_chunk: int = 512) -> Callable:
    def spt_train_step(state: dict, batch: Dict[str, jax.Array]):
        def loss_fn(train):
            params = P.combine(train, state["frozen"])
            hidden, aux = S.model_hidden(params, cfg, batch, remat=True)
            lm_loss, stats = lm_cross_entropy(params, cfg, hidden,
                                              batch["labels"], loss_chunk)
            nl = max(1, cfg.num_layers)
            total = lm_loss
            total += cfg.spt.lb_loss_weight * aux.get("lb_loss", 0.0) / nl
            if cfg.spt.qerr_loss_weight:
                total += cfg.spt.qerr_loss_weight * aux.get("qerr", 0.0) / nl
            return total, {"lm_loss": lm_loss, **stats,
                           "lb_loss": aux.get("lb_loss", 0.0),
                           "dropped": aux.get("dropped", 0.0)}

        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["train"])
        new_train, new_opt, om = adamw_update(
            state["train"], grads, state["opt"], state["step"], ocfg)
        new_state = {"step": state["step"] + 1, "train": new_train,
                     "frozen": state["frozen"], "opt": new_opt}
        metrics = {"loss": loss, **metrics, **om}
        return new_state, metrics

    return spt_train_step


def build_prefill_step(cfg: ModelConfig, max_len: int) -> Callable:
    return engine.build_prefill_step(cfg, max_len)


def build_decode_step(cfg: ModelConfig) -> Callable:
    return engine.build_decode_step(cfg)


# ------------------------------------------------------------- shardings
def _ns(mesh, spec):
    return NamedSharding(mesh, spec)


def _map_specs(mesh, tree):
    return jax.tree_util.tree_map(
        lambda s: _ns(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, PartitionSpec))


def batch_specs(cfg: ModelConfig, specs: Dict[str, Any], rules) -> dict:
    """PartitionSpec per batch input (train/prefill)."""
    out = {}
    for name, sds in specs.items():
        if name in ("tokens", "labels"):
            out[name] = spec_for(sds.shape, ("batch", None), rules)
        elif name == "frontend_embeds":
            out[name] = spec_for(sds.shape, ("batch", None, None), rules)
        elif name == "token":
            out[name] = spec_for(sds.shape, ("batch",), rules)
        elif name == "pos":
            out[name] = PartitionSpec()
        else:
            raise KeyError(name)
    return out


def cache_specs(cfg: ModelConfig, abstract_caches, rules):
    axes = engine.decode_cache_axes(cfg)
    return jax.tree_util.tree_map(
        lambda sds, ax: spec_for(sds.shape, ax, rules),
        abstract_caches, axes,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def train_shardings(cfg: ModelConfig, mesh, rules, specs):
    st = S.state_specs(cfg, rules)
    bt = batch_specs(cfg, specs, rules)
    scalar = PartitionSpec()
    metric_specs = scalar  # all metrics are scalars -> replicated
    return (_map_specs(mesh, st), _map_specs(mesh, bt),
            _map_specs(mesh, st), _ns(mesh, metric_specs))


def decode_shardings(cfg: ModelConfig, mesh, rules, abstract_caches, specs):
    ps = S.param_specs(cfg, rules)
    cs = cache_specs(cfg, abstract_caches, rules)
    bs = batch_specs(cfg, specs, rules)
    logits = spec_for((1, 1, cfg.padded_vocab), ("batch", None, "vocab"),
                      rules)
    return (_map_specs(mesh, ps), _map_specs(mesh, cs),
            _map_specs(mesh, bs), _ns(mesh, logits))
