"""What every plain float32 reference shares, whatever its model: how
matrix products are computed (``Dots``, with the float8 control), AdamW,
and the fine-tuning steps that follow a model's loss.

It imports nothing of the program under test. A model's reference (for
example ``spt.py``) supplies its loss, ``train_loss(train, frozen, tokens,
labels, m, dots)``; ``run_train_steps`` drives any such loss.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------ precision
def _round_fp8(x: jax.Array, dtype=jnp.float8_e4m3fn) -> jax.Array:
    """Per-tensor scaled float8 rounding, back in float32."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, float(jnp.finfo(dtype).max) / amax, 1.0)
    return (x * scale).astype(dtype).astype(F32) / scale


@jax.custom_vjp
def _fp8_grad(y):
    return y


def _fp8_grad_fwd(y):
    return y, None


def _fp8_grad_bwd(_, g):
    return (_round_fp8(g, jnp.float8_e5m2),)


_fp8_grad.defvjp(_fp8_grad_fwd, _fp8_grad_bwd)


def _fp8_value(x):
    """Forward value rounded to fp8; the gradient passes straight through."""
    return x + jax.lax.stop_gradient(_round_fp8(x) - x)


@dataclasses.dataclass(frozen=True)
class Dots:
    """How matrix products are computed: "f32" (the reference) or "fp8"
    (the control, as float8 training computes: both operands rounded to
    per-tensor scaled e4m3, the incoming gradient of the product to e5m2;
    float32 accumulation)."""
    mode: str = "f32"

    def __call__(self, eq: str, a: jax.Array, b: jax.Array) -> jax.Array:
        a, b = a.astype(F32), b.astype(F32)
        if self.mode == "f32":
            return jnp.einsum(eq, a, b, precision=HIGHEST)
        if self.mode == "fp8":
            y = jnp.einsum(eq, _fp8_value(a), _fp8_value(b),
                           precision=HIGHEST)
            return _fp8_grad(y)
        raise ValueError(self.mode)


# ------------------------------------------------------------ training
@dataclasses.dataclass(frozen=True)
class AdamW:
    lr: float
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.01
    grad_clip: float = 1.0


def combine(a, b):
    """Merge two trees with disjoint leaves (trainable + frozen)."""
    if a is None or b is None:
        return b if a is None else a
    return {k: combine(a.get(k), b.get(k)) for k in set(a) | set(b)}


def train_step(train, frozen, opt, step, tokens, labels, loss: Callable,
               adam: AdamW):
    """One fine-tuning step of ``loss(train, frozen, tokens, labels)``:
    returns (train', opt', loss, clipped grads)."""
    value, g = jax.value_and_grad(loss)(train, frozen, tokens, labels)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x)
                         for x in jax.tree_util.tree_leaves(g)))
    clip = jnp.minimum(1.0, adam.grad_clip / (gnorm + 1e-12))
    g = jax.tree_util.tree_map(lambda x: x * clip, g)
    t = step + 1.0
    mo = jax.tree_util.tree_map(lambda a, x: adam.b1 * a + (1 - adam.b1) * x,
                                opt["m"], g)
    vo = jax.tree_util.tree_map(
        lambda a, x: adam.b2 * a + (1 - adam.b2) * x * x, opt["v"], g)

    def upd(p, a, b):
        step_ = (a / (1 - adam.b1 ** t)) / (
            jnp.sqrt(b / (1 - adam.b2 ** t)) + adam.eps)
        return p - adam.lr * (step_ + adam.weight_decay * p)

    new = jax.tree_util.tree_map(upd, train, mo, vo)
    return new, {"m": mo, "v": vo}, value, g


def run_train_steps(train, frozen, batches: Sequence, loss: Callable,
                    adam: AdamW):
    """Follow len(batches) steps of ``loss`` from (train, zero moments).
    Returns (losses, clipped grads of step 1, train params after the last
    step)."""
    step = jax.jit(lambda tr, fr, op, st, tok, lab: train_step(
        tr, fr, op, st, tok, lab, loss, adam))
    opt = {"m": jax.tree_util.tree_map(jnp.zeros_like, train),
           "v": jax.tree_util.tree_map(jnp.zeros_like, train)}
    losses, first = [], None
    for i, (tok, lab) in enumerate(batches):
        train, opt, value, g = step(train, frozen, opt, jnp.float32(i), tok,
                                    lab)
        losses.append(float(value))
        if first is None:
            first = jax.device_get(g)
    return losses, first, jax.device_get(train)
