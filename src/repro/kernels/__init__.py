"""Pallas TPU kernels for the SPT hot spots (DESIGN.md §6).

Each kernel directory ships:
  <name>.py — pl.pallas_call body with explicit BlockSpec VMEM tiling
  ops.py    — jit'd public wrapper (custom_vjp: fused forward, ref backward)
  ref.py    — pure-jnp oracle (reuses the validated core/ implementations)

Validated on CPU with interpret=True; tests/test_tpu_compile.py compiles
each one for a described TPU v5e, and chip_smoke.py drives them on a chip.

Every public wrapper takes ``interpret: Optional[bool] = None`` and routes
it through ``resolve_interpret`` at the innermost pallas_call site: None
means "derive from the backend" (interpret everywhere except real TPU),
so callers never hard-code a platform assumption.  analysis/lint.py
enforces the ``None`` default repo-wide.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """None -> interpret off TPU, compiled on TPU; explicit bool wins."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
