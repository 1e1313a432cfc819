"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the leading ``pod``
axis is pure data parallelism (gradient all-reduce crosses the slow
inter-pod links once per step) — DESIGN.md §4.

Defined as functions (never module-level constants) so importing this
module cannot touch jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes, devices=None):
    """Arbitrary mesh (e.g. (1, 1) on one CPU device).  Axes are ``Auto``:
    the model code places activations with sharding constraints and lets
    the partitioner resolve the rest (JAX's default is ``Explicit``)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def mesh_num_devices(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
