"""Mesh construction: the one place that calls ``jax.make_mesh``.

FUNCTIONS, not module constants — importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """Mesh with every axis in ``Auto`` mode: the partition rules in
    ``parallel/sharding.py`` shard parameters and constrain a few
    activations, and leave the rest to the compiler. ``jax.make_mesh``
    defaults to ``Explicit`` axes, under which an op such as the
    embedding ``take`` on a vocab-sharded table raises instead."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; multi_pod prepends a 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def batch_axes_of(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
