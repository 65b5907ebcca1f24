"""Helpers shared by every Pallas kernel in this package: the rounding
arithmetic and the TPU compiler params each ``pallas_call`` passes."""
from __future__ import annotations

from jax.experimental.pallas import tpu as pltpu


def compiler_params(dims):
    """Mosaic compiler params naming each grid dimension's semantics
    ("parallel" | "arbitrary"); interpret mode ignores them."""
    return pltpu.CompilerParams(dimension_semantics=dims)


def round_up(x: int, mult: int) -> int:
    """Smallest multiple of `mult` that is >= x."""
    return -(-x // mult) * mult
