"""Helpers shared by every Pallas kernel in this package: the rounding
arithmetic, the TPU compiler params each ``pallas_call`` passes, and the
paged pool kernels' scale-row unpacking."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu


def compiler_params(dims):
    """Mosaic compiler params naming each grid dimension's semantics
    ("parallel" | "arbitrary"); interpret mode ignores them."""
    return pltpu.CompilerParams(dimension_semantics=dims)


def round_up(x: int, mult: int) -> int:
    """Smallest multiple of `mult` that is >= x."""
    return -(-x // mult) * mult


def slot_scales(row, bs: int):
    """One pool block's head-major scale row (1, bh·bs) -> (bh, bs): lane
    h·bs + t to [h, t] (`kv_cache.scale_rows` layout), in-kernel.

    Mosaic has no lane-to-sublane reshape, so each head's row is masked
    out of a sublane broadcast and its bs lanes rolled down to lanes
    [0, bs): lane t of head h sums the one value h·bs + t and bh - 1
    zeros, which is exact."""
    bh = row.shape[-1] // bs
    a = jnp.broadcast_to(row, (bh, bh * bs))
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    head = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    a = jnp.where(lane // bs == head, a, 0.0)
    out = a
    for k in range(1, bh):
        out = out + pltpu.roll(a, k * bs, 1)
    return out[:, :bs]


def head_tiles(tile, bh: int):
    """A pool tile (rows, bh·H) -> (bh, rows, H), in-kernel: each of the
    bh heads' H lanes as one batch entry. Static lane slices, since
    Mosaic has no lane-splitting reshape (H = 160 is no lane multiple)."""
    h = tile.shape[-1] // bh
    return jnp.stack([tile[:, i * h:(i + 1) * h] for i in range(bh)])
