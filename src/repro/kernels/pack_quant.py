"""Fused per-token quantize kernel (activation side of the M4BRAM path).

The paper's activations arrive at the BPE already quantized (the CIM
instruction carries 2–8-bit activations). On TPU the quantization itself is
a bandwidth-bound elementwise pass, so we fuse absmax → scale → round →
clip into one VMEM-resident kernel: each grid step owns `bm` full rows so
the row reduction never leaves VMEM.

Outputs int8 codes (packing to sub-byte words is a layout transform done by
repro.core.bitplane at weight-load time; activations stay int8 because the
MXU consumes int8 lanes directly).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import compiler_params as _compiler_params
from repro.kernels.common import round_up as _round_up


def _quantize_rows_kernel(x_ref, q_ref, s_ref, *, bits: int, signed: bool):
    x = x_ref[...].astype(jnp.float32)
    qhi = (1 << (bits - 1)) - 1 if signed else (1 << bits) - 1
    qlo = -(1 << (bits - 1)) if signed else 0
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = absmax / qhi
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    q = jnp.clip(jnp.round(x * inv), qlo, qhi)
    # int32 hop: float→int8 saturates (255 → 127, corrupting unsigned 8-bit
    # codes) while int32→int8 wraps, storing the code's bit pattern exactly —
    # the bit-plane matmul reconstructs it mod 2^bits.
    q_ref[...] = q.astype(jnp.int32).astype(jnp.int8)
    s_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("bits", "signed", "bm", "interpret"))
def quantize_rows(
    x: jax.Array,
    *,
    bits: int = 8,
    signed: bool = True,
    bm: int = 256,
    interpret: bool = True,
):
    """Per-row symmetric quantization of (M, K) float x.

    Returns (codes int8 (M, K), scales float32 (M, 1)).
    """
    if x.ndim != 2:
        raise ValueError("quantize_rows expects (M, K)")
    m, k = x.shape
    bm_ = min(bm, _round_up(m, 8))
    mp = _round_up(m, bm_)
    xp = jnp.zeros((mp, k), x.dtype).at[:m].set(x)
    kernel = functools.partial(_quantize_rows_kernel, bits=bits, signed=signed)
    q, s = pl.pallas_call(
        kernel,
        grid=(mp // bm_,),
        in_specs=[pl.BlockSpec((bm_, k), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bm_, k), lambda i: (i, 0)),
            pl.BlockSpec((bm_, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, k), jnp.int8),
            jax.ShapeDtypeStruct((mp, 1), jnp.float32),
        ],
        compiler_params=_compiler_params(("parallel",)),
        name="quantize_rows",
        interpret=interpret,
    )(xp)
    return q[:m], s[:m]
