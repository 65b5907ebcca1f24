"""Bit-plane mixed-precision matmul — the M4BRAM BPE dataflow on the MXU.

The paper's BPE consumes activation bits serially and LUT-selects partial
sums ``{0, W1, W2, W1+W2}``; algebraically each cycle adds
``(I1[n]·W1 + I2[n]·W2) << n``. Vectorized over a whole tile that is::

    acc = sum_p (plane_p @ W) << (p · plane_bits)  -  2^(a_bits-1) · colsum(W)

with 2-bit planes (the TPU-efficient choice: ceil(a_bits/2) MXU passes, each
an int8×int8→int32 matmul) and the offset term playing the INV-row's role
for signed activations (see repro/core/bitplane.py).

TPU mapping decisions (hw-codesign):
  * Grid (M/bm, N/bn, K/bk) with ("parallel", "parallel", "arbitrary")
    dimension semantics — K innermost so the int32 accumulator tile stays
    resident in VMEM across K steps (revisited output block).
  * Block shapes default to (bm, bn, bk) = (128, 128, 256): MXU-aligned
    (multiples of 128 on M/N for the 128×128 systolic array; 256 on K keeps
    the x/w tiles at 32 KiB / 64 KiB int8 — well inside VMEM with Pallas'
    automatic double-buffering of BlockSpec tiles, the analogue of the
    paper's double-buffered load/compute/store pipeline).
  * The plane decomposition runs on registers in VMEM (shift+mask on the
    already-loaded int8 tile) — the duplication-shuffler analogue: HBM only
    ever sees packed data; unpacking is free bandwidth multiplication.
  * The number of planes is static (specialized per a_bits) so the P-loop
    fully unrolls into `P` MXU contractions — latency scales with ceil(a/2)
    exactly as the paper's (n/2+2)-cycle double-pumped BPE.

Validated in interpret mode on CPU against repro/kernels/ref.py (exact
integer equality) across shapes, precisions and signedness.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import compiler_params as _compiler_params
from repro.kernels.common import round_up as _round_up


def _bitplane_matmul_kernel(
    x_ref,  # (bm, bk) int8 activation codes
    w_ref,  # (bk, bn) int8 weight codes
    o_ref,  # (bm, bn) int32 accumulator (revisited across K grid steps)
    *,
    a_bits: int,
    act_signed: bool,
    plane_bits: int,
    w_plane_lo: int,
):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.int32)
    w = w_ref[...].astype(jnp.int32)

    if w_plane_lo:
        # Plane-truncated contraction: use only the top planes of the
        # (conceptually little-endian plane-decomposed) weight codes.  A
        # signed code w stores as offset-binary u = w + 2^(b-1), whose
        # plane p holds bits [p·pb, (p+1)·pb).  Dropping planes [0, lo)
        # and re-weighting plane p at 2^((p-lo)·pb) is exactly
        # floor(u / 4^lo) - 2^(b-1)/4^lo; since the sign offset 2^(b-1)
        # divides by 4^lo whenever 2·lo < b (pb = 2), that equals the
        # arithmetic shift w >> (lo·pb) — the sign plane stays the top
        # plane and the truncated code is itself a valid signed
        # (b - lo·pb)-bit code.  Crucially the shift happens BEFORE the
        # activation-offset colsum correction below: the correction term
        # offset·colsum(W) must be computed over the *truncated* weight,
        # otherwise the dropped low planes of W would leak back in
        # through the correction.
        w = w >> (w_plane_lo * plane_bits)

    offset = (1 << (a_bits - 1)) if act_signed else 0
    u = x + offset  # offset-binary: planes are unsigned
    n_planes = -(-a_bits // plane_bits)
    mask = (1 << plane_bits) - 1

    acc = jnp.zeros(o_ref.shape, jnp.int32)
    for p in range(n_planes):  # static unroll: one MXU pass per plane
        plane = ((u >> (p * plane_bits)) & mask).astype(jnp.int8)
        part = jax.lax.dot_general(
            plane,
            w.astype(jnp.int8),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        acc = acc + (part << (p * plane_bits))

    if offset:
        # INV-row analogue: subtract offset * colsum(W) for this K block.
        colsum = jnp.sum(w, axis=0, keepdims=True)
        acc = acc - offset * colsum

    o_ref[...] += acc


@functools.partial(
    jax.jit,
    static_argnames=("a_bits", "act_signed", "plane_bits", "w_plane_lo",
                     "bm", "bn", "bk", "interpret"),
)
def bitplane_matmul(
    x_codes: jax.Array,
    w_codes: jax.Array,
    *,
    a_bits: int = 8,
    act_signed: bool = True,
    plane_bits: int = 2,
    w_plane_lo: int = 0,
    bm: int = 128,
    bn: int = 128,
    bk: int = 256,
    interpret: bool = True,
) -> jax.Array:
    """(M, K) int codes × (K, N) int codes → (M, N) int32 exact product.

    Shapes need not be block-aligned; inputs are zero-padded (zero codes
    contribute nothing — including to the offset correction, since colsum
    of a zero column block is zero; likewise a zero code is shift-invariant
    so padding is safe under ``w_plane_lo`` truncation).

    ``w_plane_lo`` contracts only the top planes of the weight codes:
    plane ``lo`` becomes the new least-significant plane, realized as an
    arithmetic shift of the signed codes (see the kernel for why that is
    exactly "keep planes [lo:]"). The caller re-scales the dequantized
    output by ``(1 << (plane_bits * w_plane_lo))`` to keep the weight
    scale meaning "value of one unit of the *original* LSB".
    """
    if x_codes.ndim != 2 or w_codes.ndim != 2:
        raise ValueError("bitplane_matmul expects 2-D operands")
    m, k = x_codes.shape
    k2, n = w_codes.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {k} vs {k2}")

    # Clamp blocks to the padded problem without dropping the alignment the
    # caller's plan carries: a 128-multiple block (MXU lane contract, mosaic
    # plans) stays a 128-multiple; finer interpret-mode plans clamp to 8.
    bm_ = min(bm, _round_up(m, 8))
    bn_ = min(bn, _round_up(n, 128 if bn % 128 == 0 else 8))
    bk_ = min(bk, _round_up(k, 128 if bk % 128 == 0 else 8))
    mp, np_, kp = _round_up(m, bm_), _round_up(n, bn_), _round_up(k, bk_)

    x = jnp.zeros((mp, kp), jnp.int8).at[:m, :k].set(x_codes.astype(jnp.int8))
    w = jnp.zeros((kp, np_), jnp.int8).at[:k, :n].set(w_codes.astype(jnp.int8))

    grid = (mp // bm_, np_ // bn_, kp // bk_)
    kernel = functools.partial(
        _bitplane_matmul_kernel,
        a_bits=a_bits,
        act_signed=act_signed,
        plane_bits=plane_bits,
        w_plane_lo=w_plane_lo,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        compiler_params=_compiler_params(("parallel", "parallel", "arbitrary")),
        name="bitplane_matmul",
        interpret=interpret,
    )(x, w)
    return out[:m, :n]
