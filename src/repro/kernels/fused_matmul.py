"""Fused activation-quantize → bit-plane matmul (single Pallas kernel).

The paper's central claim is that M4BRAM computes mixed-precision matmuls
*in place*: activations arrive at the BPE already quantized and no separate
quantized-activation buffer ever materializes (§IV). The unfused TPU path
violated that — ``pack_quant.quantize_rows`` wrote int8 codes back to HBM
and ``bitplane_matmul`` re-read them, an extra M×K round trip per serve-mode
matmul. This kernel fuses absmax → scale → round → plane-decompose → MXU
contraction so the fp32 activation tile is quantized in the K-loop prologue
while already resident in VMEM, and HBM only ever sees fp32 activations in
and int32 accumulators out.

Dataflow (hw-codesign notes):
  * Grid (M/bm, N/bn, K/bk), K innermost ("arbitrary") so the int32
    accumulator tile revisits VMEM across K steps, as in bitplane_matmul.
  * The activation block is (bm, K) — *full rows* resident in VMEM, because
    the per-token absmax reduction needs the whole row. bm shrinks as K
    grows (see registry.pick_fused_blocks) instead of tiling K on the
    activation side; only the weight operand tiles along K.
  * Quantization is recomputed per K step from the resident rows (VPU work,
    cheap next to the MXU contraction) rather than staged through scratch,
    keeping the kernel free of cross-step carried state beyond the
    revisited output block.
  * Per-row scales are emitted as a second output so callers dequantize
    exactly as the unfused path did.

Exactness contract (tested): for any (a_bits, signed) the int32 accumulator
and fp32 scales are bit-identical to the unfused composition
``quantize_rows(x) → bitplane_matmul(codes, w)``. Quantization uses the very
same elementwise formula, the row max is order-independent, and the integer
accumulation is exact, so block-plan differences cannot change results.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import compiler_params, round_up


def _fused_kernel(
    x_ref,  # (bm, Kp) fp32 activation rows, fully resident
    w_ref,  # (bk, bn) int8 weight codes
    o_ref,  # (bm, bn) int32 accumulator (revisited across K grid steps)
    s_ref,  # (bm, 1) fp32 per-row scales
    *,
    a_bits: int,
    act_signed: bool,
    plane_bits: int,
    w_plane_lo: int,
    bk: int,
):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)

    # --- quantize prologue (same arithmetic as pack_quant, bit-exact) ---
    qhi = (1 << (a_bits - 1)) - 1 if act_signed else (1 << a_bits) - 1
    qlo = -(1 << (a_bits - 1)) if act_signed else 0
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = absmax / qhi
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    s_ref[...] = scale

    xs = x_ref[:, pl.ds(pl.multiple_of(kk * bk, bk), bk)].astype(jnp.float32)
    q = jnp.clip(jnp.round(xs * inv), qlo, qhi).astype(jnp.int32)

    # --- plane decompose + contract (same algebra as bitplane_matmul) ---
    offset = (1 << (a_bits - 1)) if act_signed else 0
    u = q + offset  # offset-binary: planes are unsigned
    n_planes = -(-a_bits // plane_bits)
    mask = (1 << plane_bits) - 1
    w = w_ref[...].astype(jnp.int32)
    if w_plane_lo:
        # Top-planes-only weight view: arithmetic shift ≡ drop planes
        # [0, lo) of the offset-binary decomposition (the sign offset
        # 2^(b-1) divides by 4^lo for 2·lo < b), so the sign plane stays
        # the top plane. Must happen before the colsum correction — the
        # offset term has to see the truncated weight, not the full one.
        # See _bitplane_matmul_kernel for the full derivation.
        w = w >> (w_plane_lo * plane_bits)

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
def fused_quantize_matmul(
    x: jax.Array,
    w_codes: jax.Array,
    *,
    a_bits: int = 8,
    act_signed: bool = True,
    plane_bits: int = 2,
    w_plane_lo: int = 0,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool = True,
):
    """(M, K) float × (K, N) int weight codes → ((M, N) int32, (M, 1) fp32).

    Returns the exact integer accumulator of quantized-activation codes
    against `w_codes`, plus the per-row activation scales; the caller
    dequantizes as ``acc * scales * w_scale``. Shapes need not be
    block-aligned (zero padding contributes nothing — including to the row
    absmax and to the signed-offset correction). ``w_plane_lo`` contracts
    only the top weight planes (see bitplane_matmul); the caller folds the
    ``1 << (plane_bits * w_plane_lo)`` factor into the weight scale.
    """
    if x.ndim != 2 or w_codes.ndim != 2:
        raise ValueError("fused_quantize_matmul expects 2-D operands")
    m, k = x.shape
    k2, n = w_codes.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {k} vs {k2}")

    # As in bitplane_matmul: clamping to the padded problem must preserve
    # the block plan's own alignment (128 lanes for mosaic plans).
    bm_ = min(bm, round_up(m, 8))
    bn_ = min(bn, round_up(n, 128 if bn % 128 == 0 else 8))
    bk_ = min(bk, round_up(k, 128 if bk % 128 == 0 else 8))
    mp, np_, kp = round_up(m, bm_), round_up(n, bn_), round_up(k, bk_)

    xp = jnp.zeros((mp, kp), jnp.float32).at[:m, :k].set(x.astype(jnp.float32))
    wp = jnp.zeros((kp, np_), jnp.int8).at[:k, :n].set(w_codes.astype(jnp.int8))

    grid = (mp // bm_, np_ // bn_, kp // bk_)
    kernel = functools.partial(
        _fused_kernel,
        a_bits=a_bits,
        act_signed=act_signed,
        plane_bits=plane_bits,
        w_plane_lo=w_plane_lo,
        bk=bk_,
    )
    acc, scales = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, kp), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((bk_, bn_), lambda i, j, kk: (kk, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm_, bn_), lambda i, j, kk: (i, j)),
            pl.BlockSpec((bm_, 1), lambda i, j, kk: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, np_), jnp.int32),
            jax.ShapeDtypeStruct((mp, 1), jnp.float32),
        ],
        compiler_params=compiler_params(("parallel", "parallel", "arbitrary")),
        name="fused_quantize_matmul",
        interpret=interpret,
    )(xp, wp)
    return acc[:m, :n], scales[:m]
