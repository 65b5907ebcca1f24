"""QuantizedLinear — the paper's technique as a composable JAX module.

Every linear layer in the model zoo routes through :func:`qmatmul`, which
dispatches on the weight leaf type and a :class:`~repro.core.quant.QuantConfig`:

  * ``mode='none'``  — plain bf16/fp32 matmul (the FP32 baseline).
  * ``mode='fake'``  — QAT: STE fake-quant of weights and activations, then a
    dense matmul. Matches the paper's fine-tuning (§V-A).
  * ``mode='serve'`` — the M4BRAM path: weights are *stored packed*
    (2/4/8-bit codes in int8 words, :mod:`repro.core.bitplane`), activations
    are quantized on the fly, and the product is computed by the bit-plane
    matmul kernel (:mod:`repro.kernels`). On TPU this is where the paper's
    throughput-scales-with-precision property becomes
    HBM-bytes-scale-with-precision.

Intra-layer mixed precision (Table III): a ``PackedWeight`` may carry two
filter groups — the first ``n8`` output channels at 8-bit and the rest at
``w_bits`` — mirroring the paper's 4b/8b filter groups computed by the two
heterogeneous engines.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.core import bitplane
from repro.core.quant import QuantConfig, fake_quant, quantize_tensor, quantize_weights_mixed


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedWeight:
    """A packed sub-byte weight matrix + dequant scales.

    packed : int8 storage, shape (K * bits // 8, N) — packed along K.
    scale  : (1, N) per-output-channel dequant scale (float32).
    bits   : 2/4/8 (static aux data).
    n8     : Table III mixing — leading n8 output channels are 8-bit packed
             in `packed8` with scales in `scale` too. 0 disables mixing.
    packed8: optional int8 (K, n8) storage for the 8-bit group.
    a_bits / act_signed : the activation precision this layer was packed
             for — the leaf carries its own per-layer PrecisionPolicy
             decision, so serve-time matmuls need no global QuantConfig.
    plane_lo : contract only planes [plane_lo:] of the packed codes — a
             *view-level* precision drop (w8 storage served as w4/w2 by
             plane truncation, the self-speculative draft path). Aux
             data, not a leaf: truncating a policy never copies weight
             bytes, it only re-traces the matmul.
    """

    packed: jax.Array
    scale: jax.Array
    bits: int
    k: int
    n8: int = 0
    packed8: Optional[jax.Array] = None
    a_bits: int = 8
    act_signed: bool = True
    plane_lo: int = 0

    def tree_flatten(self):
        leaves = (self.packed, self.scale, self.packed8)
        aux = (self.bits, self.k, self.n8, self.a_bits, self.act_signed,
               self.plane_lo)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        packed, scale, packed8 = leaves
        bits, k, n8, a_bits, act_signed, plane_lo = aux
        return cls(packed=packed, scale=scale, bits=bits, k=k, n8=n8,
                   packed8=packed8, a_bits=a_bits, act_signed=act_signed,
                   plane_lo=plane_lo)

    @property
    def shape(self):
        n = self.scale.shape[-1]
        return (self.k, n)

    def hbm_bytes(self) -> int:
        n_low = self.shape[1] - self.n8
        b = self.k * n_low * self.bits // 8 + self.k * self.n8
        return b + self.scale.size * 4


def pack_weight(w: jax.Array, cfg: QuantConfig) -> PackedWeight:
    """Quantize + pack a dense (K, N) weight matrix for serving."""
    if w.ndim != 2:
        raise ValueError(f"pack_weight expects (K, N), got {w.shape}")
    k, n = w.shape
    w32 = w.astype(jnp.float32)
    ab, asg = cfg.a_bits, cfg.act_signed
    if cfg.mixed_ratio_8b > 0.0 and cfg.w_bits != 8:
        q, s, n8 = quantize_weights_mixed(w32, cfg)
        if n8 == n:
            return PackedWeight(q.astype(jnp.int8), s.reshape(1, n), 8, k, 0,
                                None, ab, asg)
        q8, ql = q[:, :n8], q[:, n8:]
        pk = bitplane.pack_weights(ql, cfg.w_bits, axis=0)
        return PackedWeight(pk, s.reshape(1, n), cfg.w_bits, k, n8,
                            q8.astype(jnp.int8), ab, asg)
    q, s = quantize_tensor(w32, cfg.w_bits, True, axis=1 if cfg.per_channel else None)
    pk = bitplane.pack_weights(q, cfg.w_bits, axis=0)
    s = jnp.broadcast_to(jnp.asarray(s, jnp.float32).reshape(1, -1), (1, n))
    return PackedWeight(pk, s, cfg.w_bits, k, 0, None, ab, asg)


def unpack_weight(pw: PackedWeight, *, apply_plane_lo: bool = True) -> jax.Array:
    """Dense int32 codes (K, N) for the reference path / tests.

    A ``plane_lo`` view is applied by arithmetic shift (≡ keep planes
    [lo:], see kernels/bitplane_matmul.py); pass ``apply_plane_lo=False``
    to get the raw resident codes when the downstream kernel performs the
    truncation itself (``w_plane_lo=``).
    """
    ql = bitplane.unpack_weights(pw.packed, pw.bits, axis=0)
    if pw.n8:
        q8 = pw.packed8.astype(jnp.int32)
        ql = jnp.concatenate([q8, ql], axis=1)
    if apply_plane_lo and pw.plane_lo:
        ql = ql >> (2 * pw.plane_lo)
    return ql


def dequantize_weight(pw: PackedWeight, dtype=jnp.float32) -> jax.Array:
    # Truncated codes lose 2·plane_lo low bits, so one code unit is worth
    # 4^plane_lo original LSBs — the scale regains that factor.
    scale = pw.scale * (1 << (2 * pw.plane_lo)) if pw.plane_lo else pw.scale
    return (unpack_weight(pw).astype(jnp.float32) * scale).astype(dtype)


def qmatmul(
    x: jax.Array,
    w: Union[jax.Array, PackedWeight],
    cfg: Optional[QuantConfig] = None,
    mode: str = "none",
    use_kernel: bool = False,
) -> jax.Array:
    """Quantization-aware matmul. x: (..., K); w: (K, N) or PackedWeight."""
    if isinstance(w, PackedWeight):
        return _serve_matmul(x, w, cfg, use_kernel=use_kernel)
    if mode == "none" or cfg is None:
        return x @ w.astype(x.dtype)
    if mode == "fake":
        xq = fake_quant(x, cfg.a_bits, cfg.act_signed)
        wq = fake_quant(w, cfg.w_bits, True, axis=w.ndim - 1 if cfg.per_channel else None)
        return xq @ wq.astype(xq.dtype)
    if mode == "serve":
        return _serve_matmul(x, pack_weight(w, cfg), cfg, use_kernel=use_kernel)
    raise ValueError(f"unknown qmatmul mode {mode!r}")


@jax.named_scope("serve_matmul")
def _serve_matmul(
    x: jax.Array, pw: PackedWeight, cfg: Optional[QuantConfig], use_kernel: bool
) -> jax.Array:
    """Packed-weight matmul, in the ``serve_matmul`` device scope (both
    paths), which the profiler trace attributes its device time by.

    Activation precision comes from `cfg` when given, else from the
    PackedWeight leaf itself — which is how a per-layer PrecisionPolicy
    reaches the kernel without the model threading configs around.

    use_kernel=True — the fused quantize→bit-plane Pallas kernel (exact int
    path; the real TPU implementation, validated in tests; interpret-mode
    on CPU so only used outside distributed graphs). Activations are
    quantized in the matmul's K-loop prologue; no int8 activation tensor
    ever reaches HBM.

    use_kernel=False — the algebraically *identical* dequant formulation
    for jit/pjit graphs: (codes_x · s_x) @ (codes_w · s_w). XLA fuses the
    unpack+scale chain into the matmul on TPU, so HBM sees only packed
    bytes — the kernel contract the §Perf analysis accounts with.
    """
    a_bits = cfg.a_bits if cfg is not None else pw.a_bits
    act_signed = cfg.act_signed if cfg is not None else pw.act_signed
    lead = x.shape[:-1]
    k = x.shape[-1]
    if k != pw.k:
        raise ValueError(f"K mismatch: x has {k}, weight has {pw.k}")
    x2 = x.reshape(-1, k)
    if use_kernel:
        from repro.kernels import ops as kops

        # Hand the kernel the *resident* codes and let it truncate in
        # VMEM (w_plane_lo): HBM only ever sees the one packed buffer,
        # whichever precision tier this call contracts at.
        wq = unpack_weight(pw, apply_plane_lo=False)
        acc, xscale = kops.fused_quantize_matmul(
            x2.astype(jnp.float32), wq, a_bits=a_bits, act_signed=act_signed,
            w_plane_lo=pw.plane_lo,
        )  # per-row (per-token) scale
        ws = pw.scale * (1 << (2 * pw.plane_lo)) if pw.plane_lo else pw.scale
        y = acc.astype(jnp.float32) * xscale * ws
        return y.reshape(*lead, -1).astype(x.dtype)
    # Per-token (row) activation scales, matching the kernel path's K-loop
    # prologue. Per-tensor scaling would make a token's quantized
    # activation depend on every other token in the call — decode batches,
    # prefill chunks, and speculative verify windows would each see
    # different bytes for the same token, breaking the serving stack's
    # batch-composition-independence contract.
    xq = fake_quant(x2, a_bits, act_signed, axis=0)
    w = dequantize_weight(pw, dtype=xq.dtype)
    y = xq @ w
    return y.reshape(*lead, -1).astype(x.dtype)


def _pack_in_column_blocks(w: jax.Array, cfg: QuantConfig) -> PackedWeight:
    """`pack_weight` on about 2M weights at a time, in blocks of whole
    output channels, where the scales are per channel. The
    clipping search holds one fp32 copy of its input per candidate scale
    (32 of them), which for one olmo-1b FFN weight of 16 layers asked a
    16 GB chip for 32 GB. Channels are independent, and run eagerly the
    blocks give bitwise the whole-matrix codes (a compiled search would
    not)."""
    cols = max(1, (1 << 21) // w.shape[0])
    if not cfg.per_channel or cfg.mixed_ratio_8b > 0.0 or w.shape[1] <= cols:
        return pack_weight(w, cfg)
    blocks = [pack_weight(w[:, j:j + cols], cfg)
              for j in range(0, w.shape[1], cols)]
    return jax.tree_util.tree_map(lambda *b: jnp.concatenate(b, axis=-1),
                                  *blocks)


_NO_PACK = ("embed", "head", "patch_proj", "frame_proj", "router", "u",
            "decay_base", "gn_scale", "gn_bias", "conv_w", "lambda_p")


def quantize_params_for_serving(params, cfg, min_size: int = 1 << 16):
    """Walk a parameter pytree and replace 2-D linear weights with
    PackedWeight leaves (the serving transformation).

    `cfg` is a single :class:`QuantConfig` (uniform precision, the paper's
    per-network setting) or a :class:`~repro.core.precision.PrecisionPolicy`
    mapping parameter paths to per-layer configs — each packed leaf records
    the (w_bits, a_bits) its path matched, so a served model runs mixed
    per-layer precision end-to-end.

    Exclusions (kept full-precision, matching the paper's treatment of
    non-GEMM layers): embeddings/heads (consumed by take/transpose paths),
    frontend projections, routers, and all small vectors/norm scales —
    plus anything below `min_size` elements.
    """
    import re

    from repro.core.precision import as_policy
    from repro.parallel.sharding import tree_path_str

    policy = as_policy(cfg)

    def maybe_pack(path, leaf):
        pstr = tree_path_str(path)
        if any(re.search(rf"(^|/){re.escape(n)}$", pstr) for n in _NO_PACK):
            return leaf
        if (
            not isinstance(leaf, jax.Array)
            or not jnp.issubdtype(leaf.dtype, jnp.floating)
            or leaf.size < min_size
        ):
            return leaf
        leaf_cfg = policy.for_path(pstr)
        if leaf.ndim == 2 and leaf.shape[0] % 16 == 0 and min(leaf.shape) >= 128:
            # min-dim guard: stacked norm scales (L, d) are 2-D but not GEMMs.
            return pack_weight(leaf, leaf_cfg)
        if leaf.ndim == 3 and leaf.shape[1] % 16 == 0 and leaf.shape[2] >= 16:
            # Stacked scan-over-layers weights (L, K, N): pack per layer.
            layers = [_pack_in_column_blocks(w, leaf_cfg) for w in leaf]
            return jax.tree_util.tree_map(lambda *ws: jnp.stack(ws), *layers)
        return leaf

    return jax.tree_util.tree_map_with_path(maybe_pack, params)


def packed_weight_bytes(params, w_bits: Optional[int] = None) -> int:
    """Total packed GEMM weight bytes resident in `params`; with `w_bits`,
    the bytes a plane-truncated view served at that width actually
    streams per forward pass (top planes only — a w8 leaf read at w4
    streams half its bytes, at w2 a quarter; leaves already at or below
    `w_bits` stream whole). The modeled-traffic denominator for both the
    speculative-decoding and precision-tier benchmarks."""
    from repro.core.precision import PLANE_BITS, plane_offset

    total = 0
    for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=lambda l: isinstance(l, PackedWeight)):
        if not isinstance(leaf, PackedWeight):
            continue
        nbytes = int(leaf.packed.nbytes)
        if leaf.packed8 is not None:
            nbytes += int(leaf.packed8.nbytes)
        if w_bits is not None:
            lo = plane_offset(leaf.bits, w_bits)
            nbytes = nbytes * (leaf.bits - PLANE_BITS * lo) // leaf.bits
        total += nbytes
    return total
