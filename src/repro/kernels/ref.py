"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the *semantic specification* its kernel is tested against
(tests/test_kernels_*.py sweep shapes/dtypes/precisions with
assert_allclose / exact integer equality).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import bitplane


def bitplane_matmul_ref(
    x_codes: jax.Array,
    w_codes: jax.Array,
    a_bits: int,
    act_signed: bool = True,
    w_plane_lo: int = 0,
    plane_bits: int = 2,
) -> jax.Array:
    """(M, K) int codes × (K, N) int codes → (M, N) int32, exact.

    Unsigned codes may arrive as wrapped int8 storage (255 → -1); mask to
    the a_bits range so the semantics match the kernels' offset-binary
    reconstruction mod 2^a_bits.

    ``w_plane_lo`` truncates the weight to its top planes before the
    contraction: the arithmetic shift is exactly "keep planes [lo:]" of
    the little-endian offset-binary decomposition, because the sign
    offset 2^(b-1) is divisible by 4^lo whenever 2·lo < b (see
    bitplane_matmul's kernel for the derivation).
    """
    x = x_codes.astype(jnp.int32)
    if not act_signed:
        x = x & ((1 << a_bits) - 1)
    w = w_codes.astype(jnp.int32)
    if w_plane_lo:
        w = w >> (w_plane_lo * plane_bits)
    return (x @ w).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bits", "signed"))
def quantize_pack_ref(
    x: jax.Array, bits: int, signed: bool = True
) -> tuple[jax.Array, jax.Array]:
    """Per-row absmax symmetric quantization of (M, K) float x to `bits`-bit
    codes, returned as int8 codes (unpacked; packing is layout-only) and
    per-row scales (M, 1)."""
    qhi = (1 << (bits - 1)) - 1 if signed else (1 << bits) - 1
    qlo = -(1 << (bits - 1)) if signed else 0
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = absmax / qhi
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    # int32 hop: float→int8 saturates but int32→int8 wraps, preserving the
    # bit pattern of unsigned 8-bit codes (see pack_quant).
    q = jnp.clip(jnp.round(x * inv), qlo, qhi).astype(jnp.int32).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def mixed_group_matmul_ref(
    x: jax.Array,
    w8_codes: jax.Array,
    wl_codes: jax.Array,
    scale8: jax.Array,
    scalel: jax.Array,
    a_bits: int,
) -> jax.Array:
    """Intra-layer mixed matmul (Table III): x (M, K) float; the first group
    is 8-bit codes (K, N8), the second `w_bits`-bit codes (K, NL); output is
    the float concatenation [x@deq(w8), x@deq(wl)] with activations quantized
    per-row at a_bits."""
    q, s = quantize_pack_ref(x.astype(jnp.float32), a_bits)
    acc8 = q.astype(jnp.int32) @ w8_codes.astype(jnp.int32)
    accl = q.astype(jnp.int32) @ wl_codes.astype(jnp.int32)
    y8 = acc8.astype(jnp.float32) * s * scale8.reshape(1, -1)
    yl = accl.astype(jnp.float32) * s * scalel.reshape(1, -1)
    return jnp.concatenate([y8, yl], axis=1)


def wkv6_ref(
    r: jax.Array,  # (T, H, K)   receptance
    k: jax.Array,  # (T, H, K)   key
    v: jax.Array,  # (T, H, V)   value
    w: jax.Array,  # (T, H, K)   data-dependent decay, in (0, 1)
    u: jax.Array,  # (H, K)      bonus for the current token
) -> jax.Array:
    """RWKV-6 (Finch) recurrence, sequential reference.

    State S_h ∈ R^{K×V};   out_t = r_t · (S + u ⊙ k_t v_tᵀ);
                           S ← diag(w_t) S + k_t v_tᵀ.
    Returns (T, H, V) float32.
    """
    T, H, K = r.shape
    V = v.shape[-1]

    def step(S, inp):
        r_t, k_t, v_t, w_t = inp
        kv = k_t[..., :, None] * v_t[..., None, :]          # (H, K, V)
        out = jnp.einsum("hk,hkv->hv", r_t, S + u[..., :, None] * kv)
        S = w_t[..., :, None] * S + kv
        return S, out

    S0 = jnp.zeros((H, K, V), jnp.float32)
    _, outs = jax.lax.scan(
        step, S0, (r.astype(jnp.float32), k.astype(jnp.float32),
                   v.astype(jnp.float32), w.astype(jnp.float32))
    )
    return outs


def paged_attention_ref(
    q: jax.Array,            # (B, 1, NQ, H)
    pool_k: jax.Array,       # (L, num_blocks, block_size, NKV*H)
    pool_v: jax.Array,
    block_table: jax.Array,  # (B, max_blocks) int32, -1 = unallocated
    q_pos: jax.Array,        # (B,) per-row decode position
    layer: jax.Array,        # () int32 — the pool layer attended
    k_scale: jax.Array | None = None,  # (L, num_blocks, 1, NKV*block_size)
    v_scale: jax.Array | None = None,
    softcap: float = 0.0,
) -> jax.Array:
    """Gather-then-attend oracle for the fused paged-attention kernel.

    Slices layer `layer` out of the stacked pool, materializes each
    row's blocks in table order (the contiguous slot == position layout)
    and runs the same one-token masked-softmax math as
    ``models.common.decode_attention`` — including the int8-pool per-slot
    rescaling. This IS the "separate buffer" the fused kernel eliminates;
    it survives as the semantic specification."""
    from repro.models.kv_cache import scale_slots, split_heads

    B, _, NQ, H = q.shape
    bs, NKV = pool_k.shape[2], pool_k.shape[3] // H
    G = NQ // NKV
    max_blocks = block_table.shape[1]
    pool_k = split_heads(pool_k[layer], NKV)
    pool_v = split_heads(pool_v[layer], NKV)
    if k_scale is not None:
        k_scale = scale_slots(k_scale[layer], bs)
        v_scale = scale_slots(v_scale[layer], bs)
    tbl = jnp.maximum(block_table, 0)
    k_rows = pool_k[tbl].reshape(B, max_blocks * bs, NKV, H)
    v_rows = pool_v[tbl].reshape(B, max_blocks * bs, NKV, H)
    virt = jnp.arange(max_blocks * bs, dtype=jnp.int32)
    alloc = jnp.repeat(block_table >= 0, bs, axis=1)
    kpos = jnp.where(alloc, virt[None, :], -1)

    qr = q.reshape(B, NKV, G, H)
    s = jnp.einsum("bngh,bsnh->bngs", qr.astype(jnp.float32),
                   k_rows.astype(jnp.float32))
    if k_scale is not None:
        ks = k_scale[tbl].reshape(B, max_blocks * bs, NKV)
        s = s * jnp.moveaxis(ks, -1, 1)[:, :, None, :]
    s = s * (H**-0.5)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = jnp.broadcast_to(jnp.asarray(q_pos, jnp.int32), (B,))
    valid = (kpos >= 0) & (kpos <= q_pos[:, None])
    s = jnp.where(valid[:, None, None, :], s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1)
    if v_scale is not None:
        vs = v_scale[tbl].reshape(B, max_blocks * bs, NKV)
        p = p * jnp.moveaxis(vs, -1, 1)[:, :, None, :]
    out = jnp.einsum("bngs,bsnh->bngh", p, v_rows.astype(jnp.float32))
    return out.reshape(B, 1, NQ, H).astype(q.dtype)


def paged_prefill_ref(
    q: jax.Array,            # (1, Lc, NQ, H)
    k_new: jax.Array,        # (1, Lc, NKV, H) — chunk K/V, unquantized
    v_new: jax.Array,
    pool_k: jax.Array,       # (L, num_blocks, block_size, NKV*H)
    pool_v: jax.Array,
    blocks: jax.Array,       # (mb,) int32 row block table, -1 = unallocated
    start: jax.Array,        # () int32 chunk token 0's absolute position
    length: jax.Array,       # () int32 real chunk length (<= Lc)
    layer: jax.Array,        # () int32 — the pool layer attended and written
    k_scale: jax.Array | None = None,  # (L, num_blocks, 1, NKV*block_size)
    v_scale: jax.Array | None = None,
    softcap: float = 0.0,
):
    """Scatter-then-gather-attend oracle for the chunked-prefill kernel.

    Writes the chunk into layer `layer` of the stacked pool with
    `paged_chunk_write` (the exact quantize-on-write math the kernel's
    wrapper applies), then gathers the row's blocks of that layer in
    table order and runs a full fp32 masked softmax — chunk query i sees
    allocated positions <= start + i, padded queries (i >= length) see
    nothing and output zeros. Attending *through the pool* is the point:
    an int8 pool's chunk keys come back as dequantize(quantize(k)), the
    `_kv_attn_view` contract. Returns the stacked planes."""
    from repro.models import kv_cache as _kvc

    _, Lc, NQ, H = q.shape
    bs, NKV = pool_k.shape[2], pool_k.shape[3] // H
    G = NQ // NKV
    mb = blocks.shape[0]
    pool_k, pool_v, k_scale, v_scale = _kvc.paged_chunk_write(
        pool_k, pool_v, blocks, k_new, v_new, start, length, bs,
        k_scale, v_scale, layer=layer)
    tbl = jnp.maximum(blocks, 0)
    k_rows = pool_k[layer, tbl].reshape(mb * bs, NKV, H)
    v_rows = pool_v[layer, tbl].reshape(mb * bs, NKV, H)
    virt = jnp.arange(mb * bs, dtype=jnp.int32)
    alloc = jnp.repeat(blocks >= 0, bs)
    kpos = jnp.where(alloc, virt, -1)

    qr = q.reshape(Lc, NKV, G, H)
    s = jnp.einsum("qngh,snh->nqgs", qr.astype(jnp.float32),
                   k_rows.astype(jnp.float32))
    if k_scale is not None:
        ks = _kvc.scale_slots(k_scale[layer, tbl], bs).reshape(mb * bs, NKV)
        s = s * ks.T[:, None, None, :]
    s = s * (H**-0.5)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    qi = jnp.arange(Lc, dtype=jnp.int32)
    qpos = jnp.where(qi < length, jnp.asarray(start, jnp.int32) + qi, -1)
    valid = (kpos[None, :] >= 0) & (kpos[None, :] <= qpos[:, None])
    s = jnp.where(valid[None, :, None, :], s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid[None, :, None, :], p, 0.0)
    if v_scale is not None:
        vs = _kvc.scale_slots(v_scale[layer, tbl], bs).reshape(mb * bs, NKV)
        p = p * vs.T[:, None, None, :]
    out = jnp.einsum("nqgs,snh->qngh", p, v_rows.astype(jnp.float32))
    attn = out.reshape(1, Lc, NQ, H).astype(q.dtype)
    return attn, pool_k, pool_v, k_scale, v_scale


def flash_attention_ref(
    q: jax.Array,  # (BH, Tq, D)
    k: jax.Array,  # (BH, Tk, D)
    v: jax.Array,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
) -> jax.Array:
    """Naive fp32 softmax attention with causal/window masks."""
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (D**-0.5)
    qpos = (q_offset + jnp.arange(Tq))[:, None]
    kpos = jnp.arange(Tk)[None, :]
    mask = jnp.ones((Tq, Tk), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask[None], p, 0.0)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))
