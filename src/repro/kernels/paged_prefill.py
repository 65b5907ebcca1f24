"""Fused paged chunked-prefill flash Pallas kernel.

A token *chunk* of one request's prompt attends causally against
``[pool-resident prefix ++ the chunk itself]`` and the chunk's K/V is
written into its destination pool blocks from the same kernel — the PR 4
scalar-prefetch/block-table trick applied to the prefill grid. This kills
both halves of the old admission round trip: no post-prefill
``scatter_into_paged`` (the chunk lands in the pool as a side effect of
attending), and no per-layer HBM gather of the resident prefix
(``prefill_suffix`` materialized a contiguous ``(1, P, NKV, H)`` copy of
the prefix every layer; here prefix blocks stream through the block-table
index map exactly like the decode kernel's).

Mechanics:
  * The row's block table and ``(start, length)`` arrive via **scalar
    prefetch** (``pltpu.PrefetchScalarGridSpec``) so the pool BlockSpec
    index maps can resolve virtual block ``j`` to pool block ``table[j]``
    before the grid step runs — one DMA streams exactly that block.
  * Grid is ``(NKV/bh, max_blocks)`` with the block dimension innermost;
    the online-softmax running max / denominator / accumulator for all
    ``Lc`` chunk queries live in VMEM scratch across a row's blocks.
  * Dead steps (unallocated table entries, blocks past the chunk's last
    position) are remapped to pool block 0 — the reserved trash block —
    so no new DMA is issued for them, and ``pl.when`` skips their compute.
  * Steps whose virtual block overlaps ``[start, start + length)`` are
    *destination* steps: the kernel merges the chunk's K/V rows into the
    streamed pool tile (resident slots below ``start`` keep their pool
    values) and writes the merged tile back to the pool through an
    input/output-aliased pool ref — the epilogue write. Non-destination
    steps remap the output to the trash block, so resident prefix blocks
    (possibly shared with other rows) are never rewritten.
  * int8 pools quantize on write in-kernel (``kv_cache.quantize_kv``'s
    exact per-(token, head) math, so pool bytes are bit-identical to the
    scatter path's) and dequantize in-kernel on read: scores are computed
    on int8 codes and rescaled per key slot, probabilities per value slot
    — ``decode_attention``'s quantized math, like the decode kernel.

Masking: key slot at absolute position ``kpos`` is visible to chunk query
``i`` iff its block is allocated and ``kpos <= start + i`` (causal);
padded queries (``i >= length``) see nothing and output zeros. Positions
in ``[start + length, ...)`` of a destination block are masked on read
and preserved on write, so a later chunk appending into the same partial
block finds earlier residents intact.

The write-then-read ordering within a destination step (the merged tile
is both the attention operand and the written output) is what makes the
chunk attend to itself through the *pool's* representation: for an int8
pool a chunk key is read back as ``dequantize(quantize(k))`` — exactly
the `_kv_attn_view` contract the cold prefill applies to its own K/V.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import compiler_params as _compiler_params


def _quantize_tile(x):
    """In-kernel `kv_cache.quantize_kv`: per-(slot, head) int8 symmetric
    codes + fp32 scales for a (bs, bh, H) tile. Must stay bit-identical
    to the jnp helper — pool bytes written here are shared with readers
    that assume the scatter path's exact quantization."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = absmax / 127.0
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    codes = jnp.clip(jnp.round(xf * inv), -128, 127).astype(jnp.int8)
    return codes, scale


def _chunk_kernel(tbl_ref, meta_ref, q_ref, kn_ref, vn_ref, k_ref, v_ref,
                  *rest, bs: int, n_blk: int, lc: int, g: int, scale: float,
                  softcap: float, quantized: bool):
    if quantized:
        (ks_ref, vs_ref, o_ref, pk_out, pv_out, ks_out, vs_out,
         m_ref, l_ref, acc_ref) = rest
    else:
        o_ref, pk_out, pv_out, m_ref, l_ref, acc_ref = rest
    j = pl.program_id(1)
    start = meta_ref[0]
    length = meta_ref[1]
    last = start + length - 1

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = jnp.logical_and(
        jnp.logical_and(tbl_ref[j] >= 0, j * bs <= last), length > 0
    )

    @pl.when(live)
    def _step():
        # Chunk rows [j*bs - start, +bs) as one contiguous window of the
        # chunk K/V, which the wrapper pads by bs rows on each side so the
        # window never leaves the buffer. Rows outside the chunk are
        # replaced by pool values below, so the clamp changes nothing.
        off = jnp.clip(j * bs - start, -bs, lc) + bs
        kn = kn_ref[pl.ds(off, bs)]                   # (bs, bh, H)
        vn = vn_ref[pl.ds(off, bs)]

        def in_chunk(shape):
            cidx = j * bs - start + jax.lax.broadcasted_iota(
                jnp.int32, shape, 0)
            return jnp.logical_and(cidx >= 0, cidx < length)

        sel = in_chunk(kn.shape)
        if quantized:
            kq, ksc = _quantize_tile(kn)
            vq, vsc = _quantize_tile(vn)
            sel1 = in_chunk(ksc.shape)
            mk = jnp.where(sel, kq, k_ref[0])
            mv = jnp.where(sel, vq, v_ref[0])
            msk = jnp.where(sel1, ksc, ks_ref[0])     # (bs, bh, 1) fp32
            msv = jnp.where(sel1, vsc, vs_ref[0])
        else:
            mk = jnp.where(sel, kn.astype(k_ref.dtype), k_ref[0])
            mv = jnp.where(sel, vn.astype(v_ref.dtype), v_ref[0])

        q = q_ref[...].astype(jnp.float32)            # (bh, Lc*G, H)
        # (bh, Lc*G, H) x (bh, H, bs) -> (bh, Lc*G, bs), batched over bh.
        s = jax.lax.dot_general(
            q, mk.astype(jnp.float32).transpose(1, 2, 0),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        if quantized:
            # Per-key-slot dequant of int8 codes (scores on codes, then
            # rescale — decode_attention's order).
            s = s * msk[..., 0].transpose(1, 0)[:, None, :]
        s = s * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        # Row r of the query tile is chunk query r // G; padded queries
        # (index >= length) see nothing.
        qi = jax.lax.broadcasted_iota(jnp.int32, (1, lc * g, bs), 1)
        if g > 1:
            qi = qi // g
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, lc * g, bs), 2)
        mask = jnp.logical_and(kpos <= start + qi, qi < length)
        s = jnp.where(mask, s, jnp.finfo(jnp.float32).min)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2))
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - safe_m[:, :, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe_m), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=2)
        if quantized:
            # Per-value-slot dequant folded into the probabilities.
            p = p * msv[..., 0].transpose(1, 0)[:, None, :]
        pv = jax.lax.dot_general(
            p, mv.astype(jnp.float32).transpose(1, 0, 2),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha[:, :, None] + pv
        m_ref[...] = m_new

        # Epilogue: destination steps write the merged tile back to the
        # pool (aliased refs — in place). Non-destination steps map the
        # output to the trash block, so this store is simply skipped.
        @pl.when(j >= start // bs)
        def _write():
            pk_out[0] = mk
            pv_out[0] = mv
            if quantized:
                ks_out[0] = msk
                vs_out[0] = msv

    @pl.when(j == n_blk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l[:, :, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("softcap", "bh", "interpret"))
def paged_prefill_attention(
    q: jax.Array,            # (1, Lc, NQ, H) — rope'd chunk queries
    k_new: jax.Array,        # (1, Lc, NKV, H) — chunk K/V (unquantized)
    v_new: jax.Array,
    pool_k: jax.Array,       # (num_blocks, block_size, NKV, H)
    pool_v: jax.Array,
    blocks: jax.Array,       # (mb,) int32 row block table, -1 = unallocated
    start: jax.Array,        # () int32 absolute position of chunk token 0
    length: jax.Array,       # () int32 real chunk length (<= Lc)
    k_scale: jax.Array | None = None,  # (num_blocks, block_size, NKV, 1)
    v_scale: jax.Array | None = None,
    *,
    softcap: float = 0.0,
    bh: int = 0,             # KV heads per grid step (0 = all)
    interpret: bool = True,
):
    """Returns (attn (1, Lc, NQ, H) dtype of q, pool_k, pool_v, k_scale,
    v_scale) — the pool planes updated in place (aliased) with the chunk's
    K/V at positions [start, start + length)."""
    _, Lc, NQ, H = q.shape
    bs, NKV = pool_k.shape[1], pool_k.shape[2]
    G = NQ // NKV
    mb = blocks.shape[0]
    if bh <= 0 or NKV % bh:
        bh = NKV
    quantized = k_scale is not None
    # (NKV, Lc*G, H): a KV head's G query heads of every chunk query.
    qr = q.reshape(Lc, NKV, G, H).transpose(1, 0, 2, 3).reshape(NKV, Lc * G, H)
    pad = ((bs, bs), (0, 0), (0, 0))
    kn = jnp.pad(k_new.reshape(Lc, NKV, H), pad)     # (Lc + 2*bs, NKV, H)
    vn = jnp.pad(v_new.reshape(Lc, NKV, H), pad)
    blocks = blocks.astype(jnp.int32)
    meta = jnp.stack([jnp.asarray(start, jnp.int32),
                      jnp.asarray(length, jnp.int32)])

    def q_map(h, j, tbl, mt):
        return (h, 0, 0)

    def new_map(h, j, tbl, mt):
        return (0, h, 0)

    def blk_map(h, j, tbl, mt):
        # Dead steps (unallocated block / past the chunk) remap to the
        # trash block 0: a repeated index issues no new DMA.
        live = jnp.logical_and(tbl[j] >= 0, j * bs <= mt[0] + mt[1] - 1)
        return (jnp.where(live, jnp.maximum(tbl[j], 0), 0), 0, h, 0)

    def dst_map(h, j, tbl, mt):
        # Destination steps write back through the aliased pool ref; all
        # other steps dump the (unwritten) output tile into the trash
        # block so resident prefix blocks are never rewritten.
        live = jnp.logical_and(tbl[j] >= 0, j * bs <= mt[0] + mt[1] - 1)
        dst = jnp.logical_and(live, j >= mt[0] // bs)
        return (jnp.where(dst, jnp.maximum(tbl[j], 0), 0), 0, h, 0)

    in_specs = [
        pl.BlockSpec((bh, Lc * G, H), q_map),
        pl.BlockSpec((Lc + 2 * bs, bh, H), new_map),
        pl.BlockSpec((Lc + 2 * bs, bh, H), new_map),
        pl.BlockSpec((1, bs, bh, H), blk_map),
        pl.BlockSpec((1, bs, bh, H), blk_map),
    ]
    operands = [qr, kn, vn, pool_k, pool_v]
    out_shapes = [
        jax.ShapeDtypeStruct((NKV, Lc * G, H), q.dtype),
        jax.ShapeDtypeStruct(pool_k.shape, pool_k.dtype),
        jax.ShapeDtypeStruct(pool_v.shape, pool_v.dtype),
    ]
    out_specs = [
        pl.BlockSpec((bh, Lc * G, H), q_map),
        pl.BlockSpec((1, bs, bh, H), dst_map),
        pl.BlockSpec((1, bs, bh, H), dst_map),
    ]
    # Operand indices count the scalar-prefetch args (blocks=0, meta=1).
    aliases = {5: 1, 6: 2}
    if quantized:
        in_specs += [
            pl.BlockSpec((1, bs, bh, 1), blk_map),
            pl.BlockSpec((1, bs, bh, 1), blk_map),
        ]
        operands += [k_scale, v_scale]
        out_shapes += [
            jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
            jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype),
        ]
        out_specs += [
            pl.BlockSpec((1, bs, bh, 1), dst_map),
            pl.BlockSpec((1, bs, bh, 1), dst_map),
        ]
        aliases.update({7: 3, 8: 4})

    kernel = functools.partial(
        _chunk_kernel, bs=bs, n_blk=mb, lc=Lc, g=G, scale=H**-0.5,
        softcap=softcap, quantized=quantized,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(NKV // bh, mb),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((bh, Lc * G), jnp.float32),
            pltpu.VMEM((bh, Lc * G), jnp.float32),
            pltpu.VMEM((bh, Lc * G, H), jnp.float32),
        ],
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        compiler_params=_compiler_params(("arbitrary", "arbitrary")),
        input_output_aliases=aliases,
        name="paged_prefill",
        interpret=interpret,
    )(blocks, meta, *operands)
    attn = outs[0].reshape(NKV, Lc, G, H).transpose(1, 0, 2, 3)
    attn = attn.reshape(1, Lc, NQ, H)
    if quantized:
        return attn, outs[1], outs[2], outs[3], outs[4]
    return attn, outs[1], outs[2], None, None
