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

The pool is the stacked ``(L, num_blocks, block_size, NKV·H)`` planes
of ``kv_cache.PagedKVCache`` (plus, for an int8 pool, its lane-dense
``(L, num_blocks, 1, NKV·block_size)`` scale rows); the kernel reads and
writes layer ``layer`` of them in place, in the layout they are stored
in, so the model's scan over layers carries the whole pool and never
slices a layer's plane out of it or lays one out again.

Mechanics:
  * The row's block table and ``(start, length, layer)`` arrive via
    **scalar prefetch** (``pltpu.PrefetchScalarGridSpec``) so the pool
    BlockSpec index maps can resolve virtual block ``j`` to block
    ``(layer, table[j])`` of the stacked pool before the grid step runs —
    one DMA streams exactly that block.
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
  * int8 pools quantize on write with ``kv_cache.quantize_kv`` itself,
    applied to the chunk before the kernel (so pool bytes are
    bit-identical to the scatter path's): the kernel merges the chunk's
    codes and, per destination block, its scale row (laid out like the
    pool's rows) into the streamed tiles, and dequantizes in-kernel on
    read: scores are computed on int8 codes and rescaled per key slot,
    probabilities per value slot — ``decode_attention``'s quantized math,
    like the decode kernel.

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
from repro.kernels.common import head_tiles, slot_scales


def _chunk_kernel(tbl_ref, meta_ref, q_ref, kn_ref, vn_ref, k_ref, v_ref,
                  *rest, bs: int, n_blk: int, lc: int, g: int, scale: float,
                  softcap: float, quantized: bool):
    if quantized:
        (ks_ref, vs_ref, ksn_ref, vsn_ref, o_ref, pk_out, pv_out, ks_out,
         vs_out, m_ref, l_ref, acc_ref) = rest
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
        # kn/vn hold the chunk's K/V (in the pool's dtype) laid out as
        # this block's slots; slots outside the chunk keep their pool
        # values.
        def in_chunk(shape, dim, slot):
            cidx = j * bs - start + slot(
                jax.lax.broadcasted_iota(jnp.int32, shape, dim))
            return jnp.logical_and(cidx >= 0, cidx < length)

        sel = in_chunk(k_ref.shape, 0, lambda t: t)
        mk = jnp.where(sel, kn_ref[...], k_ref[...])  # (bs, bh*H)
        mv = jnp.where(sel, vn_ref[...], v_ref[...])
        if quantized:
            # Scale rows: lane h*bs + t holds slot t of head h.
            sel1 = in_chunk(ks_ref.shape, 1, lambda lane: lane % bs)
            msk = jnp.where(sel1, ksn_ref[...], ks_ref[...])  # (1, bh*bs)
            msv = jnp.where(sel1, vsn_ref[...], vs_ref[...])

        q = q_ref[...].astype(jnp.float32)            # (bh, Lc*G, H)
        bh = q.shape[0]
        # (bh, Lc*G, H) x (bh, bs, H) -> (bh, Lc*G, bs), batched over bh.
        s = jax.lax.dot_general(
            q, head_tiles(mk.astype(jnp.float32), bh),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        if quantized:
            # Per-key-slot dequant of int8 codes (scores on codes, then
            # rescale — decode_attention's order).
            s = s * slot_scales(msk, bs)[:, None, :]
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
            p = p * slot_scales(msv, bs)[:, None, :]
        pv = jax.lax.dot_general(
            p, head_tiles(mv.astype(jnp.float32), bh),
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
            pk_out[...] = mk
            pv_out[...] = mv
            if quantized:
                ks_out[...] = msk
                vs_out[...] = msv

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
    pool_k: jax.Array,       # (L, num_blocks, block_size, NKV*H)
    pool_v: jax.Array,
    blocks: jax.Array,       # (mb,) int32 row block table, -1 = unallocated
    start: jax.Array,        # () int32 absolute position of chunk token 0
    length: jax.Array,       # () int32 real chunk length (<= Lc)
    layer: jax.Array,        # () int32 — the pool layer attended and written
    k_scale: jax.Array | None = None,  # (L, num_blocks, 1, NKV*block_size)
    v_scale: jax.Array | None = None,
    *,
    softcap: float = 0.0,
    bh: int = 0,             # KV heads per grid step (0 = all)
    interpret: bool = True,
):
    """Returns (attn (1, Lc, NQ, H) dtype of q, pool_k, pool_v, k_scale,
    v_scale) — the stacked pool planes updated in place (aliased) with
    the chunk's K/V at positions [start, start + length) of layer
    `layer`; every other layer and block keeps its bytes."""
    from repro.models.kv_cache import merge_heads, quantize_kv, scale_rows

    _, Lc, NQ, H = q.shape
    bs, NKV = pool_k.shape[2], pool_k.shape[3] // H
    G = NQ // NKV
    mb = blocks.shape[0]
    if bh <= 0 or NKV % bh:
        bh = NKV
    quantized = k_scale is not None
    # (NKV, Lc*G, H): a KV head's G query heads of every chunk query.
    qr = q.reshape(Lc, NKV, G, H).transpose(1, 0, 2, 3).reshape(NKV, Lc * G, H)
    # The chunk's K/V and scales as the slots of each block it writes
    # (at most n_dst, from block start // bs on), so a destination step
    # reads its tile whole; slots outside the chunk hold a clamped
    # neighbour, which the kernel's merge never selects.
    n_dst = (Lc + bs - 1) // bs + 1
    slot = (jnp.asarray(start, jnp.int32) // bs * bs
            + jnp.arange(n_dst * bs, dtype=jnp.int32).reshape(n_dst, bs))
    src = jnp.clip(slot - jnp.asarray(start, jnp.int32), 0, Lc - 1)
    kn, vn = k_new[0], v_new[0]                      # (Lc, NKV, H)
    if quantized:
        kn, ksn = quantize_kv(kn)
        vn, vsn = quantize_kv(vn)
        ksn, vsn = scale_rows(ksn[src]), scale_rows(vsn[src])
    kn = merge_heads(kn).astype(pool_k.dtype)[src]  # (n_dst, bs, NKV*H)
    vn = merge_heads(vn).astype(pool_v.dtype)[src]
    blocks = blocks.astype(jnp.int32)
    meta = jnp.stack([jnp.asarray(start, jnp.int32),
                      jnp.asarray(length, jnp.int32),
                      jnp.asarray(layer, jnp.int32)])

    def q_map(h, j, tbl, mt):
        return (h, 0, 0)

    def live(j, tbl, mt):
        return jnp.logical_and(tbl[j] >= 0, j * bs <= mt[0] + mt[1] - 1)

    def dst(j, tbl, mt):
        return jnp.logical_and(live(j, tbl, mt), j >= mt[0] // bs)

    def src_block(j, tbl, mt):
        # Dead steps (unallocated block / past the chunk) remap to the
        # trash block 0: a repeated index issues no new DMA.
        return jnp.where(live(j, tbl, mt), jnp.maximum(tbl[j], 0), 0)

    def dst_block(j, tbl, mt):
        # Destination steps write back through the aliased pool ref; all
        # other steps dump the (unwritten) output tile into the trash
        # block so resident prefix blocks are never rewritten.
        return jnp.where(dst(j, tbl, mt), jnp.maximum(tbl[j], 0), 0)

    def blk_map(h, j, tbl, mt):
        return (mt[2], src_block(j, tbl, mt), 0, h)

    def dst_map(h, j, tbl, mt):
        return (mt[2], dst_block(j, tbl, mt), 0, h)

    def new_index(j, tbl, mt):
        # Only destination steps merge the chunk; others re-read tile 0.
        return jnp.where(dst(j, tbl, mt), j - mt[0] // bs, 0)

    def new_map(h, j, tbl, mt):
        return (new_index(j, tbl, mt), 0, h)

    pool_spec = pl.BlockSpec((None, None, bs, bh * H), blk_map)
    pool_out_spec = pl.BlockSpec((None, None, bs, bh * H), dst_map)
    new_spec = pl.BlockSpec((None, bs, bh * H), new_map)
    in_specs = [
        pl.BlockSpec((bh, Lc * G, H), q_map),
        new_spec,
        new_spec,
        pool_spec,
        pool_spec,
    ]
    operands = [qr, kn, vn, pool_k, pool_v]
    out_shapes = [
        jax.ShapeDtypeStruct((NKV, Lc * G, H), q.dtype),
        jax.ShapeDtypeStruct(pool_k.shape, pool_k.dtype),
        jax.ShapeDtypeStruct(pool_v.shape, pool_v.dtype),
    ]
    out_specs = [
        pl.BlockSpec((bh, Lc * G, H), q_map),
        pool_out_spec,
        pool_out_spec,
    ]
    # Operand indices count the scalar-prefetch args (blocks=0, meta=1).
    aliases = {5: 1, 6: 2}
    if quantized:
        row_spec = pl.BlockSpec((None, None, 1, bh * bs), blk_map)
        new_row_spec = pl.BlockSpec((None, 1, bh * bs), new_map)
        in_specs += [row_spec, row_spec, new_row_spec, new_row_spec]
        operands += [k_scale, v_scale, ksn, vsn]
        out_shapes += [
            jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
            jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype),
        ]
        row_out_spec = pl.BlockSpec((None, None, 1, bh * bs), dst_map)
        out_specs += [row_out_spec, row_out_spec]
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
