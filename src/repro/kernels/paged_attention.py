"""Fused paged-attention decode Pallas kernel.

One query token per batch row attends directly against the paged KV pool
(`kv_cache.PagedKVCache` layout: the stacked `(L, num_blocks, block_size,
NKV·H)` planes plus a `(B, max_blocks)` block table; the kernel reads
layer `layer` of them in place, in the layout they are stored in, so the
model's scan over layers never slices a layer's plane out of the pool
and XLA never lays one out again). This is the
M4BRAM argument applied to the decode hot loop: compute happens where the
data already lives — no staging copy ("separate buffer") of the pool is
ever materialized, unlike the `paged_gather` → `decode_attention`
composition, which writes a contiguous `(B, max_blocks·bs, NKV, H)` copy
to HBM every step of every layer.

Mechanics:
  * The block table, per-row positions and the layer index arrive via
    **scalar prefetch** (`pltpu.PrefetchScalarGridSpec`) so the k/v
    BlockSpec index maps can resolve virtual block `j` of row `b` to
    block `(layer, table[b, j])` of the stacked pool *before* the grid
    step runs — the DMA streams exactly that block into VMEM, straight
    from the pool.
  * Grid is `(B, NKV/bh, max_blocks)` with the block dimension innermost
    ("arbitrary"), so the online-softmax running max / denominator /
    accumulator live in VMEM scratch across a row's blocks — the flash
    contract: per-(row, head-group, layer) HBM traffic is q + the row's
    *live* blocks + out.
  * Dead steps (unallocated table entries, blocks past the row's decode
    position) are remapped to pool block 0 — the reserved trash block —
    by the index map, so no new DMA is issued for them, and `pl.when`
    skips their compute. A row's cost scales with its actual length, not
    `max_blocks`.
  * GQA: all G query heads of a KV head are processed in one tile
    (`q` reshaped to `(B, NKV, G, H)`); `bh` KV heads share a grid step,
    a block's `(bs, bh·H)` lanes split per head in-kernel
    (`common.head_tiles`).
  * int8 pools dequantize **in-kernel**: each code block's fp32 scale
    row (the pool's lane-dense `(1, NKV·bs)` head-major layout, a head
    group's `bh·bs` lanes per grid step) streams alongside it and is
    unpacked to `(bh, bs)` by `common.slot_scales`; scores are computed
    on int8 codes and rescaled per key slot, probabilities are rescaled
    per value slot — exactly `decode_attention`'s quantized math, with no
    bf16 copy of the cache anywhere.

Masking matches the gather-based reference: a slot is visible iff its
virtual block is allocated and its absolute position `kpos <= q_pos[b]`.
Rows whose table is all `-1` (freed slots) see nothing and output zeros —
their logits are discarded by the scheduler, and the trash block never
contributes to a live row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import compiler_params as _compiler_params
from repro.kernels.common import head_tiles, slot_scales


def _paged_kernel(tbl_ref, pos_ref, lay_ref, q_ref, k_ref, v_ref, *rest,
                  bs: int, n_blk: int, scale: float, softcap: float,
                  quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[b]
    visible = jnp.logical_and(tbl_ref[b, j] >= 0, j * bs <= pos)

    @pl.when(visible)
    def _step():
        q = q_ref[0].astype(jnp.float32)          # (bh, G, H)
        bh = q.shape[0]
        k = head_tiles(k_ref[...].astype(jnp.float32), bh)   # (bh, bs, H)
        v = head_tiles(v_ref[...].astype(jnp.float32), bh)   # (bh, bs, H)
        # Scores for all G query heads of each of the bh KV heads at once:
        # (bh, G, H) x (bh, bs, H) -> (bh, G, bs), batched over bh.
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        if quantized:
            # Per-key-slot dequant of int8 codes (same order as
            # decode_attention: scores on codes, then rescale).
            s = s * slot_scales(ks_ref[...], bs)[:, None, :]
        s = s * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
        mask = kpos <= pos
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
            p = p * slot_scales(vs_ref[...], bs)[:, None, :]
        pv = jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha[:, :, None] + pv
        m_ref[...] = m_new

    @pl.when(j == n_blk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, :, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("softcap", "bh", "interpret"))
def paged_attention(
    q: jax.Array,            # (B, 1, NQ, H) — one new token per row
    pool_k: jax.Array,       # (L, num_blocks, block_size, NKV*H)
    pool_v: jax.Array,
    block_table: jax.Array,  # (B, max_blocks) int32, -1 = unallocated
    q_pos: jax.Array,        # (B,) per-row decode position
    layer: jax.Array,        # () int32 — the pool layer attended
    k_scale: jax.Array | None = None,  # (L, num_blocks, 1, NKV*block_size)
    v_scale: jax.Array | None = None,
    *,
    softcap: float = 0.0,
    bh: int = 0,             # KV heads per grid step (0 = all)
    interpret: bool = True,
) -> jax.Array:
    """Returns (B, 1, NQ, H) attention output, dtype of q."""
    B, _, NQ, H = q.shape
    bs, NKV = pool_k.shape[2], pool_k.shape[3] // H
    G = NQ // NKV
    maxb = block_table.shape[1]
    if bh <= 0 or NKV % bh:
        bh = NKV
    quantized = k_scale is not None
    qr = q.reshape(B, NKV, G, H)
    block_table = block_table.astype(jnp.int32)
    q_pos = q_pos.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def qo_map(b, h, j, tbl, qp, lay):
        return (b, h, 0, 0)

    def blk_map(b, h, j, tbl, qp, lay):
        # Dead steps (unallocated block / past the row's position) remap
        # to the trash block 0: the pipeline sees a repeated index and
        # issues no new DMA, keeping traffic at the row's live blocks.
        live = jnp.logical_and(tbl[b, j] >= 0, j * bs <= qp[b])
        return (lay[0], jnp.where(live, jnp.maximum(tbl[b, j], 0), 0), 0, h)

    pool_spec = pl.BlockSpec((None, None, bs, bh * H), blk_map)
    in_specs = [pl.BlockSpec((1, bh, G, H), qo_map), pool_spec, pool_spec]
    operands = [qr, pool_k, pool_v]
    if quantized:
        row_spec = pl.BlockSpec((None, None, 1, bh * bs), blk_map)
        in_specs += [row_spec, row_spec]
        operands += [k_scale, v_scale]

    kernel = functools.partial(
        _paged_kernel, bs=bs, n_blk=maxb, scale=H**-0.5,
        softcap=softcap, quantized=quantized,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, NKV // bh, maxb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bh, G, H), qo_map),
        scratch_shapes=[
            pltpu.VMEM((bh, G), jnp.float32),
            pltpu.VMEM((bh, G), jnp.float32),
            pltpu.VMEM((bh, G, H), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, NKV, G, H), q.dtype),
        compiler_params=_compiler_params(("parallel", "parallel", "arbitrary")),
        name="paged_attention",
        interpret=interpret,
    )(block_table, q_pos, layer, *operands)
    return out.reshape(B, 1, NQ, H)
