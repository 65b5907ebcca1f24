"""Decode-time state structures (registered pytrees).

Attention KV caches are ring buffers when the arch uses sliding-window /
local attention (cache size = window, not sequence length — this is what
makes long_500k decode cells feasible for mixtral/recurrentgemma), and
full-length buffers for global attention. Recurrent families carry O(1)
states (RG-LRU hidden, conv tail, RWKV wkv state + token-shift tails).

All leaves carry a leading layer (or group) axis so decode steps scan over
layers exactly like training does.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class KVCache:
    """k/v: (L, B, S, NKV, H); slot_pos: (L, B, S) absolute position of each
    cache slot *per batch row* (−1 = empty); length: (B,) per-row count of
    tokens written.

    Every position-tracking leaf carries a batch axis so the continuous-
    batching scheduler can hold sequences at different decode depths in one
    cache: batch row b advances independently, and admitting a new request
    only rewrites row b (see `scatter_into_slot`).

    Optional int8 quantization (§Perf lever, the paper's activation-
    quantization idea applied to the cache): k/v hold int8 codes and
    k_scale/v_scale hold per-(slot, head) fp32 scales — HBM traffic per
    decode step drops ~2× (int8 + one scale per head vs bf16)."""

    k: jax.Array
    v: jax.Array
    slot_pos: jax.Array
    length: jax.Array
    k_scale: Optional[jax.Array] = None  # (L, B, S, NKV, 1) fp32
    v_scale: Optional[jax.Array] = None
    window: int = 0  # 0 = full cache; >0 = ring buffer of this size

    def tree_flatten(self):
        return (self.k, self.v, self.slot_pos, self.length,
                self.k_scale, self.v_scale), (self.window,)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, window=aux[0])

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @staticmethod
    def init(layers: int, batch: int, size: int, n_kv: int, head_dim: int,
             window: int = 0, dtype=jnp.bfloat16,
             quantized: bool = False) -> "KVCache":
        # Windowed caches are always window-sized rings (slot = pos % window
        # must never collide with a live position).
        s = window if window else size
        kd = jnp.int8 if quantized else dtype
        scale = (
            jnp.zeros((layers, batch, s, n_kv, 1), jnp.float32)
            if quantized else None
        )
        return KVCache(
            k=jnp.zeros((layers, batch, s, n_kv, head_dim), kd),
            v=jnp.zeros((layers, batch, s, n_kv, head_dim), kd),
            slot_pos=jnp.full((layers, batch, s), -1, jnp.int32),
            length=jnp.zeros((batch,), jnp.int32),
            k_scale=scale,
            v_scale=jnp.copy(scale) if quantized else None,
            window=window,
        )


def quantize_kv(x: jax.Array):
    """Per-(token, head) int8 symmetric quantization of (..., NKV, H)."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = absmax / 127.0
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    codes = jnp.clip(jnp.round(x.astype(jnp.float32) * inv), -128, 127)
    return codes.astype(jnp.int8), scale


def dequantize_kv(codes: jax.Array, scale: jax.Array) -> jax.Array:
    """Exact read-side inverse of `quantize_kv`: fp32 ``codes * scale``.

    Every consumer that reads quantized K/V *values* (rather than scoring
    on raw codes like `decode_attention`) must go through this one helper:
    the prefix-cache bit-identity contract requires a warm prefill reading
    pool codes to see the very same floats a cold prefill saw when it read
    its own freshly quantized K/V."""
    return codes.astype(jnp.float32) * scale


def ring_align(k_full, v_full, lengths, window: int):
    """Pack full-sequence prefill K/V (L, B, S, NKV, H) into the ring-buffer
    invariant used by cache_write: position p lives at slot p % window.

    `lengths` is the per-row count of real (right-padded) tokens, or None
    for "every row is full length S". Each row keeps its own last
    min(length, window) positions; empty ring slots carry slot_pos = -1
    (their values are never read — decode_attention masks them).

    Returns (k (L, B, window, NKV, H), v, slot_pos (L, B, window))."""
    L, B, S = k_full.shape[:3]
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    lengths = lengths.astype(jnp.int32)
    B = max(B, lengths.shape[0])  # degenerate layer stacks keep batch = 1
    r = jnp.arange(window, dtype=jnp.int32)
    base = jnp.maximum(lengths - window, 0)[:, None]        # (B, 1)
    # p[b, r]: the absolute position living in ring slot r of row b —
    # the unique p in [len-window, len) with p % window == r.
    p = base + jnp.mod(r[None, :] - base, window)           # (B, window)
    valid = p < lengths[:, None]
    idx = jnp.minimum(p, S - 1)[None, :, :, None, None]     # clip for gather

    def gather(a):
        return jnp.take_along_axis(a, idx.astype(jnp.int32), axis=2)

    slot_pos = jnp.where(valid, p, -1)
    return gather(k_full), gather(v_full), jnp.broadcast_to(
        slot_pos[None], (L, B, window)
    )


def full_slot_pos(layers: int, batch: int, size: int, lengths) -> jax.Array:
    """slot_pos (layers, batch, size) for a full (non-ring) cache where
    array slot == absolute position. Slots at or beyond the per-row length
    (right-pad slots, decode headroom) are marked empty (-1)."""
    s = jnp.arange(size, dtype=jnp.int32)
    if lengths is None:
        sp = jnp.broadcast_to(s, (batch, size))
    else:
        sp = jnp.where(s[None, :] < lengths[:, None].astype(jnp.int32),
                       s[None, :], -1)
    return jnp.broadcast_to(sp[None], (layers, batch, size))


def write_slot(pos, size, window: int):
    """Cache slot index for absolute position(s) `pos`.
    Full cache: slot = pos (clamped). Ring buffer: slot = pos % size."""
    return jnp.where(window > 0, pos % size, jnp.minimum(pos, size - 1))


def row_write(cache, new, slot):
    """Per-row slot write: cache (B, S, ...), new (B, 1, ...), slot (B,).
    Each batch row writes its own slot (lowered as a batched scatter)."""
    return jax.vmap(
        lambda c, n, s: jax.lax.dynamic_update_slice_in_dim(
            c, n.astype(c.dtype), s, axis=0
        )
    )(cache, new, slot)


def cache_write(k_cache, v_cache, slot_pos, k_new, v_new, pos, window: int):
    """Write one token's k/v (B, 1, NKV, H) at per-row absolute positions
    `pos` (B,) — each batch row advances independently (per-slot decode).

    Full cache: slot = pos. Ring buffer: slot = pos % size. slot_pos is
    (B, S). Returns updated (k_cache, v_cache, slot_pos).
    """
    size = k_cache.shape[1]
    slot = write_slot(pos, size, window)
    k_cache = row_write(k_cache, k_new, slot)
    v_cache = row_write(v_cache, v_new, slot)
    slot_pos = row_write(slot_pos, pos[:, None].astype(jnp.int32), slot)
    return k_cache, v_cache, slot_pos


# --------------------------------------------------------------------------
# Paged KV cache: shared block pool + per-slot block tables
# --------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedKVCache:
    """Block-pool KV cache for full-attention decode (the serving analogue
    of the paper's utilization argument: capacity is sized for the *actual*
    resident tokens, not a worst-case per-slot reservation).

    k/v: (L, num_blocks, block_size, NKV·H) — one pool shared by every
    batch slot, each slot's heads side by side in one row (the bytes of
    an (…, NKV, H) array; `split_heads` gives that view). block_table:
    (B, max_blocks) int32 maps a row's virtual block j (covering absolute
    positions [j·bs, (j+1)·bs)) to a pool block; -1 = unallocated. Pool block 0 is a reserved trash block: writes from
    free slots and unallocated virtual blocks land there and are never
    read. length: (B,) tokens written per row.

    Absolute position p of row b resolves to
    (block_table[b, p // block_size], p % block_size); gathering a row's
    blocks in table order therefore reproduces the contiguous layout slot
    == position, which is what makes the paged path bit-identical to the
    contiguous one.

    Optional int8 pool (the contiguous cache's kv_cache_quant applied to
    the block pool): k/v hold int8 codes and k_scale/v_scale hold the
    per-(slot, head) fp32 scales, written by the quantizing
    `paged_cache_write` — roughly 2× the tokens per pooled byte. A scale
    plane is (L, num_blocks, 1, NKV·block_size): one lane-dense row per
    (layer, block), head-major, so the scale of slot s, head h sits at
    lane h·block_size + s (`scale_rows` / `scale_slots` convert to and
    from the per-slot (…, block_size, NKV, 1) view).

    Both layouts are what the TPU stores without padding and what Mosaic
    reads as stored: an (…, NKV, H) code plane would be padded in the
    tiled layout the kernels need (H = 160 to 256 lanes), so XLA keeps
    it with num_blocks minor and relays it out around every kernel call;
    an (…, NKV, 1) scale plane would be padded 128-fold. Rows of NKV·H
    and NKV·block_size lanes (multiples of 128 at served widths) are not.

    The decode and chunk programs carry the stacked planes through their
    scan over layers and update them in place: every writer and both
    pool kernels take the whole (L, …) plane plus a layer index, so no
    per-layer slice of the pool is ever copied out or stacked back.

    Pool blocks have no intrinsic owner: nothing stops two rows' tables
    from mapping to the same pool block, which is exactly how the
    cross-request prefix cache shares prompt-prefix blocks (scale planes
    included for an int8 pool). Ownership lives host-side in the
    scheduler's allocator — per-block reference counts, an LRU of
    unreferenced-but-cached prefix blocks, and copy-on-write
    (`copy_pool_block`) before a row appends into a shared block.

    That host-side ownership is also what makes preemption free at this
    layer: evicting a row clears its block-table ROW, never the pool
    bytes. The K/V a preempted request computed stays resident in its
    (now refcount-0, prefix-indexed) blocks, so a warm resume just maps
    them into a fresh table row; nothing device-side is saved, restored,
    or recomputed unless the blocks were meanwhile evicted for capacity.
    Corollary: a pool block's bytes must be treated as immutable from
    the moment any digest is registered against it (the scheduler
    enforces this by copy-on-write even for a sole referencer)."""

    k: jax.Array                         # (L, num_blocks, bs, NKV*H)
    v: jax.Array
    block_table: jax.Array
    length: jax.Array
    k_scale: Optional[jax.Array] = None  # (L, num_blocks, 1, NKV*bs) fp32
    v_scale: Optional[jax.Array] = None
    block_size: int = 16

    def tree_flatten(self):
        return (self.k, self.v, self.block_table, self.length,
                self.k_scale, self.v_scale), (self.block_size,)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, block_size=aux[0])

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def num_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def blocks_per_row(self) -> int:
        return self.block_table.shape[1]

    @staticmethod
    def init(layers: int, batch: int, num_blocks: int, block_size: int,
             max_blocks: int, n_kv: int, head_dim: int,
             dtype=jnp.bfloat16, quantized: bool = False) -> "PagedKVCache":
        kd = jnp.int8 if quantized else dtype
        scale = (
            jnp.zeros((layers, num_blocks, 1, n_kv * block_size), jnp.float32)
            if quantized else None
        )
        return PagedKVCache(
            k=jnp.zeros((layers, num_blocks, block_size, n_kv * head_dim), kd),
            v=jnp.zeros((layers, num_blocks, block_size, n_kv * head_dim), kd),
            block_table=jnp.full((batch, max_blocks), -1, jnp.int32),
            length=jnp.zeros((batch,), jnp.int32),
            k_scale=scale,
            v_scale=jnp.copy(scale) if quantized else None,
            block_size=block_size,
        )


def merge_heads(x):
    """(..., NKV, H) -> the pool's (..., NKV·H) rows (a reshape)."""
    return x.reshape(*x.shape[:-2], x.shape[-2] * x.shape[-1])


def split_heads(rows, n_kv: int):
    """Inverse of `merge_heads`: (..., NKV·H) -> (..., NKV, H)."""
    return rows.reshape(*rows.shape[:-1], n_kv, rows.shape[-1] // n_kv)


def scale_rows(slots):
    """Per-slot scales (..., block_size, NKV, 1) -> the pool's scale rows
    (..., 1, NKV·block_size), head-major: slot s of head h at lane
    h·block_size + s. Exact (a transpose); works on numpy arrays too."""
    x = slots[..., 0].swapaxes(-1, -2)
    return x.reshape(*x.shape[:-2], 1, x.shape[-2] * x.shape[-1])


def scale_slots(rows, block_size: int):
    """Inverse of `scale_rows`: (..., 1, NKV·block_size) ->
    (..., block_size, NKV, 1)."""
    x = rows.reshape(*rows.shape[:-2], rows.shape[-1] // block_size,
                     block_size)
    return x.swapaxes(-1, -2)[..., None]


def paged_slot(block_table, pos, block_size: int):
    """Resolve per-row absolute positions `pos` (B,) to (pool block (B,),
    in-block offset (B,)). Unallocated virtual blocks (and free slots,
    whose tables are all -1) resolve to the trash block 0."""
    idx = jnp.clip(pos // block_size, 0, block_table.shape[1] - 1)
    blk = jnp.take_along_axis(block_table, idx[:, None].astype(jnp.int32),
                              axis=1)[:, 0]
    return jnp.maximum(blk, 0), pos % block_size


def _write_scales(k_scale, v_scale, layer, blk, off, ks, vs,
                  block_size: int):
    """Write per-(token, head) scales (N, NKV) into the stacked scale rows
    at (layer, blk[n], lane h·block_size + off[n]), one token's rows at a
    time (read, replace its NKV lanes, write back). A scatter would do the
    same, but on a TPU XLA runs it on a relaid-out copy of the whole
    stacked plane; these row updates stay in place."""
    lane = jnp.arange(k_scale.shape[-1], dtype=jnp.int32)
    ks = jnp.repeat(ks, block_size, axis=1)       # (N, W): head h's lanes
    vs = jnp.repeat(vs, block_size, axis=1)

    def one(n, planes):
        at = (layer, blk[n], 0, 0)
        keep = lane % block_size != off[n]
        return tuple(
            jax.lax.dynamic_update_slice(
                p, jnp.where(keep, jax.lax.dynamic_slice(
                    p, at, (1, 1, 1, p.shape[-1])), new[n]), at)
            for p, new in zip(planes, (ks, vs)))

    return jax.lax.fori_loop(0, blk.shape[0], one, (k_scale, v_scale))


def paged_cache_write(pool_k, pool_v, block_table, k_new, v_new, pos,
                      block_size: int, k_scale=None, v_scale=None, *, layer):
    """Write one token's k/v (B, 1, NKV, H) into layer `layer` of the
    stacked pool (L, num_blocks, block_size, NKV·H) at per-row positions
    `pos` (B,): only the B written slots change, in place inside a jitted
    program that donates or carries the planes. Live rows own disjoint
    blocks; free rows all write the trash block.

    When the pool is int8 (scale planes passed), the incoming bf16 k/v is
    quantized here — per-(token, head) symmetric codes land in the pool
    and their fp32 scales in the matching scale-row lanes. Returns
    (pool_k, pool_v, k_scale, v_scale); the scales are None passthroughs
    for an unquantized pool."""
    blk, off = paged_slot(block_table, pos, block_size)
    if k_scale is not None:
        k_new, ks = quantize_kv(k_new)
        v_new, vs = quantize_kv(v_new)
        k_scale, v_scale = _write_scales(k_scale, v_scale, layer, blk, off,
                                         ks[:, 0, :, 0], vs[:, 0, :, 0],
                                         block_size)
    pool_k = pool_k.at[layer, blk, off].set(
        merge_heads(k_new[:, 0]).astype(pool_k.dtype))
    pool_v = pool_v.at[layer, blk, off].set(
        merge_heads(v_new[:, 0]).astype(pool_v.dtype))
    return pool_k, pool_v, k_scale, v_scale


def paged_chunk_write(pool_k, pool_v, blocks, k_new, v_new, start, length,
                      block_size: int, k_scale=None, v_scale=None, *, layer):
    """Write one row's `length`-token chunk (1, Lc, NKV, H) into layer
    `layer` of the stacked pool at absolute positions [start, start +
    length), routed through the row's own block table `blocks` (mb,).
    Padded chunk slots (t >= length) and positions past the table are
    routed to the trash block 0, so a fixed-shape Lc never touches blocks
    a later chunk owns. int8 pools quantize on write exactly like
    `paged_cache_write`. Returns (pool_k, pool_v, k_scale, v_scale)."""
    Lc = k_new.shape[1]
    pos = jnp.asarray(start, jnp.int32) + jnp.arange(Lc, dtype=jnp.int32)
    valid = jnp.arange(Lc) < length
    idx = jnp.clip(pos // block_size, 0, blocks.shape[0] - 1)
    blk = jnp.where(valid, jnp.maximum(blocks[idx], 0), 0)
    off = pos % block_size
    k_new, v_new = k_new[0], v_new[0]
    if k_scale is not None:
        k_new, ks = quantize_kv(k_new)
        v_new, vs = quantize_kv(v_new)
        k_scale, v_scale = _write_scales(k_scale, v_scale, layer, blk, off,
                                         ks[..., 0], vs[..., 0], block_size)
    pool_k = pool_k.at[layer, blk, off].set(
        merge_heads(k_new).astype(pool_k.dtype))
    pool_v = pool_v.at[layer, blk, off].set(
        merge_heads(v_new).astype(pool_v.dtype))
    return pool_k, pool_v, k_scale, v_scale


def paged_gather(pool_k, pool_v, block_table, k_scale=None, v_scale=None,
                 max_blocks: Optional[int] = None, *, layer, n_kv: int):
    """Gather each row's blocks in table order from layer `layer` of the
    stacked pool (`n_kv` KV heads a slot): returns (k (B, S, NKV, H), v,
    kpos (B, S), k_scale, v_scale) with S = max_blocks · block_size and
    kpos[b, p] = p where row b's virtual block p // bs is allocated, -1
    elsewhere — the exact (values, positions) layout of the contiguous
    cache, ready for decode_attention. Scale rows of an int8 pool gather
    the same way and come back per slot ((B, S, NKV, 1) —
    decode_attention's quantized-cache layout), None for a bf16 pool.

    `max_blocks` (host-known, static) clamps the gather to the first
    `max_blocks` table columns: when the caller knows no live row has
    more than that many allocated blocks (the scheduler's allocator
    does), the dead-weight gather of guaranteed-unallocated trash-block
    columns is skipped entirely instead of copying blocks_per_row blocks
    per row every step."""
    if max_blocks is not None:
        block_table = block_table[:, :max_blocks]
    B, n_blocks = block_table.shape
    bs = pool_k.shape[2]
    tbl = jnp.maximum(block_table, 0)
    k_rows = split_heads(pool_k[layer, tbl], n_kv).reshape(
        B, n_blocks * bs, n_kv, -1)
    v_rows = split_heads(pool_v[layer, tbl], n_kv).reshape(
        B, n_blocks * bs, n_kv, -1)
    virt = jnp.arange(n_blocks * bs, dtype=jnp.int32)
    alloc = jnp.repeat(block_table >= 0, bs, axis=1)
    kpos = jnp.where(alloc, virt[None, :], -1)
    ks_rows = vs_rows = None
    if k_scale is not None:
        ks_rows = scale_slots(k_scale[layer, tbl], bs).reshape(
            B, n_blocks * bs, n_kv, 1)
        vs_rows = scale_slots(v_scale[layer, tbl], bs).reshape(
            B, n_blocks * bs, n_kv, 1)
    return k_rows, v_rows, kpos, ks_rows, vs_rows


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RecurrentState:
    """Griffin recurrent-block state: RG-LRU hidden + causal-conv tail.

    h: (L, B, W); conv_tail: (L, B, conv_width-1, W).
    """

    h: jax.Array
    conv_tail: jax.Array

    def tree_flatten(self):
        return (self.h, self.conv_tail), ()

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RwkvState:
    """RWKV-6 per-layer state: wkv (L, B, H, K, V) + token-shift tails
    (L, B, d) for time-mix and channel-mix."""

    wkv: jax.Array
    tm_shift: jax.Array
    cm_shift: jax.Array

    def tree_flatten(self):
        return (self.wkv, self.tm_shift, self.cm_shift), ()

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DecodeCache:
    """Top-level decode carry: whichever sub-states the family uses, plus
    per-slot position counters.

    pos: (B,) int32 — the absolute position each batch slot decodes at.
    Slots are independent: the continuous-batching scheduler holds requests
    at different depths in one cache and one compiled decode signature."""

    pos: jax.Array
    kv: Optional[KVCache] = None
    rec: Optional[RecurrentState] = None
    rwkv: Optional[RwkvState] = None

    def tree_flatten(self):
        return (self.pos, self.kv, self.rec, self.rwkv), ()

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)


# --------------------------------------------------------------------------
# Slot scatter: admit one prefilled request into a batch cache row
# --------------------------------------------------------------------------


def _write_row(big, small, slot):
    """Overwrite batch row `slot` of `big` (batch axis 1) with `small`
    (batch axis 1 of size 1)."""
    start = (0, slot) + (0,) * (big.ndim - 2)
    return jax.lax.dynamic_update_slice(big, small.astype(big.dtype), start)


def _pad_seq(x, size: int, fill):
    """Pad the cache-slot axis (axis 2) of a solo-prefill leaf up to the
    batch cache's fixed size."""
    s = x.shape[2]
    if s == size:
        return x
    if s > size:
        raise ValueError(
            f"prefilled cache ({s} slots) exceeds batch cache capacity "
            f"({size}); raise the scheduler's max_ctx"
        )
    pad = jnp.full((*x.shape[:2], size - s, *x.shape[3:]), fill, x.dtype)
    return jnp.concatenate([x, pad], axis=2)


def _scatter_kv(big: KVCache, small: KVCache, slot) -> KVCache:
    size = big.k.shape[2]
    k = _write_row(big.k, _pad_seq(small.k, size, 0), slot)
    v = _write_row(big.v, _pad_seq(small.v, size, 0), slot)
    sp = _write_row(big.slot_pos, _pad_seq(small.slot_pos, size, -1), slot)
    length = jax.lax.dynamic_update_slice(
        big.length, small.length.astype(big.length.dtype), (slot,)
    )
    ks = vs = None
    if big.quantized:
        ks = _write_row(big.k_scale, _pad_seq(small.k_scale, size, 0.0), slot)
        vs = _write_row(big.v_scale, _pad_seq(small.v_scale, size, 0.0), slot)
    return KVCache(k=k, v=v, slot_pos=sp, length=length,
                   k_scale=ks, v_scale=vs, window=big.window)


def scatter_into_slot(batch: DecodeCache, solo: DecodeCache, slot) -> DecodeCache:
    """Admit a solo-prefilled request (batch axis of size 1) into batch
    row `slot` of a live decode cache. Only row `slot` changes — every
    other slot's KV / recurrent / RWKV state and position is untouched,
    which is what makes mid-decode admission safe.

    `slot` may be a traced scalar: one compiled scatter serves all slots
    (per solo-prefill length)."""
    slot = jnp.asarray(slot, jnp.int32)
    pos = jax.lax.dynamic_update_slice(
        batch.pos, solo.pos.astype(batch.pos.dtype), (slot,)
    )
    kv = _scatter_kv(batch.kv, solo.kv, slot) if batch.kv is not None else None
    rec = None
    if batch.rec is not None:
        rec = RecurrentState(
            h=_write_row(batch.rec.h, solo.rec.h, slot),
            conv_tail=_write_row(batch.rec.conv_tail, solo.rec.conv_tail, slot),
        )
    rwkv = None
    if batch.rwkv is not None:
        rwkv = RwkvState(
            wkv=_write_row(batch.rwkv.wkv, solo.rwkv.wkv, slot),
            tm_shift=_write_row(batch.rwkv.tm_shift, solo.rwkv.tm_shift, slot),
            cm_shift=_write_row(batch.rwkv.cm_shift, solo.rwkv.cm_shift, slot),
        )
    return DecodeCache(pos=pos, kv=kv, rec=rec, rwkv=rwkv)


def scatter_into_paged(batch: DecodeCache, solo: DecodeCache, slot,
                       row_blocks) -> DecodeCache:
    """Admit a solo-prefilled request into the paged pool. `solo` carries a
    contiguous full cache (right-padded: array slot == absolute position);
    its virtual block j goes to pool block row_blocks[j]. Entries past the
    allocated prompt blocks are -1 and land in the trash block (they hold
    only right-pad / headroom slots, which are empty anyway).

    `slot` may be traced; `row_blocks` is the (max_blocks,) block-table row
    the allocator filled for this request."""
    kv: PagedKVCache = batch.kv
    bs = kv.block_size
    s_solo = solo.kv.k.shape[2]
    nb = -(-s_solo // bs)
    pad = nb * bs - s_solo

    def as_blocks(a):
        if pad:
            a = jnp.pad(a, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 3))
        return a[:, 0].reshape(a.shape[0], nb, bs, *a.shape[3:])

    slot = jnp.asarray(slot, jnp.int32)
    row_blocks = jnp.asarray(row_blocks, jnp.int32)
    dst = jnp.maximum(
        jnp.take(row_blocks, jnp.arange(nb), mode="fill", fill_value=-1), 0
    )
    k = kv.k.at[:, dst].set(
        merge_heads(as_blocks(solo.kv.k)).astype(kv.k.dtype))
    v = kv.v.at[:, dst].set(
        merge_heads(as_blocks(solo.kv.v)).astype(kv.v.dtype))
    ks = vs = None
    if kv.quantized:
        # The solo prefill cache is quantized too (same cfg): its codes
        # scattered above, its per-(slot, head) scales go to the matching
        # scale rows.
        ks = kv.k_scale.at[:, dst].set(
            scale_rows(as_blocks(solo.kv.k_scale)).astype(kv.k_scale.dtype))
        vs = kv.v_scale.at[:, dst].set(
            scale_rows(as_blocks(solo.kv.v_scale)).astype(kv.v_scale.dtype))
    table = jax.lax.dynamic_update_slice(
        kv.block_table, row_blocks[None, : kv.blocks_per_row], (slot, 0)
    )
    length = jax.lax.dynamic_update_slice(
        kv.length, solo.kv.length.astype(kv.length.dtype), (slot,)
    )
    pos = jax.lax.dynamic_update_slice(
        batch.pos, solo.pos.astype(batch.pos.dtype), (slot,)
    )
    return DecodeCache(pos=pos, kv=PagedKVCache(
        k=k, v=v, block_table=table, length=length,
        k_scale=ks, v_scale=vs, block_size=bs))


def scatter_suffix_into_paged(batch: DecodeCache, solo: DecodeCache, slot,
                              row_blocks, start_block) -> DecodeCache:
    """Admit a *suffix-only* prefill (prefix-cache hit) into the paged
    pool. `solo` holds only the uncached tail of the prompt: its cache
    slot ``t`` corresponds to absolute position ``start_block·bs + t``
    (suffix writes always begin at a block boundary — only whole prompt
    blocks are ever shared), so virtual block ``start_block + j`` of the
    suffix goes to pool block ``row_blocks[start_block + j]``. Entries
    past the allocated span are -1 and land in the trash block, exactly
    like `scatter_into_paged`'s right-pad handling.

    ``slot`` and ``start_block`` may be traced; ``row_blocks`` is the
    full (max_blocks,) block-table row — shared prefix blocks included —
    which is written to the device table alongside the suffix data."""
    kv: PagedKVCache = batch.kv
    bs = kv.block_size
    s_solo = solo.kv.k.shape[2]
    nb = -(-s_solo // bs)
    pad = nb * bs - s_solo

    def as_blocks(a):
        if pad:
            a = jnp.pad(a, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 3))
        return a[:, 0].reshape(a.shape[0], nb, bs, *a.shape[3:])

    slot = jnp.asarray(slot, jnp.int32)
    row_blocks = jnp.asarray(row_blocks, jnp.int32)
    start_block = jnp.asarray(start_block, jnp.int32)
    dst = jnp.maximum(
        jnp.take(row_blocks, start_block + jnp.arange(nb), mode="fill",
                 fill_value=-1), 0
    )
    k = kv.k.at[:, dst].set(
        merge_heads(as_blocks(solo.kv.k)).astype(kv.k.dtype))
    v = kv.v.at[:, dst].set(
        merge_heads(as_blocks(solo.kv.v)).astype(kv.v.dtype))
    ks = vs = None
    if kv.quantized:
        ks = kv.k_scale.at[:, dst].set(
            scale_rows(as_blocks(solo.kv.k_scale)).astype(kv.k_scale.dtype))
        vs = kv.v_scale.at[:, dst].set(
            scale_rows(as_blocks(solo.kv.v_scale)).astype(kv.v_scale.dtype))
    table = jax.lax.dynamic_update_slice(
        kv.block_table, row_blocks[None, : kv.blocks_per_row], (slot, 0)
    )
    length = jax.lax.dynamic_update_slice(
        kv.length, solo.kv.length.astype(kv.length.dtype), (slot,)
    )
    pos = jax.lax.dynamic_update_slice(
        batch.pos, solo.pos.astype(batch.pos.dtype), (slot,)
    )
    return DecodeCache(pos=pos, kv=PagedKVCache(
        k=k, v=v, block_table=table, length=length,
        k_scale=ks, v_scale=vs, block_size=bs))


def set_paged_row(batch: DecodeCache, solo: DecodeCache, slot,
                  row_blocks) -> DecodeCache:
    """Admission metadata write for a *fully* prefix-cached prompt: every
    prompt position is already resident in shared pool blocks, so only the
    row's block table, length, and decode position change — no KV data
    moves. (`solo` is the one-token logits prefill; only its length/pos
    leaves are read.)"""
    kv: PagedKVCache = batch.kv
    slot = jnp.asarray(slot, jnp.int32)
    row_blocks = jnp.asarray(row_blocks, jnp.int32)
    table = jax.lax.dynamic_update_slice(
        kv.block_table, row_blocks[None, : kv.blocks_per_row], (slot, 0)
    )
    length = jax.lax.dynamic_update_slice(
        kv.length, solo.kv.length.astype(kv.length.dtype), (slot,)
    )
    pos = jax.lax.dynamic_update_slice(
        batch.pos, solo.pos.astype(batch.pos.dtype), (slot,)
    )
    return DecodeCache(pos=pos, kv=dataclasses.replace(
        kv, block_table=table, length=length))


def set_decode_positions(cache: DecodeCache, pos, length) -> DecodeCache:
    """Overwrite every row's decode position and live length in one device
    write — the speculative-decode bookkeeping op.

    Drafting advances each row's ``pos``/``length`` one token per draft
    step (the jitted decode step advances *all* rows) and the verify chunk
    sets its slot past every drafted position; after greedy acceptance the
    host knows the true position of every row (accepted prefix boundary
    for speculating rows, the pre-draft value for everyone else) and
    restores it here. Rejected positions' pool bytes are left stale — the
    position mask (`kpos <= q_pos`) hides them from every subsequent read,
    and the row's next writes land there anyway, so no pool rollback is
    needed; the entire rollback IS this metadata write."""
    kv: PagedKVCache = cache.kv
    return DecodeCache(
        pos=jnp.asarray(pos, jnp.int32),
        kv=dataclasses.replace(kv, length=jnp.asarray(length, jnp.int32)),
    )


def copy_pool_block(cache: DecodeCache, src, dst) -> DecodeCache:
    """Copy-on-write support: duplicate pool block `src` into `dst` across
    every layer (k, v, and the int8 scale planes when present). The
    allocator calls this before a row appends into a block it shares with
    other rows or with the prefix cache — the sharers keep reading the
    pristine block, the appender writes into its private copy. Copying the
    whole block (appended slots included) is safe: a row only ever reads
    slots below its own position, and its next writes overwrite the rest.

    `src`/`dst` may be traced scalars — one compiled copy serves every
    (src, dst) pair."""
    kv: PagedKVCache = cache.kv
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    k = kv.k.at[:, dst].set(kv.k[:, src])
    v = kv.v.at[:, dst].set(kv.v[:, src])
    ks = vs = None
    if kv.quantized:
        ks = kv.k_scale.at[:, dst].set(kv.k_scale[:, src])
        vs = kv.v_scale.at[:, dst].set(kv.v_scale[:, src])
    return dataclasses.replace(cache, kv=dataclasses.replace(
        kv, k=k, v=v, k_scale=ks, v_scale=vs))


def write_pool_block(cache: DecodeCache, dst, k, v,
                     k_scale=None, v_scale=None) -> DecodeCache:
    """Write one block's worth of K/V bytes into pool block `dst` across
    every layer — the swap-in half of the host-RAM block tier. `k`/`v`
    are (L, block_size, NKV, H) arrays in the pool's dtype (int8 codes
    for a quantized pool, with the fp32 `k_scale`/`v_scale` per-slot
    scales (L, block_size, NKV, 1) alongside), laid into the pool's rows
    by `merge_heads` and `scale_rows`; they round-trip device → pinned host numpy →
    device verbatim, which is what makes a warm-from-host admission
    bitwise identical to the blocks' original residency.

    `dst` may be a traced scalar — one compiled write serves every
    destination block."""
    kv: PagedKVCache = cache.kv
    dst = jnp.asarray(dst, jnp.int32)
    kk = kv.k.at[:, dst].set(merge_heads(jnp.asarray(k, kv.k.dtype)))
    vv = kv.v.at[:, dst].set(merge_heads(jnp.asarray(v, kv.v.dtype)))
    ks = vs = None
    if kv.quantized:
        ks = kv.k_scale.at[:, dst].set(
            scale_rows(jnp.asarray(k_scale, kv.k_scale.dtype)))
        vs = kv.v_scale.at[:, dst].set(
            scale_rows(jnp.asarray(v_scale, kv.v_scale.dtype)))
    return dataclasses.replace(cache, kv=dataclasses.replace(
        kv, k=kk, v=vv, k_scale=ks, v_scale=vs))


def grow_cache(cache: DecodeCache, size: int) -> DecodeCache:
    """Extend a full-attention contiguous cache's slot axis to at least
    `size` empty slots (ring buffers and recurrent states are position-
    unbounded and pass through untouched). This is what lets the static
    engine decode past the prefill headroom instead of silently rewriting
    the last slot via write_slot's clamp."""
    kv = cache.kv
    if kv is None or not isinstance(kv, KVCache) or kv.window:
        return cache
    cur = kv.k.shape[2]
    if cur >= size:
        return cache
    pad = size - cur
    zk = jnp.zeros((*kv.k.shape[:2], pad, *kv.k.shape[3:]), kv.k.dtype)
    sp = jnp.full((*kv.slot_pos.shape[:2], pad), -1, jnp.int32)
    ks = vs = None
    if kv.quantized:
        zs = jnp.zeros((*kv.k_scale.shape[:2], pad, *kv.k_scale.shape[3:]),
                       kv.k_scale.dtype)
        ks = jnp.concatenate([kv.k_scale, zs], axis=2)
        vs = jnp.concatenate([kv.v_scale, jnp.copy(zs)], axis=2)
    return dataclasses.replace(cache, kv=KVCache(
        k=jnp.concatenate([kv.k, zk], axis=2),
        v=jnp.concatenate([kv.v, jnp.copy(zk)], axis=2),
        slot_pos=jnp.concatenate([kv.slot_pos, sp], axis=2),
        length=kv.length, k_scale=ks, v_scale=vs, window=kv.window,
    ))
