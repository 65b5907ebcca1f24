"""Public jit'd wrappers for the Pallas kernels.

This module is the only kernel entry point the rest of the framework uses.
It owns:
  * backend dispatch through :mod:`repro.kernels.registry` — every op takes
    an optional ``backend=`` ("interpret" | "mosaic" | "reference") and
    otherwise uses the registry's active backend (platform default: Mosaic
    on TPU, interpret elsewhere),
  * block-shape selection per operand shape (VMEM budgeting, memoized in
    the registry's plan cache),
  * the packed/mixed-group compositions used by QuantizedLinear — the
    serve path runs the *fused* quantize→bit-plane kernel so activations
    never round-trip through HBM as int8 codes.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import bitplane
from repro.kernels import bitplane_matmul as _bpm
from repro.kernels import fused_matmul as _fused
from repro.kernels import pack_quant as _pq
from repro.kernels import paged_attention as _paged
from repro.kernels import paged_prefill as _paged_pf
from repro.kernels import ref as _ref
from repro.kernels import wkv6 as _wkv6
from repro.kernels.registry import KernelBackend, get_registry, use_backend  # noqa: F401


def pick_matmul_blocks(
    m: int, n: int, k: int, backend: Optional[str] = None
) -> Tuple[int, int, int]:
    """Memoized (bm, bn, bk) for shape (m, n, k) on the given/active backend.

    Large shapes take MXU tiles fitting the ~4 MiB VMEM working-set budget;
    small shapes round up only to the backend's alignment (interpret mode
    tiles at 8, so tiny layers no longer pad N/K up to 128).
    """
    return get_registry().matmul_plan(m, n, k, backend)


def bitplane_matmul(
    x_codes: jax.Array,
    w_codes: jax.Array,
    *,
    a_bits: int = 8,
    act_signed: bool = True,
    plane_bits: int = 2,
    w_plane_lo: int = 0,
    blocks: Optional[Tuple[int, int, int]] = None,
    backend=None,
) -> jax.Array:
    """Exact int matmul of activation codes × weight codes via bit planes.

    ``w_plane_lo`` contracts only the top weight planes (the self-
    speculative draft path): plane ``lo`` becomes the LSB plane and the
    caller re-scales dequantization by ``4**w_plane_lo``.
    """
    be = get_registry().resolve(backend)
    if be.is_reference:
        return _ref.bitplane_matmul_ref(x_codes, w_codes, a_bits, act_signed,
                                        w_plane_lo=w_plane_lo,
                                        plane_bits=plane_bits)
    m, k = x_codes.shape
    n = w_codes.shape[1]
    bm, bn, bk = blocks or get_registry().matmul_plan(m, n, k, be)
    return _bpm.bitplane_matmul(
        x_codes,
        w_codes,
        a_bits=a_bits,
        act_signed=act_signed,
        plane_bits=plane_bits,
        w_plane_lo=w_plane_lo,
        bm=bm,
        bn=bn,
        bk=bk,
        interpret=be.interpret,
    )


def quantize_rows(x: jax.Array, *, bits: int = 8, signed: bool = True,
                  backend=None):
    """Fused per-row (per-token) quantization: (M, K) float → int8 codes + scales."""
    be = get_registry().resolve(backend)
    if be.is_reference:
        return _ref.quantize_pack_ref(x.astype(jnp.float32), bits, signed=signed)
    return _pq.quantize_rows(x, bits=bits, signed=signed, interpret=be.interpret)


def fused_quantize_matmul(
    x: jax.Array,
    w_codes: jax.Array,
    *,
    a_bits: int = 8,
    act_signed: bool = True,
    plane_bits: int = 2,
    w_plane_lo: int = 0,
    blocks: Optional[Tuple[int, int, int]] = None,
    backend=None,
):
    """(M, K) float × (K, N) int codes → ((M, N) int32, (M, 1) fp32 scales).

    One kernel: per-row quantization happens in the matmul's K-loop prologue
    with the fp32 rows resident in VMEM — no intermediate int8 activation
    tensor in HBM. Bit-identical to ``quantize_rows → bitplane_matmul``.
    ``w_plane_lo`` contracts only the top weight planes (draft-policy path).
    """
    be = get_registry().resolve(backend)
    if be.is_reference:
        q, s = _ref.quantize_pack_ref(x.astype(jnp.float32), a_bits,
                                      signed=act_signed)
        return _ref.bitplane_matmul_ref(q, w_codes, a_bits, act_signed,
                                        w_plane_lo=w_plane_lo,
                                        plane_bits=plane_bits), s
    m, k = x.shape
    n = w_codes.shape[1]
    bm, bn, bk = blocks or get_registry().fused_matmul_plan(m, n, k, be)
    return _fused.fused_quantize_matmul(
        x,
        w_codes,
        a_bits=a_bits,
        act_signed=act_signed,
        plane_bits=plane_bits,
        w_plane_lo=w_plane_lo,
        bm=bm,
        bn=bn,
        bk=bk,
        interpret=be.interpret,
    )


def packed_matmul(
    x: jax.Array,
    packed: jax.Array,
    scale: jax.Array,
    *,
    w_bits: int,
    a_bits: int = 8,
    act_signed: bool = True,
    w_plane_lo: int = 0,
    backend=None,
) -> jax.Array:
    """float x (M, K) × packed sub-byte weights ((K·bits/8), N) → float (M, N).

    The end-to-end M4BRAM serving path: unpack weights (VMEM-side layout
    op), then the *fused* quantize→bit-plane kernel (activations quantized
    in the matmul prologue), then dequantize with per-token × per-channel
    scales. ``w_plane_lo`` runs the plane-truncated draft contraction on
    the same packed buffer; the dropped low planes shrink the code range
    by 4^lo, so the weight scale regains that factor here.
    """
    wq = bitplane.unpack_weights(packed, w_bits, axis=0)
    acc, xs = fused_quantize_matmul(
        x.astype(jnp.float32), wq, a_bits=a_bits, act_signed=act_signed,
        w_plane_lo=w_plane_lo, backend=backend,
    )
    ws = scale.reshape(1, -1)
    if w_plane_lo:
        ws = ws * (1 << (2 * w_plane_lo))
    return (acc.astype(jnp.float32) * xs * ws).astype(x.dtype)


def mixed_group_matmul(
    x: jax.Array,
    w8_codes: jax.Array,
    wl_packed: jax.Array,
    scale8: jax.Array,
    scalel: jax.Array,
    *,
    w_bits: int,
    a_bits: int = 8,
    backend=None,
) -> jax.Array:
    """Intra-layer mixed 8b/low-bit group matmul (paper Table III).

    The activation quantization is shared between the groups (one kernel
    pass — which is why this path stays unfused), then each filter group
    runs its own bit-plane matmul — the two groups are the TPU analogue of
    the paper's BPE/DSP heterogeneous split, and XLA schedules them
    back-to-back on the MXU with no interlock.
    """
    xq, xs = quantize_rows(x.astype(jnp.float32), bits=a_bits, signed=True,
                           backend=backend)
    acc8 = bitplane_matmul(xq, w8_codes.astype(jnp.int32), a_bits=a_bits,
                           backend=backend)
    wl = bitplane.unpack_weights(wl_packed, w_bits, axis=0)
    accl = bitplane_matmul(xq, wl, a_bits=a_bits, backend=backend)
    y8 = acc8.astype(jnp.float32) * xs * scale8.reshape(1, -1)
    yl = accl.astype(jnp.float32) * xs * scalel.reshape(1, -1)
    return jnp.concatenate([y8, yl], axis=1).astype(x.dtype)


def flash_attention(
    q: jax.Array,  # (B, T, NQ, H)
    k: jax.Array,  # (B, S, NKV, H)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    bq: int = 128,
    bk: int = 128,
    backend=None,
) -> jax.Array:
    """GQA-aware flash attention: kv heads are broadcast to the q-head
    grid, heads fold into the batch grid dim. Returns (B, T, NQ, H)."""
    be = get_registry().resolve(backend)
    B, T, NQ, H = q.shape
    NKV = k.shape[2]
    G = NQ // NKV
    qf = q.transpose(0, 2, 1, 3).reshape(B * NQ, T, H)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * NQ, -1, H)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * NQ, -1, H)
    if be.is_reference:
        out = _ref.flash_attention_ref(qf, kf, vf, causal, window, q_offset)
    else:
        from repro.kernels import flash_attention as _fa

        out = _fa.flash_attention(
            qf, kf, vf, causal=causal, window=window, q_offset=q_offset,
            bq=bq, bk=bk, interpret=be.interpret,
        )
    return out.reshape(B, NQ, T, H).transpose(0, 2, 1, 3).astype(q.dtype)


def paged_attention(
    q: jax.Array,            # (B, 1, NQ, H) — one new token per row
    pool_k: jax.Array,       # (L, num_blocks, block_size, NKV*H)
    pool_v: jax.Array,
    block_table: jax.Array,  # (B, max_blocks) int32, -1 = unallocated
    q_pos: jax.Array,        # (B,) per-row decode position
    *,
    layer: jax.Array,        # () int32 — the pool layer attended
    k_scale: Optional[jax.Array] = None,  # (L, num_blocks, 1, NKV*bs)
    v_scale: Optional[jax.Array] = None,
    softcap: float = 0.0,
    blocks: Optional[Tuple[int, int, int]] = None,
    backend=None,
) -> jax.Array:
    """Fused flash-decode attention over layer ``layer`` of the stacked
    paged KV pool.

    The pool planes are the whole ``(L, num_blocks, block_size, NKV·H)``
    stack of ``kv_cache.PagedKVCache`` (an int8 pool's scale planes are
    its lane-dense ``(L, num_blocks, 1, NKV·block_size)`` rows): the
    kernel reads layer ``layer`` in place and in the stored layout, so a
    scan over layers can carry the pool instead of slicing it.
    Block-table resolution happens *inside* the kernel (scalar
    prefetch): each grid step streams one live pool block into VMEM and
    folds it into the online softmax — no contiguous gather of the pool
    is ever materialized, per-row HBM traffic is the row's live blocks,
    and an int8 pool (``k_scale``/``v_scale`` rows) dequantizes
    in-kernel. The reference backend runs the gather-then-attend oracle
    (:func:`repro.kernels.ref.paged_attention_ref`), which is the
    bit-exactness specification the kernel is tested against.

    The kernel is ownership-agnostic: multiple rows' tables may map to
    the same pool block (the scheduler's cross-request prefix cache does
    exactly that), since each row only ever reads blocks through its own
    table and positions below its own ``q_pos``.
    """
    be = get_registry().resolve(backend)
    if be.is_reference:
        return _ref.paged_attention_ref(
            q, pool_k, pool_v, block_table, q_pos, layer,
            k_scale=k_scale, v_scale=v_scale, softcap=softcap,
        )
    H = q.shape[3]
    bs, n_kv = pool_k.shape[2], pool_k.shape[3] // H
    bh, _, _ = blocks or get_registry().paged_attention_plan(n_kv, bs, H, be)
    if bh <= 0 or n_kv % bh:
        bh = n_kv  # plans must divide the KV heads; fall back to all
    return _paged.paged_attention(
        q, pool_k, pool_v, block_table, q_pos, layer, k_scale, v_scale,
        softcap=softcap, bh=bh, interpret=be.interpret,
    )


def paged_prefill(
    q: jax.Array,            # (1, Lc, NQ, H) — one row's chunk queries
    k_new: jax.Array,        # (1, Lc, NKV, H) — chunk K/V (unquantized)
    v_new: jax.Array,
    pool_k: jax.Array,       # (L, num_blocks, block_size, NKV*H)
    pool_v: jax.Array,
    blocks: jax.Array,       # (mb,) int32 row block table, -1 = unallocated
    start: jax.Array,        # () int32 chunk token 0's absolute position
    length: jax.Array,       # () int32 real chunk length (<= Lc)
    *,
    layer: jax.Array,        # () int32 — the pool layer attended and written
    k_scale: Optional[jax.Array] = None,  # (L, num_blocks, 1, NKV*bs)
    v_scale: Optional[jax.Array] = None,
    softcap: float = 0.0,
    blocks_plan: Optional[Tuple[int, int, int]] = None,
    backend=None,
):
    """Fused paged chunked-prefill: attend a prompt chunk against
    [pool-resident prefix ++ chunk] causally AND write the chunk's K/V
    into its destination pool blocks, in one kernel, on layer ``layer``
    of the stacked pool (planes laid out as in :func:`paged_attention`).

    The decode kernel's scalar-prefetch/block-table trick applied to the
    prefill grid: resident prefix blocks stream through the table index
    map (no per-layer HBM gather of the prefix), and destination blocks
    are written back through input/output-aliased pool refs from the
    kernel epilogue (no post-prefill scatter round trip, and no copy of
    any other block or layer). int8 pools quantize on write with the
    exact `quantize_kv` math, so the pool bytes are bit-identical to the
    scatter path's.

    Returns (attn (1, Lc, NQ, H) in q's dtype, pool_k, pool_v, k_scale,
    v_scale) — the stacked planes; scales are None passthroughs for a
    bf16 pool. The reference backend runs the scatter-then-gather-attend
    oracle (:func:`repro.kernels.ref.paged_prefill_ref`), the semantic
    spec the kernel is tested against."""
    be = get_registry().resolve(backend)
    if be.is_reference:
        return _ref.paged_prefill_ref(
            q, k_new, v_new, pool_k, pool_v, blocks, start, length, layer,
            k_scale=k_scale, v_scale=v_scale, softcap=softcap,
        )
    H = q.shape[3]
    bs, n_kv = pool_k.shape[2], pool_k.shape[3] // H
    bh, _, _ = blocks_plan or get_registry().paged_prefill_plan(n_kv, bs, H,
                                                                be)
    if bh <= 0 or n_kv % bh:
        bh = n_kv  # plans must divide the KV heads; fall back to all
    return _paged_pf.paged_prefill_attention(
        q, k_new, v_new, pool_k, pool_v, blocks, start, length, layer,
        k_scale, v_scale, softcap=softcap, bh=bh, interpret=be.interpret,
    )


def wkv6(r, k, v, w, u, *, chunk: int = 32, backend=None) -> jax.Array:
    """Chunked RWKV-6 mixer. See repro/kernels/wkv6.py."""
    be = get_registry().resolve(backend)
    if be.is_reference:
        return _ref.wkv6_ref(r, k, v, w, u)
    return _wkv6.wkv6(r, k, v, w, u, chunk=chunk, interpret=be.interpret)


def wkv6_batched(r, k, v, w, u, *, chunk: int = 32, backend=None) -> jax.Array:
    """vmapped-over-batch wkv6: r/k/w (B, T, H, K), v (B, T, H, V)."""
    fn = functools.partial(wkv6, chunk=chunk, backend=backend)
    return jax.vmap(lambda a, b, c, d: fn(a, b, c, d, u))(r, k, v, w)
