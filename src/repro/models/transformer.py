"""Decoder / encoder / VLM transformer (dense and MoE) with scan-over-layers,
remat, GQA attention and the M4BRAM QuantizedLinear at every projection.

One implementation serves six assigned archs:
  dense   : nemotron-4-15b/340b, olmo-1b, stablelm-12b
  vlm     : paligemma-3b (stub patch frontend, prefix-LM masking)
  encoder : hubert-xlarge (bidirectional, frame-stub frontend, class head)
  moe     : mixtral-8x22b (+SWA), llama4-maverick (top-1, 128 experts)
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common as cm
from repro.models import moe as moe_mod
from repro.models.kv_cache import (
    DecodeCache,
    KVCache,
    PagedKVCache,
    cache_write,
    full_slot_pos,
    paged_cache_write,
    paged_gather,
)
from repro.parallel.sharding import constrain


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# Free cache slots appended after prefill for subsequently decoded tokens
# (serving engines re-prefill/rebatch past this; the dry-run decode cell
# allocates seq_len + headroom the same way via init_cache).
DECODE_HEADROOM = 8


# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------


def init_block(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 8)
    d, hd = cfg.d_model, cfg.head_dim
    dt = _dtype(cfg)
    p = {
        "ln1": cm.norm_init(cfg.norm, d, dt),
        "ln2": cm.norm_init(cfg.norm, d, dt),
        "wq": cm.dense_init(ks[0], d, cfg.n_heads * hd, dt),
        "wk": cm.dense_init(ks[1], d, cfg.n_kv_heads * hd, dt),
        "wv": cm.dense_init(ks[2], d, cfg.n_kv_heads * hd, dt),
        "wo": cm.dense_init(ks[3], cfg.n_heads * hd, d, dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dt)
        p["k_norm"] = jnp.zeros((hd,), dt)
    if cfg.moe_experts:
        p["moe"] = moe_mod.init_moe(ks[4], cfg)
    else:
        p["ffn"] = cm.ffn_init(ks[5], cfg, d, cfg.d_ff, dt)
    return p


def init_params(key, cfg: ModelConfig) -> dict:
    keys = jax.random.split(key, 4)
    dt = _dtype(cfg)
    layer_keys = jax.random.split(keys[0], cfg.num_layers)
    blocks = jax.vmap(lambda k: init_block(k, cfg))(layer_keys)
    params = {
        "embed": cm.embed_init(keys[1], cfg.vocab, cfg.d_model, dt),
        "blocks": blocks,
        "final_norm": cm.norm_init(cfg.norm, cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        params["head"] = cm.dense_init(keys[2], cfg.d_model, cfg.vocab, dt)
    if cfg.frontend == "patch_stub":
        params["patch_proj"] = cm.dense_init(keys[3], cfg.frontend_dim, cfg.d_model, dt)
    elif cfg.frontend == "frame_stub":
        params["frame_proj"] = cm.dense_init(keys[3], cfg.frontend_dim, cfg.d_model, dt)
    return params


# --------------------------------------------------------------------------
# Block apply (shared across train / prefill / decode)
# --------------------------------------------------------------------------


def _attention_qkv(p, cfg: ModelConfig, x, positions):
    q_cfg, qm = cfg.quant, ("fake" if cfg.quant else "none")
    B, T, _ = x.shape
    hd = cfg.head_dim
    q = cm.linear(x, p["wq"], q_cfg, qm).reshape(B, T, cfg.n_heads, hd)
    k = cm.linear(x, p["wk"], q_cfg, qm).reshape(B, T, cfg.n_kv_heads, hd)
    v = cm.linear(x, p["wv"], q_cfg, qm).reshape(B, T, cfg.n_kv_heads, hd)
    with jax.named_scope("attention"):
        if cfg.qk_norm:
            q = cm.rms_head_norm(q, p["q_norm"])
            k = cm.rms_head_norm(k, p["k_norm"])
        q = cm.rope(q, positions, cfg.rope_theta)
        k = cm.rope(k, positions, cfg.rope_theta)
    from repro.parallel.sharding import axis_size

    if cfg.attn_shard == "heads" and cfg.n_heads % max(axis_size("model"), 1) == 0:
        # TP attention: heads sharded over `model`.
        q = constrain(q, "batch", None, "model", None)
        k = constrain(k, "batch", None, "model", None)
        v = constrain(v, "batch", None, "model", None)
    else:
        # Sequence-parallel attention (heads don't divide the model axis,
        # e.g. llama4's 40 or paligemma's 8 heads on a 16-way mesh): shard
        # the query sequence dim instead — scores/softmax stay 16-way
        # sharded, k/v are gathered once per layer. See EXPERIMENTS §Perf B.
        q = constrain(q, "batch", "model", None, None)
        k = constrain(k, "batch", None, None, None)
        v = constrain(v, "batch", None, None, None)
    return q, k, v


def _kv_attn_view(k, v, kv_quant_attn: bool):
    """The K/V values attention actually reads. For an int8 KV cache the
    prefill reads its own K/V *through the quantizer* (quantize →
    dequantize), so attending over codes later gathered from the cache —
    the cross-request prefix-cache admission path — is bit-identical to
    attending over the in-flight prefill K/V: both sides see exactly
    `dequantize_kv(quantize_kv(kv))`, and quantization is deterministic
    per (token, head). Without kv_cache_quant this is the identity."""
    if not kv_quant_attn:
        return k, v
    from repro.models.kv_cache import dequantize_kv, quantize_kv

    return dequantize_kv(*quantize_kv(k)), dequantize_kv(*quantize_kv(v))


def block_apply(
    p: dict,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    mask: cm.AttnMask,
    kv_quant_attn: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Full-sequence block (train / prefill). Returns (x, k, v, aux_loss).

    `kv_quant_attn` (prefill with an int8 KV cache only) makes attention
    read K/V through the quantizer — see `_kv_attn_view`; the returned
    k/v stay unquantized (the cache quantizes them once, at the end of
    prefill, with the same deterministic `quantize_kv`)."""
    h = cm.apply_norm(x, p["ln1"], cfg.norm)
    q, k, v = _attention_qkv(p, cfg, h, positions)
    k_att, v_att = _kv_attn_view(k, v, kv_quant_attn)
    attn = cm.chunked_attention(
        q, k_att, v_att, mask, softcap=cfg.attn_logit_softcap,
        q_chunk=min(cfg.attn_q_chunk, q.shape[1]),
        kv_chunk=min(cfg.attn_kv_chunk, k.shape[1]),
    )
    x, aux = _block_post_attn_seq(p, cfg, x, attn)
    return x, k, v, aux


def _block_post_attn_seq(p: dict, cfg: ModelConfig, x, attn):
    """Full-sequence post-attention tail (output projection + FFN/MoE
    residual), shared by `block_apply` and `prefill_suffix` — one copy so
    the warm (suffix) path can never drift from the cold path. Returns
    (x, aux_loss)."""
    attn = attn.reshape(*x.shape[:2], cfg.n_heads * cfg.head_dim)
    x = x + cm.linear(attn, p["wo"], cfg.quant, "fake" if cfg.quant else "none")
    h2 = cm.apply_norm(x, p["ln2"], cfg.norm)
    if cfg.moe_experts:
        y, aux = moe_mod.moe_apply_shardmap(p["moe"], h2, cfg)
    else:
        y, aux = cm.ffn_apply(p["ffn"], h2, cfg), jnp.zeros((), jnp.float32)
    x = x + y
    x = constrain(x, "batch", None, None)
    return x, aux


def block_decode(
    p: dict,
    cfg: ModelConfig,
    x: jax.Array,            # (B, 1, d)
    pos: jax.Array,          # (B,) per-slot positions
    k_cache, v_cache, slot_pos, k_scale=None, v_scale=None,
):
    """Single-token block against a (ring) cache. Every batch slot decodes
    at its own position. Returns x + new cache."""
    h = cm.apply_norm(x, p["ln1"], cfg.norm)
    positions = pos[:, None]                      # (B, 1)
    q, k, v = _attention_qkv(p, cfg, h, positions)
    if cfg.kv_cache_quant:
        from repro.models.kv_cache import quantize_kv, row_write, write_slot

        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        k_cache, v_cache, slot_pos = cache_write(
            k_cache, v_cache, slot_pos, kq, vq, pos, cfg.attn_window
        )
        slot = write_slot(pos, k_cache.shape[1], cfg.attn_window)
        k_scale = row_write(k_scale, ks, slot)
        v_scale = row_write(v_scale, vs, slot)
    else:
        k_cache, v_cache, slot_pos = cache_write(
            k_cache, v_cache, slot_pos, k, v, pos, cfg.attn_window
        )
        k_scale = v_scale = None  # ignore dummy scan placeholders
    with jax.named_scope("attention"):
        attn = cm.decode_attention(
            q, k_cache, v_cache, slot_pos, pos,
            window=cfg.attn_window, softcap=cfg.attn_logit_softcap,
            k_scale=k_scale, v_scale=v_scale,
        )
    x = _block_post_attn(p, cfg, x, attn)
    return x, k_cache, v_cache, slot_pos, k_scale, v_scale


def _block_post_attn(p: dict, cfg: ModelConfig, x, attn):
    """Shared decode tail: output projection + FFN/MoE residual."""
    attn = attn.reshape(x.shape[0], 1, cfg.n_heads * cfg.head_dim)
    x = x + cm.linear(attn, p["wo"], cfg.quant, "fake" if cfg.quant else "none")
    h2 = cm.apply_norm(x, p["ln2"], cfg.norm)
    if cfg.moe_experts:
        y, _ = moe_mod.moe_apply_shardmap(p["moe"], h2, cfg)
    else:
        y = cm.ffn_apply(p["ffn"], h2, cfg)
    return x + y


def block_decode_paged(
    p: dict,
    cfg: ModelConfig,
    x: jax.Array,            # (B, 1, d)
    pos: jax.Array,          # (B,) per-slot positions
    pool,                    # (k, v, k_scale, v_scale) stacked pool planes
    block_table,             # (B, max_blocks)
    block_size: int,
    layer,                   # () int32 — this block's pool layer
    fused: bool = True,
    gather_blocks: Optional[int] = None,
):
    """Single-token block against layer `layer` of the stacked paged
    pool: write the new k/v into pos's (block, offset) of that layer in
    place — quantizing on the way in when the pool is int8 (scale planes
    not None) — then attend through the fused `ops.paged_attention`
    kernel, which reads that layer of the stacked planes as stored,
    resolves the block table inside the kernel and never materializes a
    contiguous copy of the pool. Returns (x, pool) with the updated
    planes.

    `fused=False` keeps the original gather-then-attend composition
    (`paged_gather` → `decode_attention`, value/position layout identical
    to the contiguous cache) as the reference path for bit-exactness
    tests; `gather_blocks` clamps its gather to a host-known live-block
    bound."""
    h = cm.apply_norm(x, p["ln1"], cfg.norm)
    q, k, v = _attention_qkv(p, cfg, h, pos[:, None])
    pool_k, pool_v, k_scale, v_scale = paged_cache_write(
        *pool[:2], block_table, k, v, pos, block_size,
        k_scale=pool[2], v_scale=pool[3], layer=layer,
    )
    with jax.named_scope("attention"):
        if fused:
            from repro.kernels import ops

            attn = ops.paged_attention(
                q, pool_k, pool_v, block_table, pos, layer=layer,
                k_scale=k_scale, v_scale=v_scale,
                softcap=cfg.attn_logit_softcap,
            )
        else:
            k_rows, v_rows, kpos, ks_rows, vs_rows = paged_gather(
                pool_k, pool_v, block_table, k_scale, v_scale,
                max_blocks=gather_blocks, layer=layer, n_kv=cfg.n_kv_heads,
            )
            attn = cm.decode_attention(
                q, k_rows, v_rows, kpos, pos, softcap=cfg.attn_logit_softcap,
                k_scale=ks_rows, v_scale=vs_rows,
            )
    return (_block_post_attn(p, cfg, x, attn),
            (pool_k, pool_v, k_scale, v_scale))


def _pool(kv: PagedKVCache):
    """The stacked pool planes a layer scan carries: (k, v, k_scale,
    v_scale), the scales None for a bf16 pool."""
    return kv.k, kv.v, kv.k_scale, kv.v_scale


def _layer_ids(cfg: ModelConfig) -> jax.Array:
    """The layer index each scan step hands its pool kernels."""
    return jnp.arange(cfg.num_layers, dtype=jnp.int32)


# --------------------------------------------------------------------------
# Embedding / inputs
# --------------------------------------------------------------------------


def _embed_scale(cfg: ModelConfig) -> bool:
    """Whether token embeddings are scaled by sqrt(d_model) at lookup —
    one rule for every path (train/prefill/suffix-prefill/decode); the
    warm ≡ cold bit-identity contract depends on these agreeing."""
    return cfg.family in ("vlm",) or cfg.name.startswith("recurrentgemma")


def embed_inputs(params, cfg: ModelConfig, batch) -> Tuple[jax.Array, jax.Array]:
    """Returns (x (B, T, d), positions (B, T)) handling frontend stubs."""
    scale = _embed_scale(cfg)
    if cfg.frontend == "patch_stub":
        patches = batch["patches"].astype(_dtype(cfg))  # (B, P, frontend_dim)
        pe = cm.linear(patches, params["patch_proj"])
        te = cm.embed_lookup(params["embed"], batch["tokens"], scale=scale)
        x = jnp.concatenate([pe, te], axis=1)
    elif cfg.frontend == "frame_stub":
        frames = batch["frames"].astype(_dtype(cfg))    # (B, T, frontend_dim)
        x = cm.linear(frames, params["frame_proj"])
    else:
        x = cm.embed_lookup(params["embed"], batch["tokens"], scale=scale)
    B, T = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    x = constrain(x, "batch", None, None)
    return x, positions


def _mask_for(cfg: ModelConfig) -> cm.AttnMask:
    return cm.AttnMask(
        causal=cfg.causal,
        window=cfg.attn_window,
        prefix_len=cfg.num_prefix_embeds if cfg.family == "vlm" else 0,
    )


# --------------------------------------------------------------------------
# Forward passes
# --------------------------------------------------------------------------


def _scan_blocks(params, cfg, x, positions, mask, collect_kv: bool,
                 kv_quant_attn: bool = False):
    def body(carry, block_p):
        xc, aux = carry
        xn, k, v, a = block_apply(block_p, cfg, xc, positions, mask,
                                  kv_quant_attn)
        out = (k, v) if collect_kv else None
        return (xn, aux + a), out

    body_fn = jax.checkpoint(body) if cfg.remat else body
    if cfg.scan_layers:
        (x, aux), kv = jax.lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)),
                                    params["blocks"])
    else:
        aux = jnp.zeros((), jnp.float32)
        kvs = []
        L = jax.tree_util.tree_leaves(params["blocks"])[0].shape[0]
        for i in range(L):
            block_p = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            (x, aux), out = body_fn((x, aux), block_p)
            kvs.append(out)
        kv = (
            tuple(jnp.stack([o[j] for o in kvs]) for j in range(2))
            if collect_kv else None
        )
    return x, aux, kv


def forward_hidden(params, cfg: ModelConfig, batch):
    x, positions = embed_inputs(params, cfg, batch)
    x, aux, _ = _scan_blocks(params, cfg, x, positions, _mask_for(cfg), False)
    return cm.apply_norm(x, params["final_norm"], cfg.norm), aux


def _embed(params, cfg: ModelConfig, tokens):
    """Token embeddings of the decode-path programs, in the ``embed``
    device scope."""
    with jax.named_scope("embed"):
        return cm.embed_lookup(params["embed"], tokens,
                               scale=_embed_scale(cfg))


def _head(params, cfg: ModelConfig, x):
    """Final norm and logits of the decode-path programs, in the ``head``
    device scope."""
    with jax.named_scope("head"):
        return compute_logits(params, cfg,
                              cm.apply_norm(x, params["final_norm"], cfg.norm))


def compute_logits(params, cfg: ModelConfig, hidden):
    if cfg.tie_embeddings:
        logits = cm.logits_head(hidden, params["embed"],
                                softcap=cfg.logits_softcap, transpose=True)
    else:
        logits = cm.logits_head(hidden, params["head"], softcap=cfg.logits_softcap)
    return constrain(logits, "batch", None, "model")


def train_loss(params, cfg: ModelConfig, batch) -> Tuple[jax.Array, dict]:
    hidden, aux = forward_hidden(params, cfg, batch)
    logits = compute_logits(params, cfg, hidden)
    if cfg.family == "encoder":
        labels = batch["labels"]
        loss = cm.cross_entropy(logits, labels).mean()
    elif cfg.family == "vlm":
        P = cfg.num_prefix_embeds
        text_logits = logits[:, P:-1]
        labels = batch["tokens"][:, 1:]
        loss = cm.cross_entropy(text_logits, labels).mean()
    else:
        loss = cm.cross_entropy(logits[:, :-1], batch["tokens"][:, 1:]).mean()
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux_loss": aux}


# --------------------------------------------------------------------------
# Serving: prefill + decode
# --------------------------------------------------------------------------


def prefill(params, cfg: ModelConfig, batch) -> Tuple[DecodeCache, jax.Array]:
    """Full-sequence forward; returns a DecodeCache and last-token logits.

    ``batch["lengths"]`` (B,) marks right-padded serving prompts: row b's
    real tokens sit at positions 0..lengths[b]-1 and trailing pad slots are
    excluded from the cache (slot_pos = -1) and from the returned logits,
    so a prompt bucketed up to any length prefills bit-identically to an
    exact-length prefill (causal attention never looks at trailing pads)."""
    x, positions = embed_inputs(params, cfg, batch)
    B, S = x.shape[:2]
    lengths = batch.get("lengths")
    if lengths is not None:
        lengths = jnp.asarray(lengths, jnp.int32)
    x, _, kv = _scan_blocks(params, cfg, x, positions, _mask_for(cfg), True,
                            kv_quant_attn=cfg.kv_cache_quant)
    k_all, v_all = kv  # (L, B, S, NKV, H)
    w = cfg.attn_window
    if w:
        from repro.models.kv_cache import ring_align

        k_all, v_all, slot_pos = ring_align(k_all, v_all, lengths, w)
    else:
        # Full cache: leave headroom slots for tokens decoded next.
        pad = DECODE_HEADROOM
        zk = jnp.zeros((*k_all.shape[:2], pad, *k_all.shape[3:]), k_all.dtype)
        k_all = jnp.concatenate([k_all, zk], axis=2)
        v_all = jnp.concatenate([v_all, zk], axis=2)
        slot_pos = full_slot_pos(cfg.num_layers, B, S + pad,
                                 jnp.full((B,), S, jnp.int32)
                                 if lengths is None else lengths)
    if cfg.kv_cache_quant:
        from repro.models.kv_cache import quantize_kv

        k_all, k_scale = quantize_kv(k_all)
        v_all, v_scale = quantize_kv(v_all)
    else:
        k_all = k_all.astype(_dtype(cfg))
        v_all = v_all.astype(_dtype(cfg))
        k_scale = v_scale = None
    length = jnp.full((B,), S, jnp.int32) if lengths is None else lengths
    kvc = KVCache(
        k=k_all,
        v=v_all,
        slot_pos=slot_pos,
        length=length,
        k_scale=k_scale,
        v_scale=v_scale,
        window=w,
    )
    logits = _head(params, cfg, cm.last_token_slice(x, lengths))
    return DecodeCache(pos=length, kv=kvc), logits


def prefill_suffix(params, cfg: ModelConfig, batch):
    """Prefill only the *uncached tail* of a prompt against prefix K/V
    already resident in the paged block pool — the compute half of the
    cross-request prefix cache (the memory half is block sharing in the
    scheduler's allocator). Each layer gathers its prefix K/V straight
    from the pool blocks, the suffix computes q/k/v at its true absolute
    positions, and attention runs over ``[prefix KV ++ suffix KV]`` with
    explicit key positions — per-row math identical to the cold full
    prefill, so a prefix-hit request's tokens are bit-identical to a cold
    request's (int8 pools included: both sides read K/V through
    `dequantize_kv`/`quantize_kv`, see `_kv_attn_view`).

    ``batch`` keys:
      tokens (1, Ls)        right-padded suffix token ids
      lengths (1,)          real suffix length
      start ()              absolute position of the first suffix token ==
                            number of prefix positions resident in the pool
      pool_k / pool_v       (L, num_blocks, bs, NKV·H) pool planes
      prefix_blocks (mb,)   the row's pool blocks covering positions
                            [0, start) in virtual-block order; -1 entries
                            gather the trash block and are masked out
      pool_k_scale / pool_v_scale   int8-pool scale rows (quantized only)

    Returns ``(DecodeCache, logits)``; the solo cache holds ONLY the
    suffix: cache slot ``t`` ↔ absolute position ``start + t`` (see
    `kv_cache.scatter_suffix_into_paged`), and ``pos``/``length`` carry
    the full row length ``start + lengths``."""
    if cfg.attn_window:
        raise ValueError("prefix caching requires a full-attention cache")
    tokens = batch["tokens"]
    B, Ls = tokens.shape
    lengths = jnp.asarray(batch["lengths"], jnp.int32)
    start = jnp.asarray(batch["start"], jnp.int32)
    pool_k, pool_v = batch["pool_k"], batch["pool_v"]
    blocks = jnp.asarray(batch["prefix_blocks"], jnp.int32)
    L = cfg.num_layers
    bs = pool_k.shape[2]
    P = blocks.shape[0] * bs
    quant = cfg.kv_cache_quant

    from repro.models.kv_cache import dequantize_kv, quantize_kv, scale_slots

    tbl = jnp.maximum(blocks, 0)
    pk = pool_k[:, tbl].reshape(L, P, cfg.n_kv_heads, cfg.head_dim)
    pv = pool_v[:, tbl].reshape(L, P, cfg.n_kv_heads, cfg.head_dim)
    if quant:
        ksc = scale_slots(batch["pool_k_scale"][:, tbl], bs).reshape(
            L, P, cfg.n_kv_heads, 1)
        vsc = scale_slots(batch["pool_v_scale"][:, tbl], bs).reshape(
            L, P, cfg.n_kv_heads, 1)
        pk = dequantize_kv(pk, ksc)
        pv = dequantize_kv(pv, vsc)

    ppos = jnp.arange(P, dtype=jnp.int32)
    prefix_kpos = jnp.where(ppos < start, ppos, -1)
    spos = start + jnp.arange(Ls, dtype=jnp.int32)
    suffix_kpos = jnp.where(jnp.arange(Ls) < lengths[0], spos, -1)
    kpos_cat = jnp.concatenate([prefix_kpos, suffix_kpos])
    positions = jnp.broadcast_to(spos[None], (B, Ls))
    mask = cm.AttnMask(causal=cfg.causal)

    x = _embed(params, cfg, tokens)
    x = constrain(x, "batch", None, None)

    def body(xc, layer_in):
        block_p, pk_l, pv_l = layer_in
        h = cm.apply_norm(xc, block_p["ln1"], cfg.norm)
        q, k, v = _attention_qkv(block_p, cfg, h, positions)
        k_att, v_att = _kv_attn_view(k, v, quant)
        k_cat = jnp.concatenate([pk_l[None].astype(k_att.dtype), k_att], axis=1)
        v_cat = jnp.concatenate([pv_l[None].astype(v_att.dtype), v_att], axis=1)
        attn = cm.chunked_attention(
            q, k_cat, v_cat, mask, q_offset=start, kpos=kpos_cat,
            softcap=cfg.attn_logit_softcap,
            q_chunk=min(cfg.attn_q_chunk, Ls),
            kv_chunk=min(cfg.attn_kv_chunk, P + Ls),
        )
        xn, _ = _block_post_attn_seq(block_p, cfg, xc, attn)
        return xn, (k, v)

    x, (k_all, v_all) = jax.lax.scan(body, x, (params["blocks"], pk, pv))
    if quant:
        k_all, k_scale = quantize_kv(k_all)
        v_all, v_scale = quantize_kv(v_all)
    else:
        k_all = k_all.astype(_dtype(cfg))
        v_all = v_all.astype(_dtype(cfg))
        k_scale = v_scale = None
    total = start + lengths
    kvc = KVCache(
        k=k_all, v=v_all,
        slot_pos=jnp.broadcast_to(suffix_kpos[None, None], (L, B, Ls)),
        length=total, k_scale=k_scale, v_scale=v_scale, window=0,
    )
    logits = _head(params, cfg, cm.last_token_slice(x, lengths))
    return DecodeCache(pos=total, kv=kvc), logits


def prefill_chunk(params, cfg: ModelConfig, cache: DecodeCache, batch,
                  all_logits: bool = False):
    """Prefill one token *chunk* of a single row's prompt directly against
    the shared paged pool — the decode-path model method behind
    Sarathi-style chunked prefill. Each layer runs the fused
    ``ops.paged_prefill`` kernel: the chunk attends causally over
    ``[pool-resident prefix ++ chunk]`` with prefix blocks streamed
    through the row's block table, and the chunk's K/V lands in its
    destination pool blocks from the kernel epilogue (quantize-on-write
    for int8 pools). No contiguous prefix copy is ever materialized and
    no post-prefill scatter runs — admission becomes a sequence of these
    calls, interleaved with decode steps by the scheduler.

    ``batch`` keys:
      tokens (1, Lc)   right-padded chunk token ids
      lengths (1,)     real chunk length (<= Lc)
      start ()         absolute position of the chunk's first token; the
                       positions [0, start) are already pool-resident —
                       either a shared warm prefix or this row's earlier
                       chunks (byte-identical by the quantize-on-write
                       contract, so the kernel can't tell them apart)
      slot ()          the row's batch slot in `cache`
      blocks (nbp,)    the row's pool blocks covering positions
                       [0, start + lengths[0]) in virtual-block order;
                       -1 entries are dead (trash-block remapped)

    Returns ``(cache, logits (1, 1, V))`` — the pool planes updated in
    place, ``cache.pos``/``kv.length`` advanced to ``start + lengths[0]``
    at ``slot``, and logits for the chunk's last real token (only the
    final chunk's logits are meaningful: they sample the first output
    token). Chunk boundaries never change the math — attention depends
    only on absolute positions and pool bytes — so any chunk split of a
    prompt is bit-identical to the whole-prompt prefill.

    ``all_logits=True`` returns logits for every chunk position
    ``(1, Lc, V)`` instead of the last real token — the speculative-decode
    *verify* shape, where every position's argmax is compared against the
    draft (see :func:`prefill_chunk_logits`). Positions past ``lengths[0]``
    are padding; their logits are meaningless and must be ignored."""
    if cfg.attn_window:
        raise ValueError("chunked prefill requires a full-attention "
                         f"paged cache (attn_window={cfg.attn_window})")
    tokens = batch["tokens"]
    B, Lc = tokens.shape
    lengths = jnp.asarray(batch["lengths"], jnp.int32)
    start = jnp.asarray(batch["start"], jnp.int32)
    slot = jnp.asarray(batch["slot"], jnp.int32)
    blocks = jnp.asarray(batch["blocks"], jnp.int32)
    kv: PagedKVCache = cache.kv
    length = lengths[0]

    spos = start + jnp.arange(Lc, dtype=jnp.int32)
    positions = jnp.broadcast_to(spos[None], (B, Lc))
    x = _embed(params, cfg, tokens)
    x = constrain(x, "batch", None, None)
    x, (k, v, ks, vs) = _chunk_layers(params, cfg, x, positions, _pool(kv),
                                      blocks, start, length)
    logits = _head(params, cfg,
                   x if all_logits else cm.last_token_slice(x, lengths))
    total = start + length
    new_cache = DecodeCache(
        pos=cache.pos.at[slot].set(total),
        kv=PagedKVCache(k=k, v=v, block_table=kv.block_table,
                        length=kv.length.at[slot].set(total),
                        k_scale=ks, v_scale=vs, block_size=kv.block_size),
    )
    return new_cache, logits


def _chunk_layers(params, cfg: ModelConfig, x, positions, pool, blocks,
                  start, length):
    """The layer scan of one chunk row: each layer attends and writes
    through the fused ``ops.paged_prefill`` kernel on its layer of the
    stacked pool planes, which the scan carries (never slices) and the
    kernel updates in place. Shared by :func:`prefill_chunk` and the
    verify rows of :func:`prefill_chunk_logits_multi`. Returns
    ``(x, pool)``."""
    from repro.kernels import ops

    def body(carry, layer_in):
        xc, (pk, pv, ks, vs) = carry
        block_p, layer = layer_in
        h = cm.apply_norm(xc, block_p["ln1"], cfg.norm)
        q, k, v = _attention_qkv(block_p, cfg, h, positions)
        with jax.named_scope("attention"):
            attn, pk, pv, ks, vs = ops.paged_prefill(
                q, k, v, pk, pv, blocks, start, length, layer=layer,
                k_scale=ks, v_scale=vs, softcap=cfg.attn_logit_softcap,
            )
        xn, _ = _block_post_attn_seq(block_p, cfg, xc, attn)
        return (xn, (pk, pv, ks, vs)), None

    (x, pool), _ = jax.lax.scan(body, (x, pool),
                                (params["blocks"], _layer_ids(cfg)))
    return x, pool


def prefill_chunk_logits(params, cfg: ModelConfig, cache: DecodeCache, batch):
    """Speculative-decode verify step: :func:`prefill_chunk` returning
    logits for *every* chunk position ``(1, Lc, V)``.

    The verify call is shaped exactly like a prefill chunk over
    ``[current token, draft tokens]``: each position attends over the
    row's pool-resident history plus the earlier chunk positions, and the
    chunk K/V (recomputed at the *full* policy) overwrites the draft's
    speculative pool writes — K/V projections are per-token functions of
    (embedding, rope position), so the verified pool bytes are identical
    to what plain decode would have written. Position i's argmax is the
    token greedy decode would emit after accepting the first i chunk
    tokens, which is what the acceptance rule compares against."""
    return prefill_chunk(params, cfg, cache, batch, all_logits=True)


def prefill_chunk_logits_multi(params, cfg: ModelConfig, cache: DecodeCache,
                               batch):
    """Batched speculative-verify: R independent chunk rows through ONE
    call — :func:`prefill_chunk_logits` per row, stacked. The scheduler
    verifies a whole tier group's speculation windows in one dispatch
    instead of one call per slot (fixed ``R = max_batch`` rows keeps one
    compiled signature per bucketed block count, exactly like the decode
    step's fixed batch).

    ``batch`` keys (all leading-R where the single-row call is scalar):
      tokens (R, Lc)    right-padded chunk token ids per row
      lengths (R,)      real chunk length per row (0 for dead rows)
      starts (R,)       absolute position of each row's first chunk token
      slots (R,)        each row's batch slot in `cache`; -1 marks a DEAD
                        row (slot not verifying this call)
      blocks (R, nbp)   each row's pool blocks; dead rows pass all -1

    Dead rows are inert by construction: an all--1 block table routes
    their K/V writes to the trash block (``paged_chunk_write`` remaps
    invalid positions to block 0) and masks every attention key (the
    kernel's online softmax over fully-masked blocks is a guarded no-op,
    the reference zeroes the probabilities exactly), their ``pos``/
    ``length`` entries are untouched (`slots` < 0 gates the update), and
    their logits rows are garbage the caller ignores.

    Rows are computed by an outer ``lax.scan`` carrying the pool planes:
    each row attends only through its own block table (its own blocks
    plus read-only shared prefix blocks) and writes only its own
    destination blocks, so row order cannot change any row's math — each
    row's logits are bitwise what its solo :func:`prefill_chunk_logits`
    call would return. Returns ``(cache, logits (R, Lc, V))``."""
    if cfg.attn_window:
        raise ValueError("chunked prefill requires a full-attention "
                         f"paged cache (attn_window={cfg.attn_window})")
    tokens = batch["tokens"]
    R, Lc = tokens.shape
    lengths = jnp.asarray(batch["lengths"], jnp.int32)
    starts = jnp.asarray(batch["starts"], jnp.int32)
    slots = jnp.asarray(batch["slots"], jnp.int32)
    blocks = jnp.asarray(batch["blocks"], jnp.int32)
    kv: PagedKVCache = cache.kv

    def row(carry, row_in):
        pool, pos, lng = carry
        toks_r, len_r, start_r, slot_r, blocks_r = row_in
        positions = (start_r + jnp.arange(Lc, dtype=jnp.int32))[None]
        x = _embed(params, cfg, toks_r[None])
        x = constrain(x, "batch", None, None)
        x, pool = _chunk_layers(params, cfg, x, positions, pool, blocks_r,
                                start_r, len_r)
        logits = _head(params, cfg, x)
        total = start_r + len_r
        sc = jnp.maximum(slot_r, 0)      # .at[-1] would wrap — clamp + gate
        live = slot_r >= 0
        pos = pos.at[sc].set(jnp.where(live, total, pos[sc]))
        lng = lng.at[sc].set(jnp.where(live, total, lng[sc]))
        return (pool, pos, lng), logits[0]

    carry = (_pool(kv), cache.pos, kv.length)
    ((k, v, ks, vs), pos, lng), logits = jax.lax.scan(
        row, carry, (tokens, lengths, starts, slots, blocks)
    )
    new_cache = DecodeCache(
        pos=pos,
        kv=PagedKVCache(k=k, v=v, block_table=kv.block_table, length=lng,
                        k_scale=ks, v_scale=vs, block_size=kv.block_size),
    )
    return new_cache, logits


def decode_step(params, cfg: ModelConfig, cache: DecodeCache, tokens: jax.Array,
                paged_fused: bool = True,
                gather_blocks: Optional[int] = None):
    """tokens: (B, 1) → (new_cache, logits (B, 1, V)). cache.pos is (B,):
    each slot decodes at its own position (continuous batching). Dispatches
    on the cache flavour: contiguous KVCache or block-table PagedKVCache
    (fused paged-attention kernel by default; `paged_fused=False` runs the
    gather-then-attend reference, optionally clamped to `gather_blocks`)."""
    if isinstance(cache.kv, PagedKVCache):
        return _decode_step_paged(params, cfg, cache, tokens,
                                  fused=paged_fused,
                                  gather_blocks=gather_blocks)
    x = _embed(params, cfg, tokens)
    x = constrain(x, "batch", None, None)
    pos = cache.pos

    quant = cache.kv.quantized

    def body(xc, layer_in):
        block_p, kc, vc, sp, ks, vs = layer_in
        xn, kc, vc, sp, ks, vs = block_decode(
            block_p, cfg, xc, pos, kc, vc, sp, ks, vs
        )
        return xn, (kc, vc, sp, ks, vs)

    L = cfg.num_layers
    ks_in = cache.kv.k_scale if quant else jnp.zeros((L, 0))
    vs_in = cache.kv.v_scale if quant else jnp.zeros((L, 0))
    x, (k_new, v_new, sp_new, ks_new, vs_new) = jax.lax.scan(
        body, x,
        (params["blocks"], cache.kv.k, cache.kv.v, cache.kv.slot_pos,
         ks_in, vs_in),
    )
    logits = _head(params, cfg, x)
    new_cache = DecodeCache(
        pos=pos + 1,
        kv=KVCache(k=k_new, v=v_new, slot_pos=sp_new,
                   length=cache.kv.length + 1,
                   k_scale=ks_new if quant else None,
                   v_scale=vs_new if quant else None,
                   window=cfg.attn_window),
    )
    return new_cache, logits


def _decode_step_paged(params, cfg: ModelConfig, cache: DecodeCache, tokens,
                       fused: bool = True,
                       gather_blocks: Optional[int] = None):
    """decode_step over the shared block pool: one compiled signature for
    any mix of slot depths and block-table layouts. The scan over layers
    carries the stacked pool planes and each layer writes and attends
    its own layer of them in place. `fused`/`gather_blocks` select the
    fused kernel (default) vs the clamped gather-then-attend reference
    path."""
    x = _embed(params, cfg, tokens)
    x = constrain(x, "batch", None, None)
    pos = cache.pos
    kv: PagedKVCache = cache.kv
    table = kv.block_table

    def body(carry, layer_in):
        xc, pool = carry
        block_p, layer = layer_in
        xn, pool = block_decode_paged(
            block_p, cfg, xc, pos, pool, table, kv.block_size, layer,
            fused=fused, gather_blocks=gather_blocks,
        )
        return (xn, pool), None

    (x, (k, v, ks, vs)), _ = jax.lax.scan(
        body, (x, _pool(kv)), (params["blocks"], _layer_ids(cfg))
    )
    logits = _head(params, cfg, x)
    new_cache = DecodeCache(
        pos=pos + 1,
        kv=PagedKVCache(k=k, v=v, block_table=table,
                        length=kv.length + 1, k_scale=ks, v_scale=vs,
                        block_size=kv.block_size),
    )
    return new_cache, logits


def init_cache(cfg: ModelConfig, batch: int, seq_len: int) -> DecodeCache:
    """Empty cache sized for decoding after `seq_len` tokens of context."""
    kvc = KVCache.init(
        cfg.num_layers, batch, seq_len + DECODE_HEADROOM, cfg.n_kv_heads,
        cfg.head_dim, window=cfg.attn_window, dtype=_dtype(cfg),
        quantized=cfg.kv_cache_quant,
    )
    return DecodeCache(pos=jnp.full((batch,), seq_len, jnp.int32), kv=kvc)


def init_paged_cache(cfg: ModelConfig, batch: int, num_blocks: int,
                     block_size: int, max_blocks: int) -> DecodeCache:
    """Empty paged cache: `num_blocks` pool blocks (block 0 = trash) shared
    by `batch` slots of up to `max_blocks` blocks each. Full causal
    attention only — ring buffers are already window-bounded and stay
    contiguous. With cfg.kv_cache_quant the pool holds int8 codes plus
    per-(slot, head) fp32 scale planes (~2× tokens per pooled byte)."""
    if cfg.attn_window:
        raise ValueError("paged KV cache requires full attention "
                         f"(attn_window={cfg.attn_window})")
    kvc = PagedKVCache.init(
        cfg.num_layers, batch, num_blocks, block_size, max_blocks,
        cfg.n_kv_heads, cfg.head_dim, dtype=_dtype(cfg),
        quantized=cfg.kv_cache_quant,
    )
    return DecodeCache(pos=jnp.zeros((batch,), jnp.int32), kv=kvc)
