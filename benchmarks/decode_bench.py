"""Paged decode-attention benchmark: gather-then-attend vs the fused
paged-attention kernel, bf16 vs int8 pool, swept over context length.

Measures one decode step's attention (single layer) against a paged KV
pool three ways:

  * gather       — `paged_gather` materializes the full contiguous
    (B, max_blocks·bs, NKV, H) copy of every row's table span, then
    `decode_attention` reads it back: the "separate buffer" the fused
    kernel eliminates. Cost scales with `max_blocks`, not live tokens.
  * gather-clamp — the same composition with the gather clamped to the
    host-known live block count (`paged_gather(..., max_blocks=live)`),
    the cheaper surviving reference path.
  * fused        — `ops.paged_attention`: block-table resolution inside
    the Pallas kernel, one pool block streamed per grid step, online
    softmax in VMEM scratch, no materialized copy.

Interpret-mode wall time proves all paths run and tracks their relative
CPU cost; the HBM-traffic model (and its v5e `memory_time_s` projection)
is the TPU-relevant number — the fused path moves ~1/3 the bytes at full
occupancy and the gap widens with context because the gather's staging
copy grows with it.

The int8 section demonstrates the ROADMAP's "paged support for the int8
KV cache": the same pooled byte budget holds ~2× the tokens (int8 codes +
per-(slot, head) fp32 scales vs bf16), verified by serving through an
int8 pool end to end.

Run:  PYTHONPATH=src python -m benchmarks.decode_bench [--quick]
Writes BENCH_paged_attention.json at the repo root.
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

from benchmarks.common import emit, timed


def _pool_case(rng, ctx, *, B, n_kv, group, H, bs, quantized):
    """A fully-occupied paged pool: every row holds ctx live tokens."""
    import jax.numpy as jnp

    from repro.models.kv_cache import merge_heads, quantize_kv, scale_rows

    maxb = ctx // bs
    nb = B * maxb + 1  # + trash block 0
    kf = jnp.asarray(rng.normal(size=(nb, bs, n_kv, H)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(nb, bs, n_kv, H)), jnp.float32)
    if quantized:
        pool_k, k_scale = quantize_kv(kf)
        pool_v, v_scale = quantize_kv(vf)
        # The pool's one-layer stack: scale rows, heads side by side.
        k_scale, v_scale = scale_rows(k_scale)[None], scale_rows(v_scale)[None]
    else:
        pool_k, pool_v = kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16)
        k_scale = v_scale = None
    pool_k, pool_v = merge_heads(pool_k)[None], merge_heads(pool_v)[None]
    table = jnp.arange(1, B * maxb + 1, dtype=jnp.int32).reshape(B, maxb)
    q = jnp.asarray(rng.normal(size=(B, 1, n_kv * group, H)), jnp.bfloat16)
    q_pos = jnp.full((B,), ctx - 1, jnp.int32)
    return q, pool_k, pool_v, table, q_pos, k_scale, v_scale


def _hbm_bytes(span_tokens, *, B, n_kv, H, bs, itemsize, scale_bytes,
               fused):
    """Per-(layer, step) attention HBM traffic model over `span_tokens`
    cache slots per row. The gather path reads every table-mapped pool
    block (trash for unallocated entries), writes the contiguous staging
    copy, and re-reads it in the attention — 3× its span's pool bytes;
    the fused path streams each live block once (its span IS the live
    tokens)."""
    per_tok = n_kv * (2 * H * itemsize + scale_bytes)  # k+v (+scales)
    span = B * span_tokens * per_tok
    return span if fused else 3 * span


def run(quick: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.models.common import decode_attention
    from repro.models.kv_cache import paged_gather
    from repro.roofline.hw import memory_time_s

    B, n_kv, group, H, bs = 2, 2, 2, 64, 16
    ctxs = [64, 128] if quick else [64, 128, 256, 512]
    rng = np.random.default_rng(0)
    rows = []
    results = {}

    def gather_fn(max_blocks):
        def f(q, pk, pv, tbl, pos, ks, vs):
            k_r, v_r, kpos, ks_r, vs_r = paged_gather(
                pk, pv, tbl, ks, vs, max_blocks=max_blocks, layer=0,
                n_kv=n_kv)
            return decode_attention(q, k_r, v_r, kpos, pos,
                                    k_scale=ks_r, v_scale=vs_r)
        return jax.jit(f, static_argnums=())

    fused_fn = jax.jit(lambda q, pk, pv, tbl, pos, ks, vs:
                       ops.paged_attention(q, pk, pv, tbl, pos, layer=0,
                                           k_scale=ks, v_scale=vs,
                                           backend="interpret"))

    for quantized in (False, True):
        dt = "int8" if quantized else "bf16"
        itemsize = 1 if quantized else 2
        scale_bytes = 8 if quantized else 0  # k+v fp32 scale per (slot, head)
        for ctx in ctxs:
            case = _pool_case(rng, ctx, B=B, n_kv=n_kv, group=group, H=H,
                              bs=bs, quantized=quantized)
            # Oversized table span: the pool is provisioned for 2x the live
            # context (the realistic serving shape — tables sized for
            # max_ctx, rows shorter), which is exactly the dead weight the
            # unclamped gather pays for and the fused kernel skips.
            q, pk, pv, tbl, pos, ks, vs = case
            pad_tbl = jnp.concatenate(
                [tbl, jnp.full_like(tbl, -1)], axis=1)
            live_blocks = ctx // bs

            paths = {
                "gather": (gather_fn(None), pad_tbl),
                "gather_clamp": (gather_fn(live_blocks), pad_tbl),
                "fused": (fused_fn, pad_tbl),
            }
            row = {"ctx": ctx, "pool_dtype": dt, "paths": {}}
            outs = {}
            spans = {"gather": 2 * ctx, "gather_clamp": ctx, "fused": ctx}
            for name, (fn, table) in paths.items():
                fn(q, pk, pv, table, pos, ks, vs)  # compile outside timing
                out, us = timed(
                    lambda fn=fn, table=table: jax.block_until_ready(
                        fn(q, pk, pv, table, pos, ks, vs)),
                    repeat=3)
                outs[name] = np.asarray(out, np.float32)
                model = _hbm_bytes(spans[name], B=B, n_kv=n_kv, H=H, bs=bs,
                                   itemsize=itemsize,
                                   scale_bytes=scale_bytes,
                                   fused=name == "fused")
                row["paths"][name] = {
                    "wall_us": round(us, 1),
                    "hbm_bytes_model": model,
                    "v5e_projected_us": round(memory_time_s(model) * 1e6, 3),
                }
                emit(f"decode/paged_attention/{dt}/ctx{ctx}/{name}", us,
                     f"hbm_bytes={model}")
            # All three compute the same attention.
            np.testing.assert_allclose(outs["fused"], outs["gather"],
                                       rtol=5e-2, atol=5e-2)
            np.testing.assert_allclose(outs["gather_clamp"], outs["gather"],
                                       rtol=0, atol=0)
            g = row["paths"]["gather"]
            f = row["paths"]["fused"]
            row["fused_bytes_reduction"] = round(
                g["hbm_bytes_model"] / f["hbm_bytes_model"], 2)
            row["fused_projected_speedup"] = round(
                g["v5e_projected_us"] / f["v5e_projected_us"], 2)
            # The absolute per-decode-step saving grows with context: the
            # staging copy the gather writes + re-reads scales with the
            # table span while the fused kernel adds only live-block reads.
            row["fused_projected_gap_us"] = round(
                g["v5e_projected_us"] - f["v5e_projected_us"], 3)
            row["fused_wall_speedup"] = round(g["wall_us"] / f["wall_us"], 2)
            rows.append(row)
            results[f"{dt}_ctx{ctx}_projected_speedup"] = (
                row["fused_projected_speedup"])

    # --- int8 pool capacity: ~2x tokens per pooled byte ------------------
    capacity = _int8_capacity_demo(quick, H=H)
    results["int8_capacity_ratio"] = capacity["capacity_ratio"]

    if quick:
        return results
    bench_path = (Path(__file__).resolve().parents[1]
                  / "BENCH_paged_attention.json")
    bench_path.write_text(json.dumps({
        "note": ("one decode step's paged attention, single layer, fully "
                 "occupied pool with tables provisioned for 2x the live "
                 "context. wall_us is MEASURED in CPU interpret mode, "
                 "where the fused kernel's serial grid emulation loses "
                 "to the gather's memcpy (interpret wall time is not a "
                 "TPU number; use --backend reference for fastest CPU "
                 "serving). hbm_bytes_model / v5e_projected_us is "
                 "MODELED, not measured: fused streams each live block "
                 "once, gather reads + stages + re-reads its full table "
                 "span (3x span bytes), so the speedup ratio is fixed by "
                 "the 2x provisioning (6x) and the widening "
                 "fused_projected_gap_us is the absolute per-step saving "
                 "growing linearly with context"),
        "config": {"batch": B, "n_kv": n_kv, "gqa_group": group,
                   "head_dim": H, "block_size": bs},
        "rows": rows,
        "int8_pool_capacity": capacity,
    }, indent=2) + "\n")
    return results


def _int8_capacity_demo(quick: bool, *, H: int) -> dict:
    """Serve end to end through an int8 paged pool holding ~2x the tokens
    of a bf16 pool with the same byte budget."""
    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_reduced_config
    from repro.models import build_model
    from repro.serving import ContinuousScheduler, Request

    cfg = get_reduced_config("olmo-1b")
    hd = cfg.head_dim
    per_tok_bf16 = 2 * hd * 2            # k+v bf16, per (layer, head)
    per_tok_int8 = 2 * (hd + 4)          # k+v int8 codes + fp32 scales
    ratio = per_tok_bf16 / per_tok_int8
    # The reduced model's tiny head_dim understates the win; at the
    # benchmark/serving head dim the scale plane amortizes to ~2x.
    ratio_h = (2 * H * 2) / (2 * (H + 4))

    bs, bf16_blocks = 4, 8
    budget = bf16_blocks * bs * per_tok_bf16
    int8_blocks = int(budget // (bs * per_tok_int8))

    cfg8 = dataclasses.replace(cfg, kv_cache_quant=True)
    params = build_model(cfg8).init(jax.random.PRNGKey(0))
    sched = ContinuousScheduler(cfg8, params, max_batch=2, max_ctx=40,
                                bucket=8, paged=True, block_size=bs,
                                pool_blocks=int8_blocks)
    rng = np.random.default_rng(3)
    n = 2 if quick else 3
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, 8),
                    max_new_tokens=6) for i in range(n)]
    done = sched.run(reqs)
    stats = sched.pool_stats()
    served = sum(len(r.out_tokens) for r in done)
    emit("decode/int8_pool_capacity", 0.0,
         f"tokens_per_budget_ratio={ratio:.2f} (h{H}: {ratio_h:.2f}) "
         f"int8_capacity={stats['capacity_tokens']} "
         f"bf16_capacity={bf16_blocks * bs}")
    return {
        "note": ("equal pooled byte budget; int8 pool = codes + "
                 "per-(slot, head) fp32 scale planes, dequantized "
                 "in-kernel by the fused paged-attention op"),
        "byte_budget": budget,
        "bf16_capacity_tokens": bf16_blocks * bs,
        "int8_capacity_tokens": stats["capacity_tokens"],
        "capacity_ratio": round(stats["capacity_tokens"]
                                / (bf16_blocks * bs), 2),
        "bytes_per_token_ratio": round(ratio, 2),
        f"bytes_per_token_ratio_h{H}": round(ratio_h, 2),
        "requests_served": len(done),
        "tokens_served": served,
        "all_completed": all(not r.failed for r in done),
    }


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="two contexts, no JSON artifact (CI smoke)")
    args = ap.parse_args()
    run(quick=args.quick)
