"""Flash attention (fwd) Pallas kernel — the memory-term lever for the
attention-heavy cells in EXPERIMENTS.md §Roofline.

The distributed/jnp path (models/common.chunked_attention) is memory-bound
under the unfused HLO convention because every (q-block × kv-block) score
tile round-trips HBM. This kernel keeps the running max/denominator and
the output accumulator in VMEM scratch across the KV grid dimension —
per-(batch, head, q-block) HBM traffic is exactly q + k + v + out, the
flash contract.

Supports causal and sliding-window masks and a query-position offset
(decode/prefill continuation). GQA callers pass q grouped per kv head
(B, NKV, G·Tq, D) or pre-broadcast kv — see ops.flash_attention for the
dispatching wrapper.

Grid: (B·H, Tq/bq, Tk/bk) with ("parallel", "parallel", "arbitrary") —
the KV dim is innermost so scratch persists across it; fully-masked KV
blocks are skipped with pl.when (the causal/window block-level test), so
compute is sub-quadratic for windowed attention, matching the jnp path's
semantics while eliminating its HBM traffic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import compiler_params as _compiler_params
from repro.kernels.common import round_up as _round_up


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, causal: bool, window: int,
                  q_offset: int, kv_len: int, scale: float, n_kb: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = q_offset + qi * bq
    k_lo = ki * bk
    # Block-level visibility: skip blocks fully outside the mask.
    visible = True
    if causal:
        visible = jnp.asarray(k_lo <= q_lo + bq - 1)
    if window:
        visible = jnp.logical_and(visible, k_lo + bk - 1 > q_lo - window)

    @pl.when(visible)
    def _step():
        q = q_ref[0].astype(jnp.float32)          # (bq, D)
        k = k_ref[0].astype(jnp.float32)          # (bk, D)
        v = v_ref[0].astype(jnp.float32)          # (bk, D)
        s = (q @ k.T) * scale                     # (bq, bk)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < kv_len
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, -jnp.inf)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - safe_m[:, None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe_m), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
        m_ref[...] = m_new

    @pl.when(ki == n_kb - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "bq", "bk", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (BH, Tq, D)
    k: jax.Array,  # (BH, Tk, D)
    v: jax.Array,  # (BH, Tk, D)
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = True,
) -> jax.Array:
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    scale = D**-0.5
    bq_ = min(bq, _round_up(Tq, 8))
    bk_ = min(bk, _round_up(Tk, 8))
    Tqp, Tkp = _round_up(Tq, bq_), _round_up(Tk, bk_)
    if Tqp != Tq:
        q = jnp.pad(q, ((0, 0), (0, Tqp - Tq), (0, 0)))
    if Tkp != Tk:
        k = jnp.pad(k, ((0, 0), (0, Tkp - Tk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Tkp - Tk), (0, 0)))
    n_kb = Tkp // bk_
    kernel = functools.partial(
        _flash_kernel, bq=bq_, bk=bk_, causal=causal, window=window,
        q_offset=q_offset, kv_len=Tk, scale=scale, n_kb=n_kb,
    )
    scratch = [
        pltpu.VMEM((bq_,), jnp.float32),
        pltpu.VMEM((bq_,), jnp.float32),
        pltpu.VMEM((bq_, D), jnp.float32),
    ]
    out = pl.pallas_call(
        kernel,
        grid=(BH, Tqp // bq_, n_kb),
        in_specs=[
            pl.BlockSpec((1, bq_, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk_, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk_, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Tqp, D), q.dtype),
        scratch_shapes=scratch,
        compiler_params=_compiler_params(("parallel", "parallel", "arbitrary")),
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
    return out[:, :Tq]
