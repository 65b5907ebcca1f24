"""RWKV-6 (Finch) chunked linear-attention kernel.

The rwkv6-3b architecture in the assigned pool is attention-free: its mixer
is the data-dependent-decay recurrence

    S_t = diag(w_t) S_{t-1} + k_t v_t^T
    o_t = r_t (S_{t-1} + diag(u) k_t v_t^T)

A naive lax.scan is latency-bound (T sequential steps of rank-1 updates).
The TPU-native formulation processes the sequence in chunks of C tokens:
within a chunk everything is dense matmul work for the MXU, and only the
C-step-compressed state crosses chunk boundaries.

Stability: decays satisfy 0 < w ≤ 1 so all exponent differences used here
(L_{t-1}-L_s for s<t and L_last-L_s) are ≤ 0 — every exp() is ≤ 1; no
log-space overflow regardless of chunk size.

Grid: (H, T/C) with ("arbitrary", "arbitrary") semantics — the state
scratch S (K, V) persists across grid steps; it is re-initialized whenever
the chunk index wraps to 0 (new head). Per-chunk work:

    term1  = (r ⊙ e^{Lsh}) @ S                    # carry-in state
    P[t,s] = Σ_k r[t,k] k[s,k] e^{Lsh[t,k]-L[s,k]}   (s < t, intra-chunk)
    P[t,t] = Σ_k r[t,k] u[k] k[t,k]                  (current-token bonus)
    out    = term1 + P @ v
    S     ← diag(e^{L_last}) S + (k ⊙ e^{L_last - L})^T @ v

Validated in interpret mode against the sequential scan oracle
(repro/kernels/ref.py::wkv6_ref).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import compiler_params as _compiler_params


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state_ref, *, chunk: int):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[0].astype(jnp.float32)  # (C, K)
    k = k_ref[0].astype(jnp.float32)  # (C, K)
    v = v_ref[0].astype(jnp.float32)  # (C, V)
    w = w_ref[0].astype(jnp.float32)  # (C, K) decays in (0, 1]
    u = u_ref[0].astype(jnp.float32)  # (1, K)

    lw = jnp.log(jnp.maximum(w, 1e-12))
    L = jnp.cumsum(lw, axis=0)          # inclusive log-decay prefix
    Lsh = L - lw                        # exclusive prefix (L_{t-1})

    S = state_ref[...]

    # Carry-in contribution.
    term1 = (r * jnp.exp(Lsh)) @ S      # (C, V)

    # Intra-chunk pairwise contribution (strictly lower triangular) plus
    # the diag bonus term. diff <= 0 for s < t, so exp() never overflows.
    diff = Lsh[:, None, :] - L[None, :, :]              # (C, C, K)
    t_ids = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_ids = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = (s_ids < t_ids)[:, :, None]
    gate = jnp.where(tri, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
    P = jnp.sum(r[:, None, :] * k[None, :, :] * gate, axis=-1)  # (C, C)
    Pdiag = jnp.sum(r * u * k, axis=-1)                          # (C,)
    eye = (s_ids == t_ids).astype(jnp.float32)
    P = P + eye * Pdiag[:, None]

    o_ref[0] = (term1 + P @ v).astype(o_ref.dtype)

    # State update to the end of the chunk.
    L_last = L[-1:, :]                                   # (1, K)
    decayed_k = k * jnp.exp(L_last - L)                  # (C, K), exps <= 1
    state_ref[...] = jnp.exp(L_last).T * S + decayed_k.T @ v


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    *,
    chunk: int = 32,
    interpret: bool = True,
) -> jax.Array:
    """Chunked WKV6. r/k/w: (T, H, K); v: (T, H, V); u: (H, K) → (T, H, V)."""
    T, H, K = r.shape
    V = v.shape[-1]
    if T % chunk:
        pad = chunk - T % chunk
        zkv = lambda a: jnp.concatenate(
            [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)], axis=0
        )
        r, kk, v = zkv(r), zkv(k), zkv(v)
        w = jnp.concatenate([w, jnp.ones((pad, H, K), w.dtype)], axis=0)
        k = kk
    Tp = r.shape[0]
    # (T, H, D) → (H, T, D) so heads are the outer grid dim.
    rt, kt, vt, wt = (jnp.swapaxes(a, 0, 1) for a in (r, k, v, w))
    out = pl.pallas_call(
        functools.partial(_wkv6_kernel, chunk=chunk),
        grid=(H, Tp // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, K), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, chunk, K), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, chunk, V), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, chunk, K), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, 1, K), lambda h, c: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, V), lambda h, c: (h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((H, Tp, V), jnp.float32),
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        compiler_params=_compiler_params(("arbitrary", "arbitrary")),
        name="wkv6",
        interpret=interpret,
    )(rt, kt, vt, wt, u[:, None, :])
    return jnp.swapaxes(out, 0, 1)[:T]

