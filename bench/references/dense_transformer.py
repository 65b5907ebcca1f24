"""Plain float32 reference of the dense decoder block the benchmark serves.

Straight `jax.numpy` at `highest` matmul precision, with no kernels, no
cache, no batching and no quantized activations. It follows the block as
the configuration file states it and imports nothing of the program: the
weights come from `benchlib.weights` with the run's seed, one layer at a
time, so the whole float32 model never has to fit at once.

Block (pre-norm, sequential residual):
    h = norm1(x); q, k, v = h Wq, h Wk, h Wv   (per-head RMS qk-norm with a
    (1 + g) gain where `qk_norm`); rotary on the whole head, halves
    rotated; causal softmax(q k^T / sqrt(head_dim)) v with each query head
    reading KV head (head // (n_heads / n_kv_heads)); x += attn Wo;
    x += W_down (silu(norm2(x) W_gate) * norm2(x) W_up).
Norms: "nonparam_ln" is LayerNorm without gain or bias, "layernorm" has
both; epsilon 1e-6. Logits: final norm, then the head (the embedding
transposed where tied).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import weights

EPS = 1e-6
PAD = 256        # sequences are padded to a multiple of this many tokens
HEAD_ROWS = 256  # logits rows computed at a time


def _norm(x, p, kind):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + EPS)
    if kind == "layernorm":
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y


def _rope(x, theta):
    """x (T, heads, hd): rotate the two halves by position angles."""
    t, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _head_rms(x, g):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * (1.0 + g.astype(jnp.float32))


def _layer(m, w, x):
    t = x.shape[0]
    nq, nkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    h = _norm(x, w.get("ln1"), m["norm"])
    q = (h @ w["wq"]).reshape(t, nq, hd)
    k = (h @ w["wk"]).reshape(t, nkv, hd)
    v = (h @ w["wv"]).reshape(t, nkv, hd)
    if m["qk_norm"]:
        q, k = _head_rms(q, w["q_norm"]), _head_rms(k, w["k_norm"])
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    g = nq // nkv
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    x = x + a.reshape(t, nq * hd) @ w["wo"]
    h2 = _norm(x, w.get("ln2"), m["norm"])
    f = jax.nn.silu(h2 @ w["w_gate"]) * (h2 @ w["w_up"])
    return x + f @ w["w_down"]


def logit_gaps(m: dict, seed: int, seqs):
    """Reference logits gaps for picked tokens.

    `seqs` is a list of (tokens (T,) int, first, picks): the model reads
    `tokens`; at each position p >= first, picks[name][p - first] is a
    token chosen there (the one that follows position p). Returns, per
    sequence, {name: gaps} with gap = best reference logit minus the
    reference logit of the picked token, float32, >= 0."""
    bits = int(m["serving"]["weights"][1:].split("a")[0])
    key = weights.base_key(seed)
    with jax.default_matmul_precision("highest"):
        outer = jax.jit(partial(weights.outer, m=m))(key)
        embed = outer["embed"]
        xs = []
        for toks, _, _ in seqs:
            tp = -(-len(toks) // PAD) * PAD
            ids = np.zeros(tp, np.int32)
            ids[:len(toks)] = toks
            xs.append(embed[jnp.asarray(ids)].astype(jnp.float32))
        layer_w = jax.jit(partial(weights.reference_layer, m=m, bits=bits))
        step = jax.jit(partial(_layer, m))
        for layer in range(m["num_layers"]):
            w = layer_w(key, layer=layer)
            xs = [step(w, x) for x in xs]
            del w
        head = (outer["embed"].T if m["tie_embeddings"] else outer["head"]
                ).astype(jnp.float32)
        final = jax.jit(lambda x, hw: _norm(x, outer.get("final_norm"),
                                            m["norm"]) @ hw)
        out = []
        for x, (toks, first, picks) in zip(xs, seqs):
            rows = np.arange(first, len(toks))
            best, chosen = [], {k: [] for k in picks}
            for lo in range(0, len(rows), HEAD_ROWS):
                r = rows[lo:lo + HEAD_ROWS]
                pad = np.full(HEAD_ROWS, r[-1])
                pad[:len(r)] = r
                lg = np.asarray(final(x[jnp.asarray(pad)], head))[:len(r)]
                best.append(lg.max(-1))
                for k, pk in picks.items():
                    p = np.asarray(pk)[lo:lo + HEAD_ROWS]
                    chosen[k].append(lg[np.arange(len(r)), p])
            best = np.concatenate(best)
            out.append({k: best - np.concatenate(v) for k, v in
                        chosen.items()})
        return out
