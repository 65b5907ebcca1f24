"""Seeded random weights for a dense transformer configuration.

One definition serves both sides of the comparison that decides
`correct`: the serving set-up packs these codes into the program's weight
leaves, and the plain reference rebuilds the same float weights from the
same seed, layer by layer. Neither side hands the other anything.

Every layer matrix is drawn as N(0, 1/d_in), then quantized per output
channel to `w_bits`-bit symmetric codes with an absmax scale. The codes
times the scales are the model's weights: the served 4-bit weights are
exact, and what the comparison measures is everything downstream of
them (8-bit activations, the int8 KV pool, bfloat16 compute, the
kernels). Embedding and head are drawn in float32 and held in bfloat16,
the type they are served in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Layer matrices in a fixed order: (name, input width, output width).
LINEAR = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
FFN = ("w_gate", "w_up", "w_down")
NORM_STD = 0.05
EMBED_STD = 0.02


def base_key(seed: int) -> jax.Array:
    """A raw PRNG key from any non-negative integer seed (wider than 32
    bits too), through numpy's SeedSequence."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def linear_shapes(m: dict) -> dict:
    """(K, N) of every layer matrix of configuration `m`."""
    d, hd, f = m["d_model"], m["head_dim"], m["d_ff"]
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def codes(key, k: int, n: int, bits: int):
    """(codes int32 (k, n) in [-2^(bits-1), 2^(bits-1)-1], scales float32
    (1, n)): a N(0, 1/k) matrix quantized per output channel."""
    w = jax.random.normal(key, (k, n), jnp.float32) * (1.0 / k) ** 0.5
    qmax = (1 << (bits - 1)) - 1
    s = jnp.max(jnp.abs(w), axis=0, keepdims=True) / qmax
    q = jnp.clip(jnp.round(w / s), -qmax - 1, qmax).astype(jnp.int32)
    return q, s


def _keys(seed_key):
    k = jax.random.wrap_key_data(seed_key)
    return jax.random.fold_in(k, 0), jax.random.fold_in(k, 1)


def _gain(key, n: int, centre: float = 0.0):
    return (centre + NORM_STD * jax.random.normal(key, (n,))).astype(
        jnp.bfloat16)


def layer_codes(seed_key, m: dict, layer, bits: int) -> dict:
    """Codes, scales and bfloat16 norm gains of one layer (`layer` may be
    traced)."""
    layers_key, _ = _keys(seed_key)
    kl = jax.random.fold_in(layers_key, layer)
    shapes = linear_shapes(m)
    out = {}
    for i, name in enumerate(LINEAR):
        out[name] = codes(jax.random.fold_in(kl, i), *shapes[name], bits)
    d, hd = m["d_model"], m["head_dim"]
    nk = jax.random.fold_in(kl, len(LINEAR))
    n1, n2, n3, n4, n5, n6 = jax.random.split(nk, 6)
    if m["norm"] == "layernorm":
        out["ln1"] = {"scale": _gain(n1, d, 1.0), "bias": _gain(n2, d)}
        out["ln2"] = {"scale": _gain(n3, d, 1.0), "bias": _gain(n4, d)}
    elif m["norm"] != "nonparam_ln":
        raise ValueError(f"norm {m['norm']!r} is not generated here")
    if m["qk_norm"]:
        out["q_norm"] = _gain(n5, hd)
        out["k_norm"] = _gain(n6, hd)
    return out


def outer(seed_key, m: dict) -> dict:
    """Embedding, head (untied configurations) and final norm, in the
    type they are served in (bfloat16)."""
    _, ok = _keys(seed_key)
    ke, kh, kn1, kn2 = jax.random.split(ok, 4)
    d, v = m["d_model"], m["vocab"]
    out = {"embed": (jax.random.normal(ke, (v, d)) * EMBED_STD)
           .astype(jnp.bfloat16)}
    if not m["tie_embeddings"]:
        out["head"] = (jax.random.normal(kh, (d, v)) * (1.0 / d) ** 0.5
                       ).astype(jnp.bfloat16)
    if m["norm"] == "layernorm":
        out["final_norm"] = {"scale": _gain(kn1, d, 1.0),
                             "bias": _gain(kn2, d)}
    return out


def reference_layer(seed_key, m: dict, layer: int, bits: int) -> dict:
    """One layer's float32 weights: codes times scales, norm gains."""
    c = layer_codes(seed_key, m, layer, bits)
    return {k: (v[0].astype(jnp.float32) * v[1]
                if k in LINEAR else v) for k, v in c.items()}
