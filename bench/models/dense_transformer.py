"""The dense decoder block, as the program serves it: its `ModelConfig`,
its serving parameters made from the seed, the pool blocks a byte budget
buys, and the operations its tokens require (the work of `mfu.*`).

A configuration file names this module with `"model":
"dense_transformer"`. Every model module gives the same four functions:

    model_config(m)                       the program's ModelConfig
    make_params(m, seed)                  serving parameters, on the device
    pool_blocks(m, block_size)            paged-pool blocks of the budget
    flops(m, tokens, keys, logits_rows)   operations those tokens require

Operations: per token through the layers 2 * P flops, P the layer
matrices' parameters (L * (d*NQ*H + 2*d*NKV*H + NQ*H*d + 3*d*f) for a
SwiGLU block). Attention: 4 * L * NQ * H flops per key a token attends to
(q.k and p.v). Head: 2 * d * V flops per logits row, one row per emitted
token. Norms, rotary and sampling are left out (under 1% at these
widths).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchlib import weights

MODEL_KEYS = ("num_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab", "rope_theta", "ffn", "norm", "qk_norm",
              "tie_embeddings")


def weight_bits(m: dict) -> tuple:
    """(w_bits, a_bits) of the configuration's serving token, e.g. w4a8."""
    tok = m["serving"]["weights"]
    w, a = tok[1:].split("a")
    return int(w), int(a)


def model_config(m: dict):
    """The program's ModelConfig for configuration file `m`."""
    from repro.configs.base import ModelConfig

    return ModelConfig(name=m["name"], family="dense",
                       kv_cache_quant=bool(m["serving"]["kv_int8"]),
                       **{k: m[k] for k in MODEL_KEYS})


def make_params(m: dict, seed: int):
    """Serving parameters in the program's layout (the tree
    `quantize_params_for_serving` gives for `init_params`), made on the
    device in one jitted call: packed w-bit layer matrices, bfloat16
    embedding, head and norm gains."""
    from repro.core.bitplane import pack_weights
    from repro.core.quantized_linear import PackedWeight

    bits, a_bits = weight_bits(m)
    shapes = weights.linear_shapes(m)

    def packed(c):
        q, s = c
        return pack_weights(q, bits, axis=0), s

    @jax.jit
    def build(seed_key):
        def one_layer(layer):
            c = weights.layer_codes(seed_key, m, layer, bits)
            return {k: (packed(v) if k in weights.LINEAR
                        else v) for k, v in c.items()}

        stacked = jax.lax.map(one_layer, jnp.arange(m["num_layers"]))
        blocks = {"ln1": stacked.pop("ln1", {}), "ln2": stacked.pop("ln2", {}),
                  "ffn": {}}
        for name in weights.LINEAR:
            pk, s = stacked.pop(name)
            leaf = PackedWeight(pk, s, bits, shapes[name][0], 0, None, a_bits,
                                True)
            (blocks["ffn"] if name in weights.FFN else blocks)[name] = leaf
        blocks.update(stacked)               # q_norm / k_norm
        out = weights.outer(seed_key, m)
        out.setdefault("final_norm", {})
        out["blocks"] = blocks
        return out

    return build(weights.base_key(seed))


def pool_blocks(m: dict, block_size: int) -> int:
    """Pool blocks that `serving.pool_bytes` buys: an int8 token costs
    layers x 2 x kv_heads x head_dim bytes of codes plus 2 x 4 bytes of
    scales per layer and KV head."""
    per_token = (m["num_layers"] * m["n_kv_heads"]
                 * (2 * m["head_dim"] + 2 * 4))
    if not m["serving"]["kv_int8"]:
        per_token = m["num_layers"] * m["n_kv_heads"] * 2 * m["head_dim"] * 2
    return m["serving"]["pool_bytes"] // (per_token * block_size)


def layer_params(m: dict) -> int:
    d, f, h = m["d_model"], m["d_ff"], m["head_dim"]
    nq, nkv = m["n_heads"], m["n_kv_heads"]
    return m["num_layers"] * (2 * d * nq * h + 2 * d * nkv * h + 3 * d * f)


def flops(m: dict, tokens: int, keys: int, logits_rows: int) -> float:
    """`tokens` run through the layers, attending to `keys` keys in all,
    and `logits_rows` rows of the head."""
    attn = 4 * m["num_layers"] * m["n_heads"] * m["head_dim"] * keys
    head = 2 * m["d_model"] * m["vocab"] * logits_rows
    return float(2 * layer_params(m) * tokens + attn + head)
