"""The system under test: the program's ServingEngine, built from a
configuration file and a seed.

Everything here goes through the program's public surface:
`ModelConfig`, `PackedWeight`, `bitplane.pack_weights`, `ServingEngine`
and its `ContinuousScheduler`. The weights are made on the device in one
jitted call from the seed (`make_params`), already in the packed form the
engine serves, so no float copy of the layer matrices ever exists.
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


def build_engine(m: dict, seed: int, params, on_token):
    """A ServingEngine at the configuration's context, batch and pool,
    block size and prefill budget left at the program's defaults."""
    import inspect

    from repro.serving import ServingEngine

    block = inspect.signature(ServingEngine).parameters["block_size"].default
    return ServingEngine(
        model_config(m), params, max_batch=m["serving"]["max_batch"],
        quant=None, seed=engine_seed(seed), max_ctx=m["context_length"],
        on_token=on_token, pool_blocks=pool_blocks(m, block))


def engine_seed(seed: int) -> int:
    """The sampling seed handed to the engine (it takes 31 bits)."""
    return int(seed) % (2**31 - 1)
