"""The system under test: the program's ServingEngine, built from a
configuration file, its model module and a seed.

The model module (`models/<name>.py`, named by the configuration's
`"model"` and loaded by `spec.model`) gives the program's model config,
the serving parameters and the pool's size in blocks; everything else
goes through the program's public `ServingEngine` and its
`ContinuousScheduler`.
"""
from __future__ import annotations


def build_engine(model, m: dict, seed: int, params, on_token):
    """A ServingEngine for `model` at the configuration's context, batch
    and pool, block size and prefill budget left at the program's
    defaults."""
    import inspect

    from repro.serving import ServingEngine

    block = inspect.signature(ServingEngine).parameters["block_size"].default
    return ServingEngine(
        model.model_config(m), params, max_batch=m["serving"]["max_batch"],
        quant=None, seed=engine_seed(seed), max_ctx=m["context_length"],
        on_token=on_token, pool_blocks=model.pool_blocks(m, block))


def engine_seed(seed: int) -> int:
    """The sampling seed handed to the engine (it takes 31 bits)."""
    return int(seed) % (2**31 - 1)
