"""Vectorized on-device sampling for the serving stack.

One jitted call samples every batch slot at once — greedy, temperature,
and top-k — with *per-slot* parameters, replacing the per-token NumPy
loop the engine used to run on the host.

Reproducibility contract: a request's sample stream is a pure function of
``(seed, rid, step)``. The base key is ``fold_in(PRNGKey(seed), rid)`` and
each emitted token folds in the request's own token counter, so sampled
outputs never depend on batch composition, slot assignment, or admission
order — a request gets the same tokens served solo, in a static batch, or
admitted mid-decode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def request_key(seed: int, rid: int) -> np.ndarray:
    """Per-request base PRNG key; the stream identity is (seed, rid) only."""
    return np.asarray(jax.random.fold_in(jax.random.PRNGKey(seed), rid))


def _sample_one(logits, temperature, top_k, base_key, step):
    """Sample one slot. logits (V,); all params scalars; vmapped over B."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    V = logits.shape[-1]
    # top-k: keep logits >= the k-th largest (ties keep everything equal
    # to the threshold); k <= 0 or k >= V disables the filter.
    kk = jnp.where((top_k <= 0) | (top_k >= V), V, top_k)
    thresh = jnp.sort(logits)[::-1][jnp.maximum(kk - 1, 0)]
    masked = jnp.where(logits >= thresh, logits, jnp.finfo(jnp.float32).min)
    key = jax.random.fold_in(base_key, step)
    temp = jnp.maximum(temperature, 1e-6)
    sampled = jax.random.categorical(key, masked / temp).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy)


@jax.jit
def sample_tokens(logits, temperatures, top_ks, base_keys, steps):
    """logits (B, V) float; temperatures (B,); top_ks (B,) int;
    base_keys (B, 2) uint32; steps (B,) int → tokens (B,) int32.

    temperature <= 0 means greedy for that slot (keys/steps unused there).
    Runs in the ``sample`` device scope.
    """
    with jax.named_scope("sample"):
        return jax.vmap(_sample_one)(logits, temperatures, top_ks, base_keys,
                                     steps)
