"""The serving programs carry the device scopes (`jax.named_scope`) that a
profiler trace attributes device time by: the compiled HLO of the paged
decode step (fused kernel and gather reference), the prefill chunk and
the multi-row verify names ``embed``, ``attention``, ``serve_matmul`` and
``head`` in its op-name metadata, and the sampler ``sample``, so a
refactor cannot drop one silently. The scans that carry the KV pool stay
outside every scope: their carry and stacking copies are no layer's."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_reduced_config
from repro.core.precision import as_policy, parse_quant_token
from repro.core.quantized_linear import quantize_params_for_serving
from repro.models import build_model, transformer
from repro.serving import sampling

BS, BLOCKS, MAX_BLOCKS, BATCH, CHUNK = 4, 16, 8, 2, 8
SCOPES = ("embed", "attention", "serve_matmul", "head")
PROGRAMS = ("decode", "decode_gather", "chunk", "verify")


def scopes(compiled_text: str) -> set:
    """Every path component of the op names in compiled HLO text."""
    return {part for path in re.findall(r'op_name="([^"]*)"', compiled_text)
            for part in path.split("/")}


@pytest.fixture(scope="module")
def served():
    cfg = dataclasses.replace(get_reduced_config("olmo-1b"),
                              kv_cache_quant=True)
    model = build_model(cfg)
    policy = as_policy(parse_quant_token("w4a8"))
    params = quantize_params_for_serving(model.init(jax.random.PRNGKey(0)),
                                         policy, min_size=1024)
    cache = model.init_paged_cache(BATCH, BLOCKS, BS, MAX_BLOCKS)
    return model, params, cache


def _compile(program: str, model, params, cache) -> str:
    """The compiled HLO text of one serving program."""
    if program.startswith("decode"):
        fused = program == "decode"
        fn = lambda p, c, t: transformer.decode_step(  # noqa: E731
            p, model.cfg, c, t, paged_fused=fused)
        args = (jnp.zeros((BATCH, 1), jnp.int32),)
    elif program == "chunk":
        fn = model.prefill_chunk
        args = ({"tokens": jnp.zeros((1, CHUNK), jnp.int32),
                 "lengths": jnp.asarray([CHUNK], jnp.int32),
                 "start": jnp.asarray(0, jnp.int32),
                 "slot": jnp.asarray(0, jnp.int32),
                 "blocks": jnp.arange(1, 3, dtype=jnp.int32)},)
    else:
        fn = model.prefill_chunk_logits_multi
        args = ({"tokens": jnp.zeros((BATCH, CHUNK), jnp.int32),
                 "lengths": jnp.full((BATCH,), CHUNK, jnp.int32),
                 "starts": jnp.zeros((BATCH,), jnp.int32),
                 "slots": jnp.arange(BATCH, dtype=jnp.int32),
                 "blocks": jnp.arange(1, 1 + 2 * BATCH, dtype=jnp.int32)
                 .reshape(BATCH, 2)},)
    return jax.jit(fn).lower(params, cache, *args).compile().as_text()


@pytest.fixture(scope="module")
def compiled(served):
    """program name -> its compiled HLO text, each compiled once."""
    texts = {}

    def get(program: str) -> str:
        if program not in texts:
            texts[program] = _compile(program, *served)
        return texts[program]

    return get


@pytest.mark.parametrize("scope", SCOPES)
@pytest.mark.parametrize("program", PROGRAMS)
def test_program_carries_the_scope(compiled, program, scope):
    assert scope in scopes(compiled(program))


@pytest.mark.parametrize("program", ("decode", "chunk", "verify"))
def test_pool_scans_are_in_no_scope(served, compiled, program):
    """Every loop whose state holds the whole stacked pool (the scan over
    layers, and verify's scan over rows) has an op name with no scope."""
    _, _, cache = served
    k = cache.kv.k
    pool = f"{jnp.dtype(k.dtype).name.replace('int', 's')}" \
           f"[{','.join(map(str, k.shape))}]"
    loops = [line for line in compiled(program).splitlines()
             if " while(" in line and pool in line.split(" while(")[0]]
    assert loops
    for line in loops:
        path = re.search(r'op_name="([^"]*)"', line).group(1)
        assert not set(path.split("/")) & set(SCOPES + ("sample",)), path


def test_sampler_carries_its_scope():
    args = (jnp.zeros((BATCH, 64)), jnp.zeros((BATCH,)),
            jnp.zeros((BATCH,), jnp.int32), jnp.zeros((BATCH, 2), jnp.uint32),
            jnp.zeros((BATCH,), jnp.int32))
    text = sampling.sample_tokens.lower(*args).compile().as_text()
    assert "sample" in scopes(text)
