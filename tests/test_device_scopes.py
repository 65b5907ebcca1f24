"""The serving programs carry the device scopes (`jax.named_scope`) that a
profiler trace attributes device time by: the compiled HLO of the paged
decode step (fused kernel and gather reference), the prefill chunk and
the multi-row verify names ``embed``, ``attention``, ``serve_matmul`` and
``head`` in its op-name metadata, and the sampler ``sample``, so a
refactor cannot drop one silently. The scans that carry the KV pool stay
outside every scope. And the pool is updated in place: no program
computes one layer's pool plane or copies a whole stacked plane
(`pool_copies`, which ``tests/test_tpu_compile.py`` applies to the same
programs compiled for a TPU)."""
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


def pool_copies(compiled_text: str, cache, in_loops: bool = True) -> list:
    """The instructions of compiled HLO that copy the paged KV pool: any
    value shaped like one layer's pool plane (a slice the layer scan
    would take), and any copy of a whole stacked plane. The stacked
    planes themselves may only be carried and updated in place.

    `in_loops=False` counts stacked copies in the entry computation
    only: on the CPU the kernels run in interpret mode, whose grid loops
    hold the whole pool as loop state and copy it there; on a TPU each
    kernel is one custom call and no copy is allowed anywhere."""
    def shape(a, drop=0):
        dt = jnp.dtype(a.dtype).name.replace("int", "s").replace("float", "f")
        return f"{dt}[{','.join(map(str, a.shape[drop:]))}]"

    planes = [a for a in (cache.kv.k, cache.kv.v, cache.kv.k_scale,
                          cache.kv.v_scale) if a is not None]
    stacked = {shape(a) for a in planes}
    per_layer = {shape(a, 1) for a in planes}
    found = []
    entry = False
    for line in compiled_text.splitlines():
        if line and not line[0].isspace():
            entry = line.startswith("ENTRY")
        if " = " not in line:
            continue
        rhs = line.split(" = ", 1)[1]
        op = re.search(r"(?:^|\s)([a-z][\w.-]*)\(", rhs)
        if not op:
            continue
        result = set(re.findall(r"\w+\[[\d,]*\]", rhs[:op.start(1)]))
        copy = op.group(1).startswith("copy") and (in_loops or entry)
        if result & per_layer or (copy and result & stacked):
            found.append(line.strip()[:200])
    return found


def serving_program(program: str, model, batch: int = BATCH,
                    chunk: int = CHUNK):
    """(function, example args after params and cache) of one serving
    program for a cache of `batch` rows, as the scheduler jits it (the
    cache donated)."""
    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    if program.startswith("decode"):
        fused = program == "decode"
        fn = lambda p, c, t: transformer.decode_step(  # noqa: E731
            p, model.cfg, c, t, paged_fused=fused)
        return fn, (i32(batch, 1),)
    if program == "chunk":
        fn = lambda p, c, b: model.prefill_chunk(p, c, b)  # noqa: E731
        return fn, ({"tokens": i32(1, chunk), "lengths": i32(1),
                     "start": i32(), "slot": i32(), "blocks": i32(2)},)
    fn = lambda p, c, b: model.prefill_chunk_logits_multi(  # noqa: E731
        p, c, b)
    return fn, ({"tokens": i32(batch, chunk), "lengths": i32(batch),
                 "starts": i32(batch), "slots": i32(batch),
                 "blocks": i32(batch, 2)},)


def _compile(program: str, model, params, cache) -> str:
    """The compiled HLO text of one serving program."""
    fn, args = serving_program(program, model)
    return jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile().as_text()


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
    """Every loop that carries the whole stacked pool (the scan over
    layers, and verify's scan over rows) has an op name with no scope.
    The pool kernels' own grid loops (interpret mode, on the CPU; one
    custom call on a TPU) carry it too, inside `attention`."""
    _, _, cache = served
    k = cache.kv.k
    pool = f"{jnp.dtype(k.dtype).name.replace('int', 's')}" \
           f"[{','.join(map(str, k.shape))}]"
    loops = [re.search(r'op_name="([^"]*)"', line).group(1).split("/")
             for line in compiled(program).splitlines()
             if " while(" in line and pool in line.split(" while(")[0]]
    scans = [path for path in loops
             if not {"paged_attention", "paged_prefill"} & set(path)]
    assert scans
    for path in scans:
        assert not set(path) & set(SCOPES + ("sample",)), path


@pytest.mark.parametrize("program", ("decode", "chunk", "verify"))
def test_pool_is_updated_in_place(served, compiled, program):
    """The fused paged decode, the chunk and the verify program hand the
    stacked pool to their kernels whole: no instruction makes one
    layer's pool plane, and nothing copies a stacked plane."""
    _, _, cache = served
    assert pool_copies(compiled(program), cache, in_loops=False) == []


def test_pool_copies_finds_a_layer_slice(served):
    """The check itself: a program that slices one layer out of the
    stacked pool for a consumer and stacks it back, as a scan with the
    pool in its xs and ys does, is caught."""
    _, _, cache = served

    def sliced(kv):
        def body(c, k):
            return c, jax.lax.optimization_barrier(k)

        return jax.lax.scan(body, 0, kv.k)[1]

    text = jax.jit(sliced).lower(cache.kv).compile().as_text()
    assert pool_copies(text, cache)


def test_sampler_carries_its_scope():
    args = (jnp.zeros((BATCH, 64)), jnp.zeros((BATCH,)),
            jnp.zeros((BATCH,), jnp.int32), jnp.zeros((BATCH, 2), jnp.uint32),
            jnp.zeros((BATCH,), jnp.int32))
    text = sampling.sample_tokens.lower(*args).compile().as_text()
    assert "sample" in scopes(text)
