"""Mosaic compiles of the served kernels at olmo-1b widths, for a described
TPU v5e chip (``v5e:2x2``): nothing runs, the TPU compiler only has to
accept each kernel. Interpret mode cannot show what Mosaic refuses (an
unsupported gather, a slice of a loaded value); these tests do. The
serving programs that carry the paged pool are compiled whole too, to
show that XLA keeps the stacked pool in the layout the kernels read: no
layer's plane is sliced out and no stacked plane is copied or laid out
again (`test_device_scopes.pool_copies`).

All chip compiles live in this one file. The topology is described inside
a module-scoped fixture, never at import, so every pytest worker collects
the same tests and only the worker that runs this file loads the TPU
library.
"""
import dataclasses
import functools
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.registry import get_registry
from repro.models import build_model
from test_device_scopes import pool_copies, serving_program

CFG = get_config("olmo-1b")
NKV, NQ, H = CFG.n_kv_heads, CFG.n_heads, CFG.head_dim
BS = 16             # serve.py's default pool block size
LAYERS = 2          # pool layers the kernels index into
POOL_BLOCKS = 512
MAX_BLOCKS = 64     # 1024 tokens of context per row
BATCH = 4           # serve.py's default max batch
CHUNK = 32          # serve.py's default prefill budget
ROOFLINE = Path(__file__).resolve().parents[1] / "bench" / "roofline"


@pytest.fixture(scope="module")
def chip():
    """A SingleDeviceSharding on chip 0 of a described v5e:2x2, with JAX's
    persistent compilation cache off: a compile for a described chip is
    written to the cache but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


def _compile(fn, chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _pool(int8: bool):
    """The stacked pool planes (codes, then scale rows for int8) and the
    layer index the kernels take."""
    dt = jnp.int8 if int8 else jnp.bfloat16
    pool = [((LAYERS, POOL_BLOCKS, BS, NKV * H), dt)] * 2
    scales = ([((LAYERS, POOL_BLOCKS, 1, NKV * BS), jnp.float32)] * 2
              if int8 else [])
    return pool, ((), jnp.int32), scales


def _decode(q, k, v, table, pos, layer, *sc):
    ks, vs = sc if sc else (None, None)
    return ops.paged_attention(q, k, v, table, pos, layer=layer,
                               k_scale=ks, v_scale=vs, backend="mosaic")


def _chunk(q, kn, vn, k, v, blocks, start, length, layer, *sc):
    ks, vs = sc if sc else (None, None)
    return ops.paged_prefill(q, kn, vn, k, v, blocks, start, length,
                             layer=layer, k_scale=ks, v_scale=vs,
                             backend="mosaic")


def _decode_shapes(int8: bool):
    pool, layer, scales = _pool(int8)
    return (((BATCH, 1, NQ, H), jnp.bfloat16), *pool,
            ((BATCH, MAX_BLOCKS), jnp.int32), ((BATCH,), jnp.int32), layer,
            *scales)


def _chunk_shapes(int8: bool):
    pool, layer, scales = _pool(int8)
    new = ((1, CHUNK, NKV, H), jnp.bfloat16)
    return (((1, CHUNK, NQ, H), jnp.bfloat16), new, new, *pool,
            ((MAX_BLOCKS,), jnp.int32), ((), jnp.int32), ((), jnp.int32),
            layer, *scales)


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_paged_attention_compiles(chip, int8):
    _compile(_decode, chip, *_decode_shapes(int8))


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_paged_prefill_compiles(chip, int8):
    _compile(_chunk, chip, *_chunk_shapes(int8))


def test_fused_quantize_matmul_compiles(chip):
    # The FFN down-projection: K = d_ff = 8192, N = d_model = 2048.
    fn = functools.partial(ops.fused_quantize_matmul, a_bits=8,
                           backend="mosaic")
    _compile(fn, chip, ((CHUNK, CFG.d_ff), jnp.float32),
             ((CFG.d_ff, CFG.d_model), jnp.int8))


@pytest.mark.parametrize("kernel", ["paged_attention", "paged_prefill"])
def test_kernel_names_reach_the_compiled_program(chip, kernel):
    """The device operation of each served attention kernel is named for
    its `pallas_call`, and carries every name the benchmark's roofline
    reader for that kernel matches (`bench/roofline/<kernel>.py` NAMES)."""
    if kernel == "paged_attention":
        text = _compile(_decode, chip, *_decode_shapes(True))
    else:
        text = _compile(_chunk, chip, *_chunk_shapes(True))
    op = re.search(r"%(\S+) = .*tpu_custom_call", text)
    names = re.search(r"^NAMES = \((.*)\)$", (ROOFLINE / f"{kernel}.py")
                      .read_text(), re.M).group(1)
    assert op and op.group(1).startswith(kernel)
    for name in re.findall(r'"([^"]+)"', names):
        assert name in op.group(1)


@pytest.mark.parametrize("program", ("decode", "chunk", "verify"))
def test_serving_programs_update_the_pool_in_place(chip, program):
    """The fused paged decode, the chunk and the verify programs at
    olmo-1b widths (two layers), compiled whole for the chip with the
    cache donated as the scheduler donates it: the stacked pool (int8
    codes and scale rows) reaches both kernels as it is stored, so the
    program holds no layer's plane and no copy of a stacked plane."""
    cfg = dataclasses.replace(CFG, num_layers=LAYERS, kv_cache_quant=True)
    model = build_model(cfg)
    # A deployment's pool (4.3 GB here): a toy one fits the chip's fast
    # memory, where XLA would prefetch whole planes.
    blocks = 32768

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(
        lambda: model.init_paged_cache(BATCH, blocks, BS, MAX_BLOCKS)))
    fn, args = serving_program(program, model, BATCH, CHUNK)
    with get_registry().use("mosaic"):
        text = jax.jit(fn, donate_argnums=(1,)).lower(
            params, cache, *on_chip(args)).compile().as_text()
    assert "tpu_custom_call" in text
    assert pool_copies(text, cache) == []
