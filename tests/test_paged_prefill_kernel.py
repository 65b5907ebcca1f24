"""Fused paged chunked-prefill kernel vs the scatter-then-attend oracle.

The specification is ``ref.paged_prefill_ref``: write the chunk's K/V
into the row's pool blocks (``kv_cache.paged_chunk_write``), gather the
whole table back, and attend the chunk's queries causally over
[pool-resident prefix ++ chunk]. The fused kernel must reproduce it
*bitwise* — attention output, pool bytes, and int8 scale planes — across
cold and warm prefixes, partial-block chunk starts, padded chunks, both
pool dtypes, softcap, and every head tiling.

Two contract subtleties the tests encode:

* The reference is compared **jitted**. Eager ``quantize_kv`` compiles
  ``absmax / 127.0`` differently from the jitted strength-reduced form
  (1 ULP on some scales); every real consumer (scheduler, transformer)
  runs jitted, so the bitwise contract is stated in the jit context.
* Pool block 0 is the trash block: freed/non-destination writes land
  there and its contents are undefined, so pool comparisons skip it.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref as kref
from repro.kernels.registry import get_registry, pick_paged_prefill_blocks

BS = 4          # pool block size
NKV, G, H = 2, 3, 16
LAYERS, LAYER = 3, 1   # stacked pool depth; the layer the cases write


def _case(seed, *, quantized, start, length, lc, mb, alloc, layer=LAYER):
    """Random stacked pool (LAYERS layers) + one row's block table with
    `alloc` live blocks, written at `layer`. The chunk covers prompt
    positions [start, start+length) inside an Lc=`lc` padded call;
    `start` need not be block-aligned (warm prefix ending mid-block)."""
    assert start + length <= alloc * BS
    rng = np.random.default_rng(seed)
    nb = 8
    shape = (LAYERS, nb, BS, NKV * H)
    if quantized:
        pk = jnp.asarray(rng.integers(-128, 128, shape), jnp.int8)
        pv = jnp.asarray(rng.integers(-128, 128, shape), jnp.int8)
        rows = (LAYERS, nb, 1, NKV * BS)
        ks = jnp.asarray(rng.random(rows) * 0.02, jnp.float32)
        vs = jnp.asarray(rng.random(rows) * 0.02, jnp.float32)
    else:
        pk = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        pv = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        ks = vs = None
    q = jnp.asarray(rng.standard_normal((1, lc, NKV * G, H)), jnp.bfloat16)
    kn = jnp.asarray(rng.standard_normal((1, lc, NKV, H)), jnp.bfloat16)
    vn = jnp.asarray(rng.standard_normal((1, lc, NKV, H)), jnp.bfloat16)
    blocks = np.full(mb, -1, np.int32)
    blocks[:alloc] = rng.permutation(np.arange(1, nb))[:alloc]
    return (q, kn, vn, pk, pv, jnp.asarray(blocks),
            jnp.int32(start), jnp.int32(length), ks, vs, jnp.int32(layer))


def _kernel(case, *, bh, softcap=0.0, backend="interpret"):
    return ops.paged_prefill(*case[:8], layer=case[10], k_scale=case[8],
                             v_scale=case[9], softcap=softcap,
                             blocks_plan=(bh, BS, H), backend=backend)


def _both(case, *, bh, softcap=0.0):
    ref = jax.jit(functools.partial(kref.paged_prefill_ref,
                                    softcap=softcap))(
        *case[:8], case[10], k_scale=case[8], v_scale=case[9])
    return ref, _kernel(case, bh=bh, softcap=softcap)


def _assert_bitwise(ref, out):
    names = ("attn", "pool_k", "pool_v", "k_scale", "v_scale")
    for name, r, o in zip(names, ref, out):
        if r is None:
            assert o is None
            continue
        r, o = np.asarray(r), np.asarray(o)
        if name != "attn":
            r, o = r[:, 1:], o[:, 1:]  # trash block: contents undefined
        assert np.array_equal(r, o), name


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("bh", [1, 2])
def test_cold_full_chunk_bitwise(quantized, bh):
    """Cold prefill, chunk fills the call exactly: attention and the
    written pool blocks match the oracle bit-for-bit."""
    case = _case(0, quantized=quantized, start=0, length=8, lc=8,
                 mb=4, alloc=2)
    _assert_bitwise(*_both(case, bh=bh))


@pytest.mark.parametrize("quantized", [False, True])
def test_warm_prefix_partial_block_start(quantized):
    """Chunk starts mid-block (warm prefix of 6 tokens, bs=4): the
    kernel merges pool-resident rows with chunk rows inside the shared
    block and never clobbers the resident prefix."""
    case = _case(1, quantized=quantized, start=6, length=7, lc=8,
                 mb=6, alloc=4)
    _assert_bitwise(*_both(case, bh=2))


@pytest.mark.parametrize("start,length,lc", [(0, 5, 8), (9, 1, 4), (4, 0, 4)])
def test_padded_short_and_empty_chunks(start, length, lc):
    """length < Lc (padded tail), a single-token chunk, and the empty
    chunk: padded query rows produce zeros, padded K/V rows never reach
    the pool, and a zero-length call is the identity on the pool."""
    case = _case(2, quantized=False, start=start, length=length, lc=lc,
                 mb=4, alloc=3)
    ref, out = _both(case, bh=2)
    _assert_bitwise(ref, out)
    if length == 0:
        assert np.array_equal(np.asarray(out[1])[:, 1:],
                              np.asarray(case[3])[:, 1:])


def test_softcap_int8():
    """Logit softcap composes with in-kernel dequantization."""
    case = _case(3, quantized=True, start=3, length=6, lc=8, mb=6, alloc=3)
    _assert_bitwise(*_both(case, bh=2, softcap=30.0))


def test_resident_prefix_blocks_untouched():
    """Blocks wholly before the chunk start keep their exact input
    bytes — the epilogue only writes destination blocks (j >= start//bs),
    everything earlier aliases through unchanged."""
    case = _case(4, quantized=False, start=8, length=4, lc=4, mb=4, alloc=3)
    _, out = _both(case, bh=2)
    tbl = np.asarray(case[5])
    for blk in tbl[:2]:  # blocks 0,1 cover positions [0, 8) — all prefix
        assert np.array_equal(np.asarray(out[1])[:, blk],
                              np.asarray(case[3])[:, blk])
        assert np.array_equal(np.asarray(out[2])[:, blk],
                              np.asarray(case[4])[:, blk])


def test_trash_block_garbage_never_leaks():
    """Huge garbage in pool block 0 (where dead writes land) must not
    change the chunk's attention output."""
    case = _case(5, quantized=False, start=4, length=6, lc=8, mb=4, alloc=3)
    clean = _kernel(case, bh=2)
    pk = case[3].at[:, 0].set(1e4)
    pv = case[4].at[:, 0].set(1e4)
    dirty = _kernel(case[:3] + (pk, pv) + case[5:], bh=2)
    assert np.array_equal(np.asarray(clean[0]), np.asarray(dirty[0]))


def test_reference_backend_dispatch():
    """backend="reference" routes to paged_prefill_ref itself."""
    case = _case(6, quantized=False, start=0, length=8, lc=8, mb=4, alloc=2)
    out = ops.paged_prefill(*case[:8], layer=case[10], backend="reference")
    ref = jax.jit(kref.paged_prefill_ref)(*case[:8], case[10])
    for r, o in zip(ref[:3], out[:3]):
        np.testing.assert_allclose(np.asarray(r, np.float32),
                                   np.asarray(o, np.float32))


@pytest.mark.parametrize("layer", [0, LAYER, LAYERS - 1],
                         ids=["first", "middle", "last"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_stacked_pool_layer_matches_per_layer_call(quantized, layer):
    """The kernel on the whole stacked pool at `layer` is bitwise the
    per-layer call — the same kernel on a one-layer pool holding only
    that layer's planes: the same attention and the same written layer.
    It changes only that layer's destination blocks: every other layer,
    and every other block of that layer, keeps its exact input bytes."""
    case = _case(8, quantized=quantized, start=6, length=7, lc=8, mb=6,
                 alloc=4, layer=layer)
    one = lambda a: None if a is None else a[layer:layer + 1]  # noqa: E731
    per_layer = case[:3] + (one(case[3]), one(case[4])) + case[5:8] + (
        one(case[8]), one(case[9]), jnp.int32(0))
    out = _kernel(case, bh=2)
    alone = _kernel(per_layer, bh=2)
    assert np.array_equal(np.asarray(out[0]), np.asarray(alone[0]))
    tbl = np.asarray(case[5])
    written = set(tbl[6 // BS:-(-13 // BS)].tolist()) | {0}   # + trash
    for plane, before, single in zip(out[1:], case[3:5] + case[8:10],
                                     alone[1:]):
        if before is None:
            assert plane is None and single is None
            continue
        plane, before = np.asarray(plane), np.asarray(before)
        assert np.array_equal(plane[layer, 1:], np.asarray(single)[0, 1:])
        for other in set(range(LAYERS)) - {layer}:
            assert np.array_equal(plane[other], before[other])
        kept = sorted(set(range(plane.shape[1])) - written)
        assert np.array_equal(plane[layer, kept], before[layer, kept])
        assert not np.array_equal(plane[layer], before[layer])


# -- registry plan plumbing --------------------------------------------------


def test_planner_registered_and_divides_heads():
    """The paged_prefill planner returns a head tile that divides n_kv
    and shrinks under a tight VMEM budget."""
    bh, bs, h = pick_paged_prefill_blocks(4, BS, H)
    assert bh >= 1 and 4 % bh == 0 and (bs, h) == (BS, H)
    tight = pick_paged_prefill_blocks(4, 128, 128, vmem_budget=1 << 16)
    assert tight[0] == 1


def test_plan_round_trips_through_file(tmp_path):
    """A recorded paged_prefill plan survives save_plans/load_plans and
    overrides the heuristic afterwards."""
    reg = get_registry()
    reg.record_plan("paged_prefill", 2, BS, H, (1, BS, H), "interpret")
    path = tmp_path / "plans.json"
    assert reg.save_plans(str(path)) >= 1
    reg._plans.pop(("paged_prefill", "interpret", (2, BS, H)))
    assert reg.load_plans(str(path)) >= 1
    assert reg.paged_prefill_plan(2, BS, H, backend="interpret") == (1, BS, H)
