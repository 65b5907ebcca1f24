"""Fused paged-attention decode kernel vs the gather-then-attend reference.

The specification is the surviving reference composition —
``kv_cache.paged_gather`` → ``models.common.decode_attention`` — swept
over ragged block tables, block-boundary positions, GQA group sizes,
bf16 and int8 pools, and freed-slot rows (trash-block garbage must never
leak into a live row's output). The kernel reads one layer of the whole
stacked pool, named by a layer index. The transformer-level test drives
the whole ``_decode_step_paged`` both ways; the scheduler-level test
checks the int8 paged pool serves greedy bit-identically to the
contiguous int8 cache."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.kernels import ops
from repro.models import build_model
from repro.models.common import decode_attention
from repro.models.kv_cache import (
    merge_heads,
    paged_gather,
    quantize_kv,
    scale_rows,
)

KEY = jax.random.PRNGKey(0)
LAYERS = 3


def _case(seed, *, B, n_kv, group, H, bs, maxb, quantized,
          positions=None, tables=None, layer=1):
    """Random stacked pool (LAYERS layers, each different) + ragged
    tables, attended at `layer`. Row b gets `tables[b]` live blocks
    (defaults: a ragged mix incl. a freed row when B >= 3); positions
    default to the last slot of each row's live span."""
    rng = np.random.default_rng(seed)
    nb = B * maxb + 1
    shape = (LAYERS, nb, bs, n_kv, H)
    kf = jnp.asarray(rng.normal(size=shape), jnp.float32)
    vf = jnp.asarray(rng.normal(size=shape), jnp.float32)
    if quantized:
        pool_k, k_scale = quantize_kv(kf)
        pool_v, v_scale = quantize_kv(vf)
        k_scale, v_scale = scale_rows(k_scale), scale_rows(v_scale)
    else:
        pool_k, pool_v = kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16)
        k_scale = v_scale = None
    pool_k, pool_v = merge_heads(pool_k), merge_heads(pool_v)
    if tables is None:
        live = [max(1, maxb - b) for b in range(B)]
        if B >= 3:
            live[B - 1] = 0  # freed slot: table all -1
        tables = live
    tbl = np.full((B, maxb), -1, np.int32)
    free = list(range(1, nb))
    rng.shuffle(free)  # non-contiguous pool blocks: table order != pool order
    for b, n in enumerate(tables):
        for j in range(n):
            tbl[b, j] = free.pop()
    if positions is None:
        positions = [max(0, n * bs - 1) for n in tables]
    q = jnp.asarray(rng.normal(size=(B, 1, n_kv * group, H)), jnp.bfloat16)
    return (q, pool_k, pool_v, jnp.asarray(tbl),
            jnp.asarray(positions, jnp.int32), k_scale, v_scale,
            jnp.int32(layer))


def _fused(case, **kw):
    q, pool_k, pool_v, tbl, pos, ks, vs, layer = case
    kw.setdefault("backend", "interpret")
    return ops.paged_attention(q, pool_k, pool_v, tbl, pos, layer=layer,
                               k_scale=ks, v_scale=vs, **kw)


def _reference(q, pool_k, pool_v, tbl, pos, k_scale, v_scale, layer):
    n_kv = pool_k.shape[3] // q.shape[3]
    k_r, v_r, kpos, ks_r, vs_r = paged_gather(pool_k, pool_v, tbl,
                                              k_scale, v_scale, layer=layer,
                                              n_kv=n_kv)
    return decode_attention(q, k_r, v_r, kpos, pos,
                            k_scale=ks_r, v_scale=vs_r)


@pytest.mark.parametrize("n_kv,group", [(4, 1), (2, 2), (2, 4)])
@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("bh", [0, 1, 2])  # 0 = planner default (all heads)
def test_fused_matches_gather_reference(n_kv, group, quantized, bh):
    """Ragged tables + freed row, every GQA grouping, both pool dtypes,
    and every head-tiling the autotuner / a loaded plan file can pick
    (bh < NKV runs the multi-step head grid): the fused kernel reproduces
    the gather-based reference on live rows (bitwise after the output's
    bf16 cast)."""
    case = _case(1, B=3, n_kv=n_kv, group=group, H=16, bs=4, maxb=4,
                 quantized=quantized)
    blocks = (bh, 4, 16) if bh else None
    out = _fused(case, blocks=blocks)
    ref = _reference(*case)
    # Row 2 is freed (table all -1): its output is discarded by the
    # scheduler and differs by construction (fused -> zeros, reference ->
    # uniform average); live rows must agree exactly in bf16.
    assert np.array_equal(np.asarray(out[:2]), np.asarray(ref[:2]))
    assert np.all(np.asarray(out[2]) == 0)


def test_reference_backend_is_gather_composition():
    """backend="reference" must agree with the explicit paged_gather →
    decode_attention composition (it IS the specification)."""
    case = _case(2, B=2, n_kv=2, group=2, H=16, bs=4, maxb=3,
                 quantized=False)
    out = _fused(case, backend="reference")
    ref = _reference(*case)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("pos", [0, 3, 4, 7, 8, 15])
def test_block_boundary_positions(pos):
    """Positions at, just before, and just after every block boundary
    (bs=4): the kernel's per-element visibility mask must match the
    reference's kpos <= q_pos on both sides of each crossing."""
    case = _case(3, B=2, n_kv=2, group=2, H=16, bs=4, maxb=4,
                 quantized=False, tables=[4, 4], positions=[pos, pos])
    out = _fused(case)
    ref = _reference(*case)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("quantized", [False, True])
def test_trash_block_never_leaks_into_live_rows(quantized):
    """Fill the trash block (pool block 0) with huge garbage — the writes
    freed slots and unallocated virtual blocks land on. No live row's
    output may change."""
    case = _case(4, B=3, n_kv=2, group=2, H=16, bs=4, maxb=4,
                 quantized=quantized)
    q, pool_k, pool_v, tbl, pos, ks, vs, layer = case
    clean = _fused(case)
    big = 120 if quantized else 1e4
    pool_k = pool_k.at[:, 0].set(big)
    pool_v = pool_v.at[:, 0].set(big)
    if quantized:
        ks = ks.at[:, 0].set(1e4)
        vs = vs.at[:, 0].set(1e4)
    dirty = _fused((q, pool_k, pool_v, tbl, pos, ks, vs, layer))
    assert np.array_equal(np.asarray(clean[:2]), np.asarray(dirty[:2]))


def test_paged_gather_max_blocks_clamp():
    """The clamped gather returns exactly the prefix of the full gather
    (satellite: stop copying guaranteed-dead trash-block columns)."""
    case = _case(5, B=3, n_kv=2, group=1, H=8, bs=4, maxb=6,
                 quantized=True, tables=[2, 3, 1])
    q, pool_k, pool_v, tbl, pos, ks, vs, layer = case
    k_f, v_f, kpos_f, ks_f, vs_f = paged_gather(pool_k, pool_v, tbl, ks, vs,
                                                layer=layer, n_kv=2)
    k_c, v_c, kpos_c, ks_c, vs_c = paged_gather(pool_k, pool_v, tbl, ks, vs,
                                                max_blocks=3, layer=layer,
                                                n_kv=2)
    n = 3 * 4
    for full, clamped in ((k_f, k_c), (v_f, v_c), (kpos_f, kpos_c),
                          (ks_f, ks_c), (vs_f, vs_c)):
        assert clamped.shape[1] == n
        assert np.array_equal(np.asarray(full[:, :n]), np.asarray(clamped))
    # And attention over the clamp is bit-identical when it covers every
    # live block (softmax weights on masked slots are exactly zero).
    full = decode_attention(q, k_f, v_f, kpos_f, pos,
                            k_scale=ks_f, v_scale=vs_f)
    clam = decode_attention(q, k_c, v_c, kpos_c, pos,
                            k_scale=ks_c, v_scale=vs_c)
    assert np.array_equal(np.asarray(full), np.asarray(clam))


@pytest.mark.parametrize("quantized", [False, True])
def test_decode_step_fused_vs_reference_path(quantized):
    """Whole-model check: _decode_step_paged with the fused kernel vs the
    gather-then-attend path — same pool writes (bitwise) and same logits
    (bf16-exact), on both pool dtypes."""
    from repro.models import transformer

    cfg = dataclasses.replace(get_reduced_config("olmo-1b"),
                              kv_cache_quant=quantized)
    params = build_model(cfg).init(KEY)
    cache = transformer.init_paged_cache(cfg, batch=2, num_blocks=9,
                                         block_size=4, max_blocks=4)
    tbl = jnp.asarray([[1, 2, 3, -1], [4, 5, -1, -1]], jnp.int32)
    kv = dataclasses.replace(cache.kv, block_table=tbl,
                             length=jnp.asarray([9, 5], jnp.int32))
    cache = dataclasses.replace(cache, kv=kv,
                                pos=jnp.asarray([9, 5], jnp.int32))
    toks = jnp.asarray([[7], [11]], jnp.int32)
    c_f, lg_f = transformer.decode_step(params, cfg, cache, toks)
    c_r, lg_r = transformer.decode_step(params, cfg, cache, toks,
                                        paged_fused=False)
    assert np.array_equal(np.asarray(lg_f), np.asarray(lg_r))
    assert np.array_equal(np.asarray(c_f.kv.k), np.asarray(c_r.kv.k))
    assert np.array_equal(np.asarray(c_f.kv.v), np.asarray(c_r.kv.v))
    if quantized:
        assert c_f.kv.quantized
        assert np.array_equal(np.asarray(c_f.kv.k_scale),
                              np.asarray(c_r.kv.k_scale))


def test_quantizing_paged_cache_write():
    """paged_cache_write with scale planes quantizes on the way in: the
    written slots of the named layer hold exactly quantize_kv's codes and
    scales, and no other layer changes."""
    from repro.models.kv_cache import (
        paged_cache_write,
        scale_slots,
        split_heads,
    )

    rng = np.random.default_rng(6)
    B, n_kv, H, bs, nb = 3, 2, 8, 4, 5
    pool_k = jnp.zeros((LAYERS, nb, bs, n_kv * H), jnp.int8)
    pool_v = jnp.zeros((LAYERS, nb, bs, n_kv * H), jnp.int8)
    ks = jnp.zeros((LAYERS, nb, 1, n_kv * bs), jnp.float32)
    vs = jnp.zeros((LAYERS, nb, 1, n_kv * bs), jnp.float32)
    tbl = jnp.asarray([[1, 2], [3, -1], [-1, -1]], jnp.int32)
    k_new = jnp.asarray(rng.normal(size=(B, 1, n_kv, H)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(B, 1, n_kv, H)), jnp.float32)
    pos = jnp.asarray([5, 2, 0], jnp.int32)  # rows 0/1 live, row 2 freed
    pool_k, pool_v, ks, vs = paged_cache_write(
        pool_k, pool_v, tbl, k_new, v_new, pos, bs, k_scale=ks, v_scale=vs,
        layer=1)
    kq, kscale = quantize_kv(k_new)
    codes = split_heads(pool_k, n_kv)[1]             # (nb, bs, n_kv, H)
    slots = scale_slots(ks, bs)[1]                   # (nb, bs, n_kv, 1)
    assert np.array_equal(np.asarray(codes[2, 1]), np.asarray(kq[0, 0]))
    assert np.array_equal(np.asarray(slots[2, 1]), np.asarray(kscale[0, 0]))
    assert np.array_equal(np.asarray(codes[3, 2]), np.asarray(kq[1, 0]))
    assert np.array_equal(np.asarray(slots[3, 2]), np.asarray(kscale[1, 0]))
    # Row 2 is freed: its write landed in the trash block, not a live one.
    assert np.array_equal(np.asarray(codes[0, 0]),
                          np.asarray(quantize_kv(k_new)[0][2, 0]))
    for other in (0, 2):
        assert not np.asarray(pool_k[other]).any()
        assert not np.asarray(ks[other]).any()


@pytest.mark.parametrize("layer", [0, 1, LAYERS - 1],
                         ids=["first", "middle", "last"])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_stacked_pool_layer_matches_per_layer_call(quantized, layer):
    """The kernel on the whole stacked pool at `layer` is bitwise the
    per-layer call: the same kernel on a one-layer pool holding only
    that layer's planes (every layer holds different bytes, so reading
    another layer would show)."""
    case = _case(7, B=3, n_kv=2, group=2, H=16, bs=4, maxb=4,
                 quantized=quantized, layer=layer)
    q, pool_k, pool_v, tbl, pos, ks, vs, _ = case
    one = lambda a: None if a is None else a[layer:layer + 1]  # noqa: E731
    per_layer = (q, one(pool_k), one(pool_v), tbl, pos, one(ks), one(vs),
                 jnp.int32(0))
    out = _fused(case)
    assert np.array_equal(np.asarray(out), np.asarray(_fused(per_layer)))
    other = _fused(case[:7] + (jnp.int32((layer + 1) % LAYERS),))
    assert not np.array_equal(np.asarray(out[:2]), np.asarray(other[:2]))


@pytest.fixture(scope="module")
def olmo_int8():
    cfg = dataclasses.replace(get_reduced_config("olmo-1b"),
                              kv_cache_quant=True)
    return cfg, build_model(cfg).init(KEY)


def test_int8_paged_serving_matches_contiguous_int8(olmo_int8):
    """The lifted scheduler restriction: int8-KV requests serve from the
    paged pool (fused kernel, in-kernel dequant) greedy bit-identical to
    the contiguous int8 cache — including a mid-decode admission across
    block boundaries."""
    from repro.serving import ContinuousScheduler, Request

    cfg, params = olmo_int8
    pa = np.arange(8) % 64
    pb = (np.arange(8) + 3) % 64
    reqs = lambda: [Request(0, pa, max_new_tokens=8),
                    Request(1, pb, max_new_tokens=5)]
    contig = ContinuousScheduler(cfg, params, max_batch=2, max_ctx=48,
                                 bucket=16, paged=False)
    ref = {r.rid: r.out_tokens for r in contig.run(reqs())}

    paged = ContinuousScheduler(cfg, params, max_batch=2, max_ctx=48,
                                bucket=16, paged=True, block_size=4)
    assert paged.paged and paged.cache.kv.quantized
    got = {r.rid: r.out_tokens for r in paged.run(reqs())}
    assert got == ref
    stats = paged.pool_stats()
    assert stats["paged"] and stats["reserved_kv_bytes"] > 0

    # Mid-decode admission: join after 3 steps, crossing block boundaries.
    sched = ContinuousScheduler(cfg, params, max_batch=2, max_ctx=48,
                                bucket=16, paged=True, block_size=4)
    r0, r1 = reqs()
    sched.submit(r0)
    for _ in range(3):
        sched.step()
    sched.submit(r1)
    while sched.num_active or sched.num_waiting:
        sched.step()
    assert r0.out_tokens == ref[0]
    assert r1.out_tokens == ref[1]
