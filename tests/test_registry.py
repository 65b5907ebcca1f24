"""Kernel backend registry: dispatch, scoped selection, block-plan cache,
and small-shape plan fixes."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.registry import (
    KernelBackend,
    KernelRegistry,
    get_registry,
    pick_fused_blocks,
    pick_matmul_blocks,
    pick_paged_attention_blocks,
    use_backend,
)

RNG = np.random.default_rng(7)


def test_default_backends_registered():
    reg = KernelRegistry()
    assert set(reg.names()) >= {"interpret", "mosaic", "reference"}
    assert reg.get("mosaic").interpret is False
    assert reg.get("reference").is_reference


def test_default_active_backend_is_platform_dependent():
    reg = KernelRegistry()
    # CPU test container: interpret is the resolved default.
    assert reg.default_name() in ("interpret", "mosaic")
    assert reg.active.name == reg.default_name()


def test_unknown_backend_raises_with_listing():
    reg = KernelRegistry()
    with pytest.raises(KeyError, match="interpret"):
        reg.get("cuda")


def test_use_backend_is_scoped():
    reg = get_registry()
    before = reg.active.name
    with reg.use("reference") as be:
        assert be.is_reference
        assert reg.active.name == "reference"
    assert reg.active.name == before


def test_per_call_backend_override():
    x = RNG.integers(-8, 8, (5, 40)).astype(np.int32)
    w = RNG.integers(-8, 8, (40, 7)).astype(np.int32)
    got_i = ops.bitplane_matmul(jnp.asarray(x), jnp.asarray(w), a_bits=4)
    got_r = ops.bitplane_matmul(jnp.asarray(x), jnp.asarray(w), a_bits=4,
                                backend="reference")
    np.testing.assert_array_equal(np.asarray(got_i), x @ w)
    np.testing.assert_array_equal(np.asarray(got_r), x @ w)


def test_reference_backend_end_to_end_ops():
    """Every op dispatches on the reference backend without Pallas."""
    with use_backend("reference"):
        x = jnp.asarray(RNG.standard_normal((4, 32)), jnp.float32)
        q, s = ops.quantize_rows(x, bits=4)
        assert q.shape == (4, 32) and s.shape == (4, 1)
        w = jnp.asarray(RNG.integers(-8, 8, (32, 6)), jnp.int32)
        acc = ops.bitplane_matmul(q, w, a_bits=4)
        np.testing.assert_array_equal(
            np.asarray(acc), np.asarray(q).astype(np.int64) @ np.asarray(w))


# -- block plans ------------------------------------------------------------


def test_pick_matmul_blocks_large_shapes_keep_mxu_tiles():
    bm, bn, bk = pick_matmul_blocks(4096, 4096, 8192)
    assert bm % 8 == 0 and bn % 128 == 0 and bk % 128 == 0
    assert 2 * (bm * bk + bk * bn) + 4 * bm * bn <= (4 << 20)


def test_pick_matmul_blocks_small_shapes_no_overpad():
    """Regression: n < 128 / k < 512 used to force 128+ blocks, padding a
    (3, 100, 5) matmul out to (8, 128, 128)."""
    bm, bn, bk = pick_matmul_blocks(3, 5, 100, n_align=8, k_align=8)
    assert bm == 8
    assert bn == 8          # was 128
    assert bk == 104        # was 128
    # The registry hands interpret-backend plans the relaxed alignment.
    plan = get_registry().matmul_plan(3, 5, 100, "interpret")
    assert plan[1] <= 8 and plan[2] <= 104
    # Mosaic keeps the MXU lane contract even for tiny shapes.
    plan_m = get_registry().matmul_plan(3, 5, 100, "mosaic")
    assert plan_m[1] % 128 == 0 and plan_m[2] % 128 == 0


def test_pick_fused_blocks_shrink_bm_for_long_rows():
    """Fused kernel keeps full fp32 rows resident: bm must shrink as K
    grows to stay inside the VMEM budget."""
    bm, bn, bk = pick_fused_blocks(256, 256, 65536)
    assert 8 * bm * 65536 + 2 * bk * bn + 4 * bm * bn <= (8 << 20)
    assert bm < 128


def test_plan_cache_memoizes():
    reg = KernelRegistry()
    p1 = reg.matmul_plan(64, 64, 64, "interpret")
    before = reg.cache_info()
    p2 = reg.matmul_plan(64, 64, 64, "interpret")
    after = reg.cache_info()
    assert p1 == p2
    assert after["hits"] == before["hits"] + 1


def test_record_plan_overrides_heuristic():
    reg = KernelRegistry()
    reg.record_plan("bitplane_matmul", 64, 64, 64, (8, 8, 8), "interpret")
    assert reg.matmul_plan(64, 64, 64, "interpret") == (8, 8, 8)


def test_autotune_caches_winner_and_skips_failures():
    reg = KernelRegistry()
    calls = []

    def run(blocks):
        if blocks[2] > 64:
            raise RuntimeError("candidate does not fit")
        calls.append(blocks)

    win = reg.autotune("bitplane_matmul", 64, 64, 64, run,
                       candidates=[(8, 8, 128), (8, 8, 64), (8, 8, 32)],
                       backend="interpret")
    assert win[2] <= 64
    n_calls = len(calls)
    again = reg.autotune("bitplane_matmul", 64, 64, 64, run,
                         backend="interpret")
    assert again == win
    assert len(calls) == n_calls  # cached — no re-measurement


def test_paged_attention_plan_bh_divides_heads():
    reg = KernelRegistry()
    bh, bs, hd = reg.paged_attention_plan(8, 16, 128, "interpret")
    assert 8 % bh == 0 and (bs, hd) == (16, 128)
    # Huge working sets shrink bh to a smaller divisor of NKV.
    bh2, _, _ = pick_paged_attention_blocks(8, 512, 4096)
    assert 8 % bh2 == 0 and bh2 < 8


def test_paged_attention_autotune_candidates_are_divisors():
    reg = KernelRegistry()
    seen = []
    reg.autotune("paged_attention", 6, 16, 64, seen.append,
                 backend="interpret")
    assert set(c[0] for c in seen) == {1, 2, 3, 6}
    assert all(c[1:] == (16, 64) for c in seen)


def test_save_and_load_plans_roundtrip(tmp_path):
    """Satellite: autotune winners survive process restarts via the JSON
    plan cache."""
    reg = KernelRegistry()
    reg.record_plan("bitplane_matmul", 64, 64, 64, (8, 8, 8), "interpret")
    reg.record_plan("paged_attention", 4, 16, 64, (2, 16, 64), "interpret")
    reg.matmul_plan(128, 256, 512, "mosaic")  # heuristic entry persists too
    path = tmp_path / "plans.json"
    assert reg.save_plans(path) == 3

    fresh = KernelRegistry()
    assert fresh.load_plans(path) == 3
    assert fresh.matmul_plan(64, 64, 64, "interpret") == (8, 8, 8)
    assert fresh.paged_attention_plan(4, 16, 64, "interpret") == (2, 16, 64)
    assert (fresh.matmul_plan(128, 256, 512, "mosaic")
            == reg.matmul_plan(128, 256, 512, "mosaic"))
    # Loaded plans count as cache hits, not misses: no re-planning.
    info = fresh.cache_info()
    assert info["plans"] == 3


def test_load_plans_rejects_unknown_version(tmp_path):
    """A wrong schema version warns and cold-starts (0 plans) — it must
    never crash the process that passed --plans."""
    path = tmp_path / "bad.json"
    path.write_text('{"version": 99, "plans": []}')
    reg = KernelRegistry()
    with pytest.warns(UserWarning, match="version"):
        assert reg.load_plans(path) == 0
    assert reg.cache_info()["plans"] == 0


def test_custom_backend_registration():
    reg = KernelRegistry()
    reg.register(KernelBackend("emulator", interpret=True, n_align=8, k_align=8))
    assert "emulator" in reg.names()
    with pytest.raises(ValueError):
        reg.register(KernelBackend("emulator", interpret=True))


def test_no_module_global_interpret_flag_left():
    assert not hasattr(ops, "_INTERPRET")
