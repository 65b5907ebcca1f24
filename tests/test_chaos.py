"""Seeded fault-injection chaos harness over the serving stack.

Every fault kind degrades ONE request or ONE call, never the engine:
alloc faults become ordinary pool pressure (queueing / preemption /
bypass), kernel faults fall back to the bitwise-identical reference
backend, NaN-corrupted logits fail exactly the poisoned request, and
raising callbacks are contained. The sweep at the bottom replays seeded
schedules end-to-end and asserts the three global properties the ISSUE
demands: no deadlock (bounded steps), every request terminal (tokens or
error, never both missing), pool invariants intact after every run —
and survivors bitwise identical to the no-fault run.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.models import build_model
from repro.serving import (
    ContinuousScheduler,
    FaultInjector,
    InjectedFault,  # noqa: F401  (exported surface)
    Request,
    assert_pool_invariants,
)

KEY = jax.random.PRNGKey(0)
P8 = (np.arange(8) * 3 + 1) % 64
P11 = (np.arange(11) * 5 + 2) % 64


@pytest.fixture(scope="module")
def olmo():
    cfg = get_reduced_config("olmo-1b")
    params = build_model(cfg).init(KEY)
    return cfg, params


def _sched(cfg, params, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_ctx", 64)
    kw.setdefault("bucket", 16)
    kw.setdefault("paged", True)
    kw.setdefault("block_size", 4)
    return ContinuousScheduler(cfg, params, **kw)


def _drain(sched, cap=400):
    out = []
    steps = 0
    while sched.num_active or sched.num_waiting:
        out.extend(sched.step())
        steps += 1
        assert steps < cap, "scheduler failed to drain under faults"
    assert_pool_invariants(sched)
    return out


def _workload(n=4):
    return [Request(i, (P8 if i % 2 else P11) + i, max_new_tokens=6)
            for i in range(n)]


def _serve(cfg, params, chaos=None, **kw):
    sched = _sched(cfg, params, chaos=chaos, **kw)
    for r in _workload():
        sched.submit(r)
    done = _drain(sched)
    return sched, {r.rid: r for r in done}


# -- the injector itself ---------------------------------------------------


def test_injector_is_deterministic():
    a = FaultInjector(7, p_kernel=0.3, p_nan=0.1)
    b = FaultInjector(7, p_kernel=0.3, p_nan=0.1)
    sched_a = [a.fire("kernel") for _ in range(50)]
    sched_b = [b.fire("kernel") for _ in range(50)]
    assert sched_a == sched_b
    assert any(sched_a)
    assert a.counts() == b.counts()


def test_injector_streams_are_independent():
    """Enabling one kind never shifts another kind's schedule: each seam
    draws from its own (seed, kind) stream."""
    solo = FaultInjector(3, p_nan=0.2)
    both = FaultInjector(3, p_nan=0.2, p_kernel=0.9)
    solo_sched, both_sched = [], []
    for i in range(40):
        both.fire("kernel")           # interleaved visits to another seam
        solo_sched.append(solo.fire("nan"))
        both_sched.append(both.fire("nan"))
    assert solo_sched == both_sched


def test_injector_zero_rate_never_draws_entropy():
    inj = FaultInjector(0, p_alloc=0.0)
    assert not any(inj.fire("alloc") for _ in range(20))
    assert inj.draws["alloc"] == 20 and inj.fired["alloc"] == 0


def test_injector_max_faults_cap():
    inj = FaultInjector(1, p_kernel=1.0, max_faults=3)
    fires = [inj.fire("kernel") for _ in range(10)]
    assert sum(fires) == 3 and fires[:3] == [True] * 3
    assert inj.total_fired == 3


def test_injector_validation():
    with pytest.raises(ValueError, match="p_nan"):
        FaultInjector(0, p_nan=1.5)
    with pytest.raises(ValueError, match="max_faults"):
        FaultInjector(0, max_faults=-1)
    inj = FaultInjector(5)
    assert {inj.pick(3) for _ in range(50)} <= {0, 1, 2}


# -- one seam at a time ----------------------------------------------------


def test_kernel_fault_falls_back_bit_identically(olmo):
    """Every decode dispatch 'fails' (capped): the reference-backend
    fallback keeps each stream bitwise the fault-free run."""
    cfg, params = olmo
    _, clean = _serve(cfg, params)
    sched, done = _serve(
        cfg, params, FaultInjector(11, p_kernel=1.0, max_faults=8))
    assert sched.kernel_fallbacks == 8
    for rid, r in done.items():
        assert r.error is None
        assert r.out_tokens == clean[rid].out_tokens


def test_kernel_fault_fallback_runs_on_reference_backend(olmo, monkeypatch):
    """The fallback really runs the reference backend even when the
    kernel path has already compiled the same decode step: the first
    decode dispatch succeeds (seed 4), later ones fault."""
    from repro.kernels import ref as kref

    calls = []
    real = kref.paged_attention_ref

    def counted(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(kref, "paged_attention_ref", counted)
    cfg, params = olmo
    sched, done = _serve(
        cfg, params, FaultInjector(4, p_kernel=0.5, max_faults=2))
    assert sched.kernel_fallbacks == 2
    assert calls, "the fallback reused the kernel backend's trace"
    assert all(r.error is None for r in done.values())


def test_nan_fault_fails_only_poisoned_request(olmo):
    cfg, params = olmo
    _, clean = _serve(cfg, params)
    sched, done = _serve(
        cfg, params, FaultInjector(2, p_nan=1.0, max_faults=1))
    assert sched.nan_logit_events == 1
    poisoned = [r for r in done.values() if r.error == "nan-logits"]
    assert len(poisoned) == 1
    for r in done.values():
        if r.error is None:
            assert r.out_tokens == clean[r.rid].out_tokens


def test_alloc_fault_degrades_to_pool_pressure(olmo):
    """A failed reservation behaves exactly like a full pool: the request
    waits (or preempts/bypasses) and everyone still completes, bitwise
    the clean run."""
    cfg, params = olmo
    _, clean = _serve(cfg, params)
    sched, done = _serve(
        cfg, params, FaultInjector(4, p_alloc=0.5, max_faults=6))
    assert sched.pool_pressure_events >= 1
    for rid, r in done.items():
        assert r.error is None
        assert r.out_tokens == clean[rid].out_tokens


def test_callback_fault_is_contained(olmo):
    cfg, params = olmo
    seen = []
    sched = _sched(cfg, params, on_token=lambda r, t: seen.append(t),
                   chaos=FaultInjector(9, p_callback=1.0, max_faults=1))
    for r in _workload():
        sched.submit(r)
    done = {r.rid: r for r in _drain(sched)}
    assert sched.callback_errors == 1
    errored = [r for r in done.values() if r.error]
    assert len(errored) == 1 and "callback" in errored[0].error
    assert len(seen) > 0              # the stream kept flowing


# -- seeded end-to-end sweep ----------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_seeded_chaos_sweep(olmo, seed):
    """All four seams armed at once over an overcommitted pool, three
    seeds: bounded steps (no deadlock), every request terminal, pool
    invariants after every drain, survivors bitwise the no-fault run —
    and the same seed replays the same fault counts."""
    cfg, params = olmo
    kw = dict(pool_blocks=10)
    _, clean = _serve(cfg, params, **kw)

    def chaos():
        return FaultInjector(seed, p_alloc=0.15, p_kernel=0.15,
                             p_nan=0.05, p_callback=0.05, max_faults=12)

    sched, done = _serve(cfg, params, chaos(), **kw)
    assert len(done) == 4
    for r in done.values():
        assert r.out_tokens is not None           # terminal, always
        if r.error is None:
            assert len(r.out_tokens) == 6
            assert r.out_tokens == clean[r.rid].out_tokens
    counts = sched.chaos.counts()

    sched2, done2 = _serve(cfg, params, chaos(), **kw)
    assert sched2.chaos.counts() == counts        # same seed, same schedule
    assert {rid: r.error for rid, r in done2.items()} == {
        rid: r.error for rid, r in done.items()}
    assert {rid: r.out_tokens for rid, r in done2.items()} == {
        rid: r.out_tokens for rid, r in done.items()}


def test_chaos_counts_surface_in_pool_stats(olmo):
    cfg, params = olmo
    sched, _ = _serve(cfg, params,
                      FaultInjector(6, p_kernel=0.5, max_faults=2))
    ch = sched.pool_stats()["chaos"]
    assert ch["seed"] == 6
    assert ch["total_fired"] == 2
    assert ch["fired"]["kernel"] == 2
    assert ch["draws"]["kernel"] >= 2
