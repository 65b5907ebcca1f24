"""Sarathi-style chunked prefill: bit-identity with whole-prompt prefill
and the decode-stall bound.

The contract: splitting admission prefill into ``prefill_budget``-token
chunks through the fused paged-prefill kernel changes *scheduling only*.
Every request's greedy tokens are bit-identical to the solo static
baseline across chunk sizes (including budget=1 and non-divisors of the
block size), mid-decode admission, the int8 pool, and warm prefix hits —
and a live decoding slot never loses more than one chunk's worth of time
per scheduler step to an admission in progress (each step runs at most
one budgeted chunk, and decode always runs alongside it)."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.models import build_model
from repro.serving import ContinuousScheduler, Request, ServingEngine

KEY = jax.random.PRNGKey(0)
BS = 4  # paged block size used throughout — small, so chunks cross blocks
PROMPT_SHORT = np.arange(8) % 64
PROMPT_LONG = (np.arange(23) * 5 + 2) % 64  # not a multiple of BS or bucket


@pytest.fixture(scope="module")
def olmo():
    cfg = get_reduced_config("olmo-1b")
    params = build_model(cfg).init(KEY)
    return cfg, params


def _solo(cfg, params, prompt, n):
    eng = ServingEngine(cfg, params, max_batch=2, bucket=16)
    return eng.generate_static(
        [Request(1, prompt, max_new_tokens=n)])[0].out_tokens


def _sched(cfg, params, budget, **kw):
    kw.setdefault("max_ctx", 64)
    return ContinuousScheduler(cfg, params, max_batch=2, bucket=16,
                               paged=True, block_size=BS,
                               chunked_prefill=True, prefill_budget=budget,
                               **kw)


def _drain(sched):
    out = []
    while sched.num_active or sched.num_waiting:
        out.extend(sched.step())
    return out


@pytest.mark.parametrize("budget", [1, BS - 1, BS, 3 * BS + 1])
def test_chunked_matches_whole_prefill(olmo, budget):
    """Every chunk size in the satellite sweep — a single token, one
    short of a block, exactly a block, and a non-divisor spanning three
    blocks — reproduces the solo static baseline bit-for-bit."""
    cfg, params = olmo
    ref = _solo(cfg, params, PROMPT_LONG, 6)
    sched = _sched(cfg, params, budget)
    sched.submit(Request(1, PROMPT_LONG, max_new_tokens=6))
    done = _drain(sched)
    assert done[0].out_tokens == ref
    assert sched.prefill_chunks_run == -(-len(PROMPT_LONG) // budget)


@pytest.mark.parametrize("kv_int8", [False, True])
def test_mid_decode_admission(olmo, kv_int8):
    """A long prompt admitted into a live decoding batch: both the
    in-flight request and the chunk-admitted one match their solo runs,
    on the bf16 and the int8 pool."""
    cfg, params = olmo
    if kv_int8:
        cfg = dataclasses.replace(cfg, kv_cache_quant=True)
    ref_a = _solo(cfg, params, PROMPT_SHORT, 12)
    ref_b = _solo(cfg, params, PROMPT_LONG, 6)
    sched = _sched(cfg, params, 5)
    sched.submit(Request(0, PROMPT_SHORT, max_new_tokens=12))
    done = []
    for _ in range(3):
        done.extend(sched.step())
    sched.submit(Request(1, PROMPT_LONG, max_new_tokens=6))
    done.extend(_drain(sched))
    got = {r.rid: r.out_tokens for r in done}
    assert got[0] == ref_a
    assert got[1] == ref_b
    assert sched.prefill_chunks_run > 0


def test_warm_prefix_then_chunked_tail(olmo):
    """Prefix cache + chunked prefill compose: the second request's warm
    block-aligned prefix stays resident (never rewritten by the chunk
    kernel) and only the uncached tail is chunk-prefilled."""
    cfg, params = olmo
    sched = _sched(cfg, params, 5, prefix_cache=True)
    sched.submit(Request(0, PROMPT_LONG, max_new_tokens=6))
    done = _drain(sched)
    chunks_cold = sched.prefill_chunks_run
    ext = np.concatenate([PROMPT_LONG, np.asarray([9, 11, 13])])
    sched.submit(Request(1, ext, max_new_tokens=6))
    done.extend(_drain(sched))
    got = {r.rid: r.out_tokens for r in done}
    assert got[0] == _solo(cfg, params, PROMPT_LONG, 6)
    assert got[1] == _solo(cfg, params, ext, 6)
    # 20 of 26 tokens warm (5 whole blocks): the tail is one 6-token plan.
    assert sched.prefix_hit_tokens == 20
    assert sched.prefill_chunks_run == chunks_cold + 2


def test_stall_bound_one_chunk_per_step(olmo):
    """While a long admission is chunk-prefilling, the live slot emits a
    token on EVERY scheduler step — no decode step is skipped for more
    than one budget's worth of prefill tokens."""
    cfg, params = olmo
    sched = _sched(cfg, params, 4)
    sched.submit(Request(0, PROMPT_SHORT, max_new_tokens=32))
    for _ in range(2):
        sched.step()
    sched.submit(Request(1, PROMPT_LONG, max_new_tokens=4))
    live = sched._slots.index(next(r for r in sched._slots
                                   if r is not None and r.rid == 0))
    tokens_before = len(sched._slots[live].out_tokens)
    stalled_before = sched.decode_steps_stalled
    steps = 0
    while True:
        start_chunks = sched.prefill_chunk_tokens
        sched.step()  # first iteration admits AND runs the first chunk
        steps += 1
        # at most one budget of prefill tokens spent this step...
        assert sched.prefill_chunk_tokens - start_chunks <= 4
        # ...and the live slot still decoded (one new token per step).
        assert len(sched._slots[live].out_tokens) == tokens_before + steps
        if sched.prefill_chunks_run and not sched._chunk_plans:
            break
    assert steps == -(-len(PROMPT_LONG) // 4)
    assert sched.decode_steps_stalled - stalled_before == steps
    _drain(sched)


def test_counters_in_pool_stats(olmo):
    """pool_stats() surfaces the interleave counters serve.py reports."""
    cfg, params = olmo
    sched = _sched(cfg, params, 8)
    sched.submit(Request(0, PROMPT_LONG, max_new_tokens=4))
    _drain(sched)
    stats = sched.pool_stats()
    assert stats["chunked_prefill"] is True
    assert stats["prefill_budget"] == 8
    assert stats["prefill_chunks_run"] == sched.prefill_chunks_run == 3
    assert stats["decode_steps_stalled"] == sched.decode_steps_stalled
    assert stats["prefill_tokens_per_step"] > 0


def test_explicit_chunked_on_unpaged_raises(olmo):
    """chunked_prefill=True without the paged pool is a config error
    (auto mode silently falls back instead)."""
    cfg, params = olmo
    with pytest.raises(ValueError, match="chunked prefill"):
        ContinuousScheduler(cfg, params, max_batch=2, max_ctx=64, bucket=16,
                            paged=False, chunked_prefill=True)
    sched = ContinuousScheduler(cfg, params, max_batch=2, max_ctx=64,
                                bucket=16, paged=False)
    assert sched.chunked_prefill is False


def test_engine_threads_knobs(olmo):
    """ServingEngine passes the chunked-prefill knobs through to its
    scheduler, and engine-level generate stays bit-identical to static."""
    cfg, params = olmo
    eng = ServingEngine(cfg, params, max_batch=2, bucket=16,
                        prefill_budget=6)
    reqs = [Request(0, PROMPT_LONG, max_new_tokens=6)]
    out = eng.generate(reqs)[0].out_tokens
    assert out == _solo(cfg, params, PROMPT_LONG, 6)
    sched = eng._sched
    assert sched.prefill_budget == 6 and sched.prefill_chunks_run > 0
    eng2 = ServingEngine(cfg, params, max_batch=2, bucket=16,
                        chunked_prefill=False)
    out2 = eng2.generate([Request(0, PROMPT_LONG, max_new_tokens=6)])
    assert out2[0].out_tokens == out
    assert eng2._sched.prefill_chunks_run == 0


PHASES = ("lifecycle", "admit", "chunk", "table_sync", "decode",
          "nan_check", "sample", "emit")


def _trace_spans(trace_dir) -> list:
    """(name, start_ns, end_ns, args) of the `serving.*` profiler spans in
    a CPU trace, in time order."""
    import glob
    import os

    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    events = [e for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith("serving.")]
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for e in sorted(events, key=lambda e: e.start_ns)]


def _traced_drain(sched, trace_dir):
    """Drain `sched` under the profiler: (finished requests, spans)."""
    jax.profiler.start_trace(str(trace_dir))
    try:
        done = _drain(sched)
    finally:
        jax.profiler.stop_trace()
    return done, _trace_spans(str(trace_dir))


@pytest.fixture(scope="module")
def traced(olmo, tmp_path_factory):
    """A long and a short prompt chunk-prefilled at budget 8 and decoded,
    under the profiler: (finished requests, spans)."""
    cfg, params = olmo
    sched = _sched(cfg, params, 8)
    sched.submit(Request(0, PROMPT_LONG, max_new_tokens=4))
    sched.submit(Request(1, PROMPT_SHORT, max_new_tokens=3))
    return _traced_drain(sched, tmp_path_factory.mktemp("trace"))


def test_step_phases_are_traced(traced):
    """Each phase of `step()` leaves a `serving.*` span: the chunk spans
    carry the chunks' starts and lengths, the decode spans' rows add up
    to the tokens decoded, and the step spans number the steps."""
    done, spans = traced
    names = {sp[0] for sp in spans}
    assert {"serving.step"} | {"serving." + p for p in PHASES} <= names
    chunks = [(a["start"], a["len"]) for n, _, _, a in spans
              if n == "serving.chunk"]
    assert sorted(chunks) == [(0, 8), (0, 8), (8, 8), (16, 7)]
    assert all(a["plans"] >= 1 for n, _, _, a in spans
               if n == "serving.chunk")
    rows = [a["rows"] for n, _, _, a in spans if n == "serving.decode"]
    # every token after a request's first comes from a decode row
    assert sum(rows) == sum(len(r.out_tokens) - 1 for r in done) > 0
    steps = [a for n, _, _, a in spans if n == "serving.step"]
    assert [a["step"] for a in steps] == list(range(1, len(steps) + 1))


@pytest.mark.parametrize("phase", PHASES)
def test_phase_spans_nest_in_steps(traced, phase):
    """Every span of a phase lies inside one `serving.step` span, and a
    step opens it at most once: one span per phase, never one per row or
    token. Retirement and admission run in every step."""
    _, spans = traced
    steps = [sp for sp in spans if sp[0] == "serving.step"]
    per_step = [0] * len(steps)
    for name, s, e, _ in spans:
        if name != "serving." + phase:
            continue
        owners = [i for i, st in enumerate(steps) if st[1] <= s and e <= st[2]]
        assert len(owners) == 1
        per_step[owners[0]] += 1
    assert max(per_step) == 1
    if phase in ("lifecycle", "admit"):
        assert min(per_step) == 1


def test_tracing_leaves_tokens_unchanged(olmo, traced):
    """The same requests served with no profiler running give the same
    tokens: the spans read host state only and change no device work."""
    cfg, params = olmo
    sched = _sched(cfg, params, 8)
    sched.submit(Request(0, PROMPT_LONG, max_new_tokens=4))
    sched.submit(Request(1, PROMPT_SHORT, max_new_tokens=3))
    untraced = {r.rid: r.out_tokens for r in _drain(sched)}
    assert untraced == {r.rid: r.out_tokens for r in traced[0]}


def test_speculation_round_is_traced(olmo, tmp_path):
    """With speculation on, each round runs in a `serving.spec` span
    inside its step, ahead of that step's trailing decode."""
    from repro.core.precision import QuantConfig

    cfg, params = olmo
    sched = _sched(cfg, params, 8, quant=QuantConfig(w_bits=8, a_bits=8),
                   speculate=2, draft_policy="w4a8")
    sched.submit(Request(0, PROMPT_SHORT, max_new_tokens=6))
    _, spans = _traced_drain(sched, tmp_path)
    steps = [sp for sp in spans if sp[0] == "serving.step"]
    specs = [sp for sp in spans if sp[0] == "serving.spec"]
    assert specs and sched.spec_verify_calls > 0
    for _, s, e, _ in specs:
        step = [st for st in steps if st[1] <= s and e <= st[2]]
        assert len(step) == 1
        decodes = [sp for sp in spans if sp[0] == "serving.decode"
                   and step[0][1] <= sp[1] <= step[0][2]]
        assert all(d[1] >= e for d in decodes)


def test_compile_counter_counts_backend_compiles():
    """`repro.serving.compiles` counts every backend compile in the
    process, with its seconds; a fresh compile moves both."""
    from repro.serving import compiles

    before = compiles.snapshot()
    jax.jit(lambda x: x * 3 + 1)(np.ones(7, np.float32)).block_until_ready()
    after = compiles.snapshot()
    assert after["compiles"] >= before["compiles"] + 1
    assert after["compile_seconds"] > before["compile_seconds"]
