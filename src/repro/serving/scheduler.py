"""Continuous-batching scheduler: request queue, slot table, mid-decode
admission, per-slot decode state, paged KV allocation, on-device sampling.

The serving analogue of the paper's headline property (the M4BRAM computes
while remaining fully usable as memory): the decode batch keeps computing
while individual slots are drained and refilled — no global barrier
between "batches" ever exists — and, with the paged cache, KV memory is
committed per *actual* request footprint instead of a worst-case `max_ctx`
reservation per slot.

Design:
  * ``max_batch`` decode slots. The jitted decode step always runs the
    full ``(max_batch, 1)`` token batch — ONE compiled decode signature
    for the scheduler's whole lifetime; slot occupancy changes, shapes
    never do. Free slots decode a dummy token whose output is discarded.
  * Admission: a waiting request is prefilled solo (B=1, prompt
    right-padded to a bucket, real length passed as ``lengths`` so pad
    slots never enter the cache or shift rope positions), and its KV /
    recurrent / RWKV state is scattered into the freed batch row
    (``kv_cache.scatter_into_slot`` / ``scatter_into_paged``). Only that
    row changes, so requests join mid-decode without perturbing live
    slots — a request's greedy output is bit-identical whether it is
    served solo, in a static batch, or admitted while other slots are
    deep into their decodes, and whether the cache is contiguous or paged.
  * Paged KV cache (full-attention archs, default): a shared block pool
    ``(L, num_blocks, block_size, NKV·H)`` plus per-slot block tables.
    Admission reserves the request's actual worst-case block count
    (``ceil((len + max_new - 1) / block_size)``) — when the pool can't
    cover it the request *queues* (no crash, no partial admission, no
    mid-decode deadlock). Blocks are allocated lazily: prompt blocks at
    admission, one more each time a decode step crosses a block boundary.
    Retirement frees a slot's blocks (and its unclaimed reservation)
    immediately.
  * Failure isolation: a request that can never fit (bucketed prompt or
    prompt + max_new beyond capacity) is marked failed (``Request.error``)
    and returned — it does not raise out of ``run()`` and live slots keep
    decoding.
  * Per-slot decode state: ``DecodeCache.pos``/``KVCache.slot_pos``/
    ``length`` all carry a batch axis; each slot's position advances
    independently of its neighbours.
  * Cross-request prefix caching (paged transformer archs, default on):
    a host-side index maps chain-hashes of block-sized token chunks to
    resident pool blocks, so a request whose prompt prefix was already
    prefilled — same system prompt, retried request — maps those blocks
    into its table instead of re-allocating and re-prefilling them.
    Ownership becomes refcounted: blocks are shared between rows,
    retirement *decrefs* instead of frees, unreferenced prefix blocks are
    retained in an LRU (freed lazily, evicted only under pool pressure),
    and a row that must append into a block it shares copies it first
    (copy-on-write). Admission prefills only the uncached suffix
    (``prefill_suffix``) and is still greedy bit-identical to a cold
    request — bf16 and int8 pools, solo / static / mid-decode admission.
  * Per-request precision tiers (paged archs, opt-in via ``tiers=``): a
    request may name a "wXaY" quality–latency class and is then served
    through a plane-truncated *view* of the one packed weight set
    (``core.precision.truncate_policy_view`` — buffers shared by
    identity, one extra jit trace per tier). ``step()`` groups live
    slots by tier and runs one decode call per group with non-group
    rows masked out of the pushed block table; a tier-T request in a
    mixed batch is greedy bit-identical to a solo engine whose whole
    policy is T. Speculation composes: the draft must truncate strictly
    below the slot's tier, verify runs at the slot's tier.
  * Sampling: vectorized on-device greedy / temperature / top-k with
    per-slot parameters and per-request ``(seed, rid)``-derived PRNG
    streams (``repro.serving.sampling``).

Driving it: ``submit()`` + ``step()`` give deterministic single-step
control (tests, custom loops); ``run()`` drains a workload, honouring
each request's ``arrival_time`` against the wall clock (staggered /
Poisson arrivals for the continuous-serving benchmark).
"""
from __future__ import annotations

import base64
import collections
import dataclasses
import hashlib
import json
import time
import warnings
from typing import Callable, Deque, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core.precision import (
    PrecisionPolicy,
    as_policy,
    degrade_order,
    parse_tier_specs,
    parse_tier_token,
    quant_token,
    truncate_policy_view,
)
from repro.core.quant import QuantConfig
from repro.core.quantized_linear import quantize_params_for_serving
from repro.models import build_model
from repro.models.kv_cache import (
    KVCache,
    PagedKVCache,
    copy_pool_block,
    scatter_into_paged,
    scatter_into_slot,
    scatter_suffix_into_paged,
    set_decode_positions,
    scale_slots,
    set_paged_row,
    split_heads,
    write_pool_block,
)
from repro.serving import sampling
from repro.serving.chaos import FaultInjector, InjectedFault
from repro.serving.speculative import (
    derive_draft_params,
    greedy_accept,
    parse_draft_spec,
)


def _contig_headroom() -> int:
    from repro.models.transformer import DECODE_HEADROOM

    return DECODE_HEADROOM


#: Preemption victim-selection policies: `most-blocks` frees the most pool
#: capacity per eviction, `lowest-tier` sheds the cheapest quality class
#: first, `latest-deadline` preempts the request with the most slack
#: (no-deadline requests first, then the latest deadline). `block-to-host`
#: selects like `most-blocks` but spills the victim's resident K/V blocks
#: to the host-RAM tier (needs ``host_pool_bytes``), so the requeued
#: victim resumes warm-from-host even when pool churn would have evicted
#: its blocks cold before re-admission.
VICTIM_POLICIES = ("most-blocks", "lowest-tier", "latest-deadline",
                   "block-to-host")

#: Versioned schema tag of the persisted prefix index (`save_index`).
INDEX_SCHEMA = "m4bram-prefix-index"
INDEX_VERSION = 1


@dataclasses.dataclass
class _HostBlock:
    """One pool block's K/V bytes parked in the host-RAM tier: plain
    numpy copies of the device planes (int8 codes + fp32 scale planes
    for a quantized pool) plus the digests that can claim it. The bytes
    are immutable — they were frozen device-side the moment a digest was
    registered — so swap-back (`write_pool_block`) reproduces the block
    verbatim and warm-from-host streams stay bitwise cold-identical."""

    k: np.ndarray                        # (L, block_size, NKV, H)
    v: np.ndarray
    k_scale: Optional[np.ndarray]        # (L, block_size, NKV, 1) fp32
    v_scale: Optional[np.ndarray]
    digests: set                         # chain digests resolving to it
    nbytes: int


@dataclasses.dataclass
class Request:
    """One generation request.

    ``arrival_time`` is seconds relative to the start of ``run()`` (0 =
    already queued). ``on_token`` streams tokens as they are sampled.
    ``t_first`` / ``t_done`` are filled by the scheduler (seconds since the
    run started) for latency accounting. ``error`` is set (and the request
    returned with no tokens) when it can never fit the cache — oversized
    requests are rejected individually instead of aborting the serve
    loop."""

    rid: int
    prompt: np.ndarray            # (T,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0                # 0 = no top-k filtering
    eos_id: Optional[int] = None
    # Per-request precision tier: a "wXaY" token (or QuantConfig) naming
    # one of the scheduler's configured `tiers`, served as a plane-
    # truncated view of the one packed weight set. None = the storage
    # policy. A request pinned to an unconfigured tier comes back failed
    # (`error` set), like any other individually-rejected request.
    tier: Union[None, str, QuantConfig] = None
    arrival_time: float = 0.0
    # Completion deadlines. `deadline_s` is wall-clock seconds after
    # `arrival_time` (evaluated only while `run()` drives the clock);
    # `deadline_steps` is a scheduler-step budget counted from `submit()`
    # (deterministic, works under manual `step()` loops too). A request
    # past either deadline — queued or mid-decode — is retired with
    # `error="deadline"`, its blocks freed exactly like a normal
    # retirement. None = no deadline.
    deadline_s: Optional[float] = None
    deadline_steps: Optional[int] = None
    on_token: Optional[Callable[["Request", int], None]] = None
    out_tokens: Optional[List[int]] = None
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    error: Optional[str] = None
    # Times this request was preempted under pool pressure (each one
    # requeued it as prompt ++ generated for a warm, bit-identical
    # resume) and, when graceful degradation kicked in, the tier it was
    # actually served at (sticky for the request's whole lifetime).
    preemptions: int = 0
    degraded_to: Optional[str] = None
    # Per-request speculative-decoding counters (filled when the
    # scheduler runs with `speculate`): draft tokens proposed for this
    # request and how many of them greedy verification accepted.
    spec_drafted: int = 0
    spec_accepted: int = 0

    @property
    def spec_acceptance_rate(self) -> float:
        return self.spec_accepted / self.spec_drafted if self.spec_drafted else 0.0

    @property
    def failed(self) -> bool:
        return self.error is not None


class ContinuousScheduler:
    """Continuous-batching scheduler (see the module docstring for the
    full design). Drive it by queueing `Request`s with `submit()` and
    advancing with `step()`, or hand a whole workload to `run()`.

    Keyword knobs: ``max_batch`` decode slots, ``max_ctx`` per-request
    position bound, ``bucket`` prefill padding granularity, ``paged``
    (None = auto: paged whenever the arch has a full-attention cache),
    ``block_size``/``pool_blocks`` pool geometry, and ``prefix_cache``
    (None = auto: on whenever the cache is paged and the arch supports
    suffix-only prefill — dense/token transformers; explicit True raises
    if unsupported)."""

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        max_batch: int = 8,
        max_ctx: int = 128,
        quant: Union[None, QuantConfig, PrecisionPolicy] = None,
        bucket: int = 64,
        seed: int = 0,
        on_token: Optional[Callable[[Request, int], None]] = None,
        paged: Optional[bool] = None,
        block_size: int = 16,
        pool_blocks: Optional[int] = None,
        prefix_cache: Optional[bool] = None,
        chunked_prefill: Optional[bool] = None,
        prefill_budget: int = 32,
        speculate: int = 0,
        draft_policy: Union[str, QuantConfig] = "w4a8",
        tiers: Union[None, str, Tuple] = None,
        preempt: Optional[bool] = None,
        victim_policy: str = "most-blocks",
        max_head_bypass: int = 4,
        degrade: bool = False,
        degrade_after: int = 2,
        chaos: Optional[FaultInjector] = None,
        host_pool_bytes: int = 0,
    ):
        self.cfg = cfg
        self.model = build_model(cfg)
        policy = as_policy(quant)
        if policy is not None:
            params = quantize_params_for_serving(params, policy,
                                                 min_size=1024)
        self.params = params
        self.max_batch = max_batch
        self.max_ctx = max_ctx
        self.bucket = bucket
        self.seed = seed
        self.on_token = on_token

        self._decode = jax.jit(self.model.decode_step, donate_argnums=(1,))

        # Fault-tolerance twin of `_decode`, traced inside
        # `use("reference")`, so an injected kernel fault can re-run the
        # SAME step on the pure-jnp reference backend (bitwise the same
        # logits/K-V). It wraps its own function: two jits of one function
        # share a trace, and would run whichever backend traced first.
        decode_step = self.model.decode_step

        def decode_on_reference(params, cache, tokens):
            from repro.kernels import get_registry
            with get_registry().use("reference"):
                return decode_step(params, cache, tokens)

        self._decode_ref = jax.jit(decode_on_reference, donate_argnums=(1,))
        self._scatter = jax.jit(scatter_into_slot, donate_argnums=(0,))
        self._scatter_paged = jax.jit(scatter_into_paged, donate_argnums=(0,))
        self._prefill_cache = {}

        # Cache flavour. Paged needs a full-attention KV cache (ring
        # buffers are already window-bounded) — eligible archs default to
        # paged. An int8 cache (cfg.kv_cache_quant) pages too: the pool
        # carries scale-plane blocks and the fused paged-attention kernel
        # dequantizes in-kernel.
        init_paged = getattr(self.model, "init_paged_cache", None)
        can_page = init_paged is not None and not cfg.attn_window
        if paged is None:
            paged = can_page
        elif paged and not can_page:
            raise ValueError(
                f"{cfg.name}: paged KV cache requires a full-attention "
                "cache (ring buffers and recurrent states are already "
                "footprint-bounded)"
            )
        self.paged = paged
        self.block_size = block_size

        # Prefix caching rides on the paged pool (shared blocks need block
        # tables + host-side ownership) and on suffix-only prefill; archs
        # where that is bit-identical to cold prefill advertise it as
        # `prefill_suffix` (model_zoo owns the eligibility rule).
        can_prefix = (
            paged
            and getattr(self.model, "prefill_suffix", None) is not None
        )
        if prefix_cache is None:
            prefix_cache = can_prefix
        elif prefix_cache and not can_prefix:
            raise ValueError(
                f"{cfg.name}: prefix caching requires the paged KV cache "
                "and an arch with suffix-only prefill (token-input, "
                "non-MoE full-attention transformer)"
            )
        self.prefix_cache = prefix_cache

        # Sarathi-style chunked prefill rides on the paged pool and on the
        # fused chunk kernel's model method (`prefill_chunk`, same
        # eligibility gate as prefill_suffix). Admission then enqueues a
        # chunk *plan* instead of prefilling solo: each step spends at
        # most `prefill_budget` prompt tokens of chunked prefill alongside
        # the decode step, so live slots stall at most one step per
        # budget's worth of admission prefill.
        can_chunk = (
            paged
            and getattr(self.model, "prefill_chunk", None) is not None
        )
        if chunked_prefill is None:
            chunked_prefill = can_chunk
        elif chunked_prefill and not can_chunk:
            raise ValueError(
                f"{cfg.name}: chunked prefill requires the paged KV cache "
                "and an arch with the fused chunk-prefill path "
                "(token-input, non-MoE full-attention transformer)"
            )
        if prefill_budget < 1:
            raise ValueError("prefill_budget must be >= 1")
        self.chunked_prefill = chunked_prefill
        self.prefill_budget = prefill_budget
        if chunked_prefill:
            self._chunk = jax.jit(self.model.prefill_chunk,
                                  donate_argnums=(1,))
        self._chunk_plans: Dict[int, dict] = {}   # slot → in-flight plan
        # Round-robin service order across in-flight chunk plans: the
        # serviced slot rotates to the back each step, so one long prompt
        # can't starve admissions queued behind it.
        self._chunk_queue: Deque[int] = collections.deque()
        self.prefill_chunks_run = 0
        self.decode_steps_stalled = 0
        self.prefill_chunk_tokens = 0
        # Steps on which a chunk actually ran — the denominator of the
        # interleave ratio. (steps_run keeps growing after the last plan
        # retires, which made the old tokens/steps_run ratio decay toward
        # zero instead of reporting the achieved interleave.)
        self.prefill_chunk_steps = 0

        # -- self-speculative decoding (draft = plane-truncated view) ----
        # Drafting reuses the decode step with *view* params (plane_lo on
        # every packed leaf — same weight bytes, one extra jit trace) and
        # verification reuses the chunked-prefill machinery with
        # all-position logits, so speculation needs the same capability
        # gate as chunked prefill plus a packed (quantized) weight set.
        if speculate:
            if speculate < 1:
                raise ValueError("speculate must be >= 1 (0 disables)")
            can_spec = (
                paged
                and getattr(self.model, "prefill_chunk_logits_multi",
                            None) is not None
            )
            if not can_spec:
                raise ValueError(
                    f"{cfg.name}: speculative decoding requires the paged "
                    "KV cache and the chunked-prefill verify path "
                    "(token-input, non-MoE full-attention transformer)"
                )
            # Raises with guidance when params carry no packed leaves
            # (serve with --quant) or the draft truncates nothing.
            self._draft_params, _ = derive_draft_params(self.params,
                                                        draft_policy)
            self._draft_cfg = parse_draft_spec(draft_policy)
            # Verify is batched: one multi-row call per tier group per
            # round (R = max_batch rows; non-verifying rows dead).
            self._verify = jax.jit(self.model.prefill_chunk_logits_multi,
                                   donate_argnums=(1,))
        self.speculate = int(speculate)
        self.draft_policy = draft_policy
        self.spec_draft_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_rounds = 0
        self.spec_verify_calls = 0     # multi-row verify dispatches
        self.spec_verify_rows = 0      # slots verified across those calls

        # -- per-request precision tiers (plane-truncated policy views) --
        # One packed weight set serves every configured tier: a tier view
        # shares the packed/scale buffers by identity and differs only in
        # pytree aux data (plane_lo), so each tier costs one extra jit
        # trace of the decode/prefill paths — never a second weight copy.
        # The key None is the base (storage-policy) tier.
        tier_cfgs: Dict[str, QuantConfig] = {}
        tier_views: Dict[Optional[str], object] = {None: self.params}
        if tiers:
            if not paged:
                raise ValueError(
                    f"{cfg.name}: per-request precision tiers need the "
                    "paged KV cache (tier groups are isolated by masked "
                    "block tables)"
                )
            for tcfg in parse_tier_specs(tiers):
                key = quant_token(tcfg)
                # Validates the tier is a pure plane-truncation of the
                # storage policy (packed params, whole-plane gap,
                # matching activation precision).
                view, _ = truncate_policy_view(self.params, tcfg)
                tier_cfgs[key] = tcfg
                tier_views[key] = view
        self._tier_cfgs = tier_cfgs
        self._tier_views = tier_views
        self.tiers = tuple(tier_cfgs)
        self._slot_tier: List[Optional[str]] = [None] * max_batch
        self.tier_counters: Dict[Optional[str], Dict[str, int]] = {
            k: {"requests": 0, "tokens": 0, "decode_calls": 0,
                "spec_draft_tokens": 0, "spec_accepted_tokens": 0}
            for k in [None, *tier_cfgs]
        }

        # -- lifecycle, preemption, degradation, fault injection ---------
        # Preemption (auto: on whenever the pool is paged): a pool-blocked
        # admission may evict one live victim per step, registering the
        # victim's resident K/V in the prefix index and requeueing it as
        # prompt ++ generated — resume is greedy bit-identical to an
        # uninterrupted run.
        if preempt is None:
            preempt = self.paged
        elif preempt and not self.paged:
            raise ValueError(
                f"{cfg.name}: preemption needs the paged KV cache (the "
                "contiguous scheduler has no pool pressure to relieve)")
        self.preempt = bool(preempt)
        if victim_policy not in VICTIM_POLICIES:
            raise ValueError(
                f"unknown victim_policy {victim_policy!r}; choose one of "
                f"{VICTIM_POLICIES}")
        self.victim_policy = victim_policy

        # -- host-RAM block tier under the paged pool --------------------
        # With a byte budget > 0, refcount-0 cached blocks evicted from
        # the device LRU move to a pinned host store (numpy copies of the
        # K/V planes, scale planes included) instead of dying, and a
        # prefix hit on a host-resident digest swaps the block back into
        # a free device slot at admission — warm-from-host is bitwise the
        # cold stream because the bytes round-trip verbatim.
        self.host_pool_bytes = int(host_pool_bytes or 0)
        if self.host_pool_bytes < 0:
            raise ValueError("host_pool_bytes must be >= 0 (0 disables "
                             "the host-RAM tier)")
        self.host_tier = bool(self.host_pool_bytes
                              and self.paged and self.prefix_cache)
        if self.host_pool_bytes and not self.host_tier:
            raise ValueError(
                f"{cfg.name}: the host-RAM block tier rides on the paged "
                "pool + prefix cache (spilled blocks are found by their "
                "chain digests); enable both or set host_pool_bytes=0")
        if victim_policy == "block-to-host" and not self.host_tier:
            raise ValueError(
                "victim_policy='block-to-host' spills the victim's K/V "
                "to the host tier; pass host_pool_bytes > 0 (and keep the "
                "paged pool + prefix cache on)")
        self._host_store: "collections.OrderedDict[int, _HostBlock]" = (
            collections.OrderedDict())          # insertion order = LRU
        self._host_index: Dict[bytes, int] = {} # digest → host id
        self._host_next_id = 0
        self.host_bytes = 0
        self.swap_ins = 0            # host → device block copies
        self.swap_outs = 0           # device → host spills
        self.host_evictions = 0      # host-tier cold deaths (budget)
        self.host_hit_blocks = 0
        self.host_hit_tokens = 0
        if self.paged:
            self._write_block = jax.jit(write_pool_block,
                                        donate_argnums=(0,))
        if max_head_bypass < 0:
            raise ValueError("max_head_bypass must be >= 0 (0 disables "
                             "head-of-line bypass)")
        self.max_head_bypass = int(max_head_bypass)
        if degrade_after < 1:
            raise ValueError("degrade_after must be >= 1")
        self.degrade = bool(degrade)
        self.degrade_after = int(degrade_after)
        if degrade:
            if not tier_cfgs:
                raise ValueError(
                    "degrade=True serves pressure admissions at the lowest "
                    "configured precision tier — pass tiers= / --tiers")
            self._degrade_to = quant_token(
                degrade_order(tier_cfgs.values())[-1])
        self.chaos = chaos
        self._cancelled: set = set()        # rids to retire at next step()
        self._step_calls = 0                # step() invocations (lifecycle clock)
        self._head_bypass = 0               # consecutive bypasses of the blocked head
        self._pressure_streak = 0           # consecutive pool-blocked steps
        self.preemptions = 0
        self.cancellations = 0
        self.deadline_misses = 0
        self.pool_pressure_events = 0
        self.queue_wait_steps = 0
        self.head_bypasses = 0
        self.degraded_requests = 0
        self.callback_errors = 0
        self.nan_logit_events = 0
        self.kernel_fallbacks = 0

        B = max_batch
        if paged:
            # Per-row virtual capacity = max_ctx rounded up to blocks; the
            # pool defaults to the contiguous worst case (every slot full)
            # — pass a smaller pool_blocks to overcommit.
            self._max_blocks = -(-max_ctx // block_size)
            usable = (pool_blocks if pool_blocks is not None
                      else max_batch * self._max_blocks)
            if usable < 1:
                raise ValueError("pool_blocks must be >= 1")
            self.pool_blocks = usable
            self.cache = init_paged(B, usable + 1, block_size,
                                    self._max_blocks)  # +1: trash block 0
            # Admission bound: max_ctx in every mode (the block-rounded
            # physical row is >= this), so static / contiguous / paged
            # agree on which requests fit.
            self._capacity = max_ctx
            self._free: List[int] = list(range(usable, 0, -1))
            # free + LRU-retained minus outstanding reservations: what
            # admission can still promise without deadlocking a live row.
            self._avail = usable
            self._reserved = np.zeros((B,), np.int64)
            self._block_tab = np.full((B, self._max_blocks), -1, np.int32)
            self._table_dirty = False
            self._peak_blocks = 0
            # -- prefix-cache / refcount state (host-side ownership) --
            self._refcnt = np.zeros((usable + 1,), np.int64)
            self._prefix_index: Dict[bytes, int] = {}   # chunk hash → block
            # block → every digest registered against it. One block can
            # serve several chain positions — e.g. a retired row's
            # straddle block carries the prompt-partial digest AND the
            # extended (prompt ++ generated) full-chunk digest. Once any
            # digest is attached the block's bytes are frozen:
            # `_ensure_private_block` copies-on-write even at refcount 1.
            self._block_hash: Dict[int, set] = {}
            self._lru: collections.OrderedDict = collections.OrderedDict()
            self._slot_hashes: List = [None] * B        # (full, partial)/slot
            self._suffix_cache = {}
            self._scatter_suffix = jax.jit(scatter_suffix_into_paged,
                                           donate_argnums=(0,))
            self._set_row = jax.jit(set_paged_row, donate_argnums=(0,))
            self._cow = jax.jit(copy_pool_block, donate_argnums=(0,))
            # One-write pos/length restore: speculation rollback and the
            # position fix-up between per-tier decode group calls.
            self._set_positions = jax.jit(set_decode_positions,
                                          donate_argnums=(0,))
            self.prefix_hit_blocks = 0
            self.prefix_hit_tokens = 0
            self.prompt_tokens_seen = 0
            self.cow_copies = 0
            self.prefix_evictions = 0
            # Bucketed tokens actually run through prefill at admission —
            # the deterministic admission-compute metric (a prefix hit
            # prefills only its suffix bucket; wall time on the interpret
            # backend is not a perf signal, this is).
            self.prefill_tokens_computed = 0
        else:
            # Fixed-shape contiguous state: every slot reserves a full
            # max_ctx(+headroom) row for its whole lifetime.
            self.cache = self.model.init_cache(max_batch, max_ctx)
            kv = self.cache.kv
            # Full-attention caches bound the absolute positions a slot
            # can reach (admission bound = max_ctx in every mode; the
            # physical row carries headroom beyond it); ring buffers and
            # recurrent states are position-unbounded.
            self._capacity = (
                max_ctx if isinstance(kv, KVCache) and kv.window == 0
                else None
            )

        self._pos_host = np.zeros((B,), np.int64)    # next write position
        self._cur = np.zeros((B, 1), np.int32)       # next input token/slot
        self._temps = np.zeros((B,), np.float32)
        self._top_ks = np.zeros((B,), np.int32)
        self._keys = np.zeros((B, 2), np.uint32)
        self._steps = np.zeros((B,), np.int32)       # per-request token ctr
        self._slots: List[Optional[Request]] = [None] * B
        self.waiting: Deque[Request] = collections.deque()
        self.steps_run = 0
        self.tokens_emitted = 0
        self._t0: Optional[float] = None             # set by run()

    # -- queue/slot accounting ---------------------------------------------

    @property
    def num_active(self) -> int:
        return sum(r is not None for r in self._slots)

    @property
    def num_waiting(self) -> int:
        return len(self.waiting)

    def submit(self, req: Request) -> None:
        """Queue a request for admission into the next free slot (FIFO;
        admission itself happens inside `step()` — including the prefix
        lookup, so a request submitted now can hit blocks that another
        request makes resident before a slot frees)."""
        req._submit_step = self._step_calls   # step-budget deadline epoch
        self.waiting.append(req)

    def cancel(self, rid: int) -> bool:
        """Request cancellation of `rid`. Processed at the start of the
        next `step()` — nothing mutates mid-step, so calling this from an
        `on_token` callback is safe. A queued request is dropped from the
        queue; a live one (including a mid-chunk-prefill plan) is retired
        with its blocks freed exactly like a normal retirement. Either way
        it comes back from `step()`/`run()` with ``error="cancelled"`` and
        whatever tokens it had emitted. Returns True iff `rid` is
        currently queued or in flight."""
        known = (any(r.rid == rid for r in self.waiting)
                 or any(r is not None and r.rid == rid for r in self._slots))
        if known:
            self._cancelled.add(rid)
        return known

    def _deadline_expired(self, req: Request,
                          now: Optional[float]) -> bool:
        if req.deadline_steps is not None:
            start = getattr(req, "_submit_step", None)
            if (start is not None
                    and self._step_calls - start > req.deadline_steps):
                return True
        if req.deadline_s is not None and now is not None:
            return now - req.arrival_time > req.deadline_s
        return False

    def _retire_abnormal(self, b: int, reason: str) -> Request:
        """Retire live row `b` off the normal finish path (cancellation,
        deadline, poisoned logits): mark the terminal state, free its
        blocks / reservation / chunk plan exactly like a normal
        retirement, and hand the request back with whatever tokens it
        emitted."""
        req = self._slots[b]
        req.error = reason
        if req.out_tokens is None:
            req.out_tokens = []
        req.t_done = self._now()
        self._release_slot(b)
        return req

    def _lifecycle_phase(self) -> List[Request]:
        """Process cancellations and deadline expiries — queued requests
        leave the queue, live rows are retired with their blocks freed —
        before this step admits or decodes anything."""
        out: List[Request] = []
        now = self._now()
        check_deadlines = any(
            r.deadline_s is not None or r.deadline_steps is not None
            for r in [*self.waiting,
                      *(r for r in self._slots if r is not None)])
        if not self._cancelled and not check_deadlines:
            return out
        keep: Deque[Request] = collections.deque()
        while self.waiting:
            r = self.waiting.popleft()
            if r.rid in self._cancelled:
                self.cancellations += 1
                self._fail(r, "cancelled")
                out.append(r)
            elif self._deadline_expired(r, now):
                self.deadline_misses += 1
                self._fail(r, "deadline")
                out.append(r)
            else:
                keep.append(r)
        self.waiting = keep
        for b, r in enumerate(self._slots):
            if r is None:
                continue
            if r.rid in self._cancelled:
                self.cancellations += 1
                out.append(self._retire_abnormal(b, "cancelled"))
            elif self._deadline_expired(r, now):
                self.deadline_misses += 1
                out.append(self._retire_abnormal(b, "deadline"))
        self._cancelled.clear()   # stale rids (already retired) drop here
        return out

    def _bucketed(self, n: int) -> int:
        return max(self.bucket, -(-n // self.bucket) * self.bucket)

    def _prefill_fn(self, length: int):
        # Key by *bucketed* length: callers pad to the bucket anyway, so
        # keying on the raw length would compile one identical executable
        # per distinct long-tail prompt length.
        length = self._bucketed(length)
        if length not in self._prefill_cache:
            self._prefill_cache[length] = jax.jit(self.model.prefill)
        return self._prefill_cache[length]

    def _now(self) -> Optional[float]:
        return None if self._t0 is None else time.perf_counter() - self._t0

    # -- paged-pool accounting ---------------------------------------------

    @staticmethod
    def _serve_tokens(req: Request) -> np.ndarray:
        """The token sequence admission serves for `req`: its prompt, plus
        any tokens it already generated before a preemption requeued it.
        Re-admitting `prompt ++ generated` is exactly what makes resume
        bit-identical — the resumed request prefills (or prefix-hits) the
        same positions an uninterrupted run would have resident, and its
        next sampled token is the (seed, rid, len(out))-stream token an
        uninterrupted run would draw."""
        if not req.out_tokens:
            return np.asarray(req.prompt)
        return np.concatenate([np.asarray(req.prompt, np.int64),
                               np.asarray(req.out_tokens, np.int64)])

    @staticmethod
    def _serve_len(req: Request) -> int:
        return len(req.prompt) + len(req.out_tokens or ())

    def _need_tokens(self, req: Request) -> int:
        # The first sampled token comes from the prefill logits and writes
        # no cache slot; only the remaining max_new - 1 decode inputs do.
        # max_new <= 0 still emits that prefill token, so it reserves like
        # max_new = 1 (anything less would under-reserve the prompt).
        # Invariant under preemption/resume: the served length grows by
        # exactly the tokens already emitted while the owed decode inputs
        # shrink by the same count.
        return len(req.prompt) + max(req.max_new_tokens, 1) - 1

    def _need_blocks(self, req: Request) -> int:
        return -(-self._need_tokens(req) // self.block_size)

    def _tier_error(self, req: Request) -> Optional[str]:
        """Validate + normalize `req.tier` into `req._tier_key` (the
        canonical "wXaY" counter/view key; None = storage policy).
        Non-None iff the tier can never be served here."""
        if req.tier is None:
            req._tier_key = None
        else:
            try:
                key = quant_token(parse_tier_token(req.tier))
            except ValueError as e:
                return f"request {req.rid}: bad precision tier: {e}"
            if key not in self._tier_views:
                have = sorted(self._tier_cfgs) or "none configured"
                return (f"request {req.rid}: unknown precision tier {key!r}; "
                        f"scheduler tiers: {have} — pass tiers= / --tiers to "
                        "serve this class")
            req._tier_key = key
        # A request admitted under graceful degradation stays degraded for
        # life: its emitted tokens and registered K/V are at the degraded
        # tier, so resuming (after a preemption) at the original tier
        # would splice two precisions into one stream.
        if req.degraded_to is not None:
            req._tier_key = req.degraded_to
        return None

    def _degrade_tier(self, req: Request) -> bool:
        """Point this admission attempt at the cheapest configured tier
        (graceful degradation under sustained pool pressure). Transient
        until the request actually admits — `_tier_error` recomputes
        `_tier_key` from `req.tier` on every attempt — and committed to
        `req.degraded_to` by the admission loop. Returns True iff the
        attempt was newly lowered."""
        low = self._degrade_to
        cur = req._tier_key
        cur_bits = (self._tier_cfgs[cur].w_bits if cur is not None
                    else 1 << 30)   # storage policy: above every tier
        if req.degraded_to is None and self._tier_cfgs[low].w_bits < cur_bits:
            req._tier_key = low
            return True
        return False

    # -- preemption: victim choice, warm-resume requeue ---------------------

    def _freeable(self, b: int) -> int:
        """Exact `_avail` increase releasing row `b` would produce: its
        unclaimed reservation plus every table block only it references
        (shared blocks survive under their other referencers, so evicting
        this row frees nothing there)."""
        row = self._block_tab[b]
        own = sum(1 for blk in row[row >= 0] if self._refcnt[int(blk)] == 1)
        return int(own) + int(self._reserved[b])

    @staticmethod
    def _deadline_rank(req: Request):
        """Slack ordering for the `latest-deadline` victim policy: bigger
        = more slack = preferred victim. No-deadline requests outrank any
        deadline; wall-clock deadlines rank by absolute deadline time;
        step budgets rank below wall-clock, by their budget horizon."""
        if req.deadline_s is None and req.deadline_steps is None:
            return (2, 0.0)
        if req.deadline_s is not None:
            return (1, req.arrival_time + req.deadline_s)
        return (0, float(getattr(req, "_submit_step", 0)
                         + req.deadline_steps))

    def _pick_victim(self, shortfall: int, exclude) -> Optional[int]:
        """Choose a preemption victim whose release alone covers the
        blocked admission's shortfall (a cascade of evictions for one
        admission is never worth the recompute — return None and let the
        head wait instead). Mid-chunk-plan rows are not preemptible
        (their resident blocks are partially written) and neither are
        rows admitted earlier in this same step."""
        cands = [b for b, r in enumerate(self._slots)
                 if r is not None and b not in self._chunk_plans
                 and b not in exclude and self._freeable(b) >= shortfall]
        if not cands:
            return None
        if self.victim_policy in ("most-blocks", "block-to-host"):
            # block-to-host selects like most-blocks; it differs in what
            # happens to the victim's K/V (spilled to host, not left to
            # LRU churn) — see `_preempt`.
            key = lambda b: (self._freeable(b), -b)       # noqa: E731
        elif self.victim_policy == "lowest-tier":
            def key(b):
                t = self._slot_tier[b]
                bits = (self._tier_cfgs[t].w_bits if t is not None
                        else 1 << 30)
                return (-bits, self._freeable(b), -b)
        else:  # latest-deadline
            def key(b):
                return (self._deadline_rank(self._slots[b]),
                        self._freeable(b), -b)
        return max(cands, key=key)

    def _preempt(self, b: int) -> None:
        """Preempt row `b` under pool pressure: release the slot — which
        registers its resident prompt+generated blocks in the prefix
        index (`_register_retired`) — and requeue the request at the BACK
        of the waiting queue as prompt ++ generated. Re-admission rides
        the ordinary suffix-only warm path over those registered blocks
        (or recomputes them cold if they were evicted meanwhile); either
        way the resumed stream is bitwise the uninterrupted one.

        With ``victim_policy="block-to-host"`` the victim's now
        refcount-0 resident blocks are spilled to the host tier
        immediately instead of sitting in the device LRU: pool churn
        between now and re-admission can no longer evict them cold, so
        the resume is warm-from-host at worst (same bits — the swap-back
        writes the spilled bytes verbatim)."""
        req = self._slots[b]
        self.preemptions += 1
        req.preemptions += 1
        row = self._block_tab[b]
        row_blocks = [int(blk) for blk in row[row >= 0]]
        self._release_slot(b)
        if self.victim_policy == "block-to-host":
            for blk in row_blocks:
                if blk in self._lru and blk in self._block_hash:
                    self._lru.pop(blk)
                    self._spill_block(blk)
                    self._free.append(blk)
        self.waiting.append(req)

    def _bypass_candidate(self, deg: bool):
        """Head-of-line mitigation: when the queue head is pool-blocked,
        find the first later request that is admissible and fits the
        current capacity — bounded to `max_head_bypass` consecutive
        bypasses so a large head is never starved by a stream of small
        arrivals. Returns (queue index, match, newly-degraded) or
        (None, None, False)."""
        if self._head_bypass >= self.max_head_bypass:
            return None, None, False
        for i in range(1, len(self.waiting)):
            r = self.waiting[i]
            if self._reject_reason(r) is not None:
                continue   # rejected for real when it reaches the head
            d = self._degrade_tier(r) if deg else False
            m = self._match_prefix(r)
            if m[2] + m[3] <= self._avail:
                return i, m, d
        return None, None, False

    # -- fault-tolerant decode dispatch -------------------------------------

    def _decode_call(self, params, cur) -> jnp.ndarray:
        """Jitted decode dispatch with the kernel fault seam: an injected
        chaos failure raised AT dispatch (before the donated cache enters
        the jitted call, so its buffers stay valid) is caught and the
        same step re-runs on the pure-jnp `reference` backend — bitwise
        the same logits and K/V writes, so one flaky backend call degrades
        to a slow call, never to a lost request or a broken stream."""
        try:
            if self.chaos is not None and self.chaos.fire("kernel"):
                raise InjectedFault("kernel dispatch")
            self.cache, logits = self._decode(params, self.cache,
                                              jnp.asarray(cur))
        except InjectedFault:
            self.kernel_fallbacks += 1
            self.cache, logits = self._decode_ref(params, self.cache,
                                                  jnp.asarray(cur))
        return logits

    def _reject_reason(self, req: Request) -> Optional[str]:
        """Non-None iff the request can never be served by this scheduler
        (vs. transiently waiting for pool blocks)."""
        err = self._tier_error(req)
        if err is not None:
            return err
        if self._capacity is None:
            return None
        need = self._need_tokens(req)
        if self.paged:
            if need > self._capacity or self._need_blocks(req) > self.pool_blocks:
                return (f"request {req.rid}: prompt ({len(req.prompt)}) + "
                        f"max_new_tokens ({req.max_new_tokens}) needs {need} "
                        f"cache slots, beyond capacity ({self._capacity} "
                        f"per slot, {self.pool_blocks * self.block_size} "
                        "pooled); raise max_ctx / pool_blocks")
            return None
        L = self._bucketed(len(req.prompt))
        # The solo prefill array carries L + headroom slots and must fit
        # the max_ctx + headroom row, hence the L > max_ctx bound.
        if L > self.max_ctx or need > self._capacity:
            return (f"request {req.rid}: bucketed prompt ({L}) or prompt + "
                    f"max_new_tokens ({need} slots) exceeds cache capacity "
                    f"(max_ctx {self.max_ctx}, {self._capacity} slots); "
                    "raise max_ctx")
        return None

    @property
    def _live_blocks(self) -> int:
        """Pool blocks referenced by at least one row's table (LRU-retained
        prefix blocks are resident but reclaimable, so they don't count)."""
        return self.pool_blocks - len(self._free) - len(self._lru)

    def _touch_peak(self) -> None:
        self._peak_blocks = max(self._peak_blocks, self._live_blocks)

    def _evict_lru(self) -> None:
        """Reclaim the least-recently-used retained prefix block and hand
        it back to the free list. Only refcount-0 blocks ever sit in the
        LRU, so eviction can never pull a block out from under a live row
        or an admission reservation (`_avail` already counts LRU blocks
        as reclaimable). With the host tier on, the block's bytes and
        digests move to the host store instead of dying — a later hit on
        the digest chain swaps them back; without it (or once the host
        budget is exhausted) the digests are dropped cold."""
        if not self._lru:
            raise RuntimeError(
                "paged pool invariant violated: reservation accounting "
                "should guarantee a free or evictable block"
            )
        blk, _ = self._lru.popitem(last=False)
        if self.host_tier and blk in self._block_hash:
            self._spill_block(blk)
        else:
            for h in self._block_hash.pop(blk, ()):
                self._prefix_index.pop(h, None)
            self.prefix_evictions += 1
        self._free.append(blk)

    # -- host-RAM block tier: spill, budget, swap-back -----------------------

    def _host_block_nbytes(self) -> int:
        """Host bytes one spilled block occupies (K + V planes across all
        layers, plus the fp32 scale planes of a quantized pool)."""
        kv = self.cache.kv
        per = 2 * kv.k.shape[0] * int(np.prod(kv.k.shape[2:])) \
            * kv.k.dtype.itemsize
        if kv.quantized:
            per += 2 * kv.k_scale.shape[0] \
                * int(np.prod(kv.k_scale.shape[2:])) \
                * kv.k_scale.dtype.itemsize
        return per

    def _host_planes(self, blk: int) -> tuple:
        """Pool block `blk`'s (k, v, k_scale, v_scale) across every layer
        as host numpy in the host tier's per-slot layout — codes
        (L, block_size, NKV, H), scales (L, block_size, NKV, 1), None for
        a bf16 pool — the bytes of the pool's rows, regrouped."""
        kv = self.cache.kv
        codes = tuple(split_heads(np.asarray(a[:, blk]), self.cfg.n_kv_heads)
                      for a in (kv.k, kv.v))
        scales = tuple(None if a is None else
                       scale_slots(np.asarray(a[:, blk]), self.block_size)
                       for a in (kv.k_scale, kv.v_scale))
        return codes + scales

    def _spill_block(self, blk: int) -> None:
        """Move pool block `blk`'s bytes and digests to the host store.
        The caller owns the block's pool bookkeeping (it must already be
        out of the LRU and about to join the free list); this moves the
        digest ownership: entries leave `_prefix_index`/`_block_hash` and
        land in `_host_index`, so no digest ever resolves to both a live
        device block and a stale host copy."""
        digests = self._block_hash.pop(blk)
        for h in digests:
            self._prefix_index.pop(h, None)
        k, v, ks, vs = self._host_planes(blk)
        entry = _HostBlock(
            k=k, v=v, k_scale=ks, v_scale=vs,
            digests=set(digests),
            nbytes=self._host_block_nbytes(),
        )
        self._add_host_entry(entry)
        self.swap_outs += 1

    def _add_host_entry(self, entry: _HostBlock) -> None:
        """Insert a block into the host store (most-recent end) and
        enforce the byte budget by evicting the oldest entries cold."""
        hid = self._host_next_id
        self._host_next_id += 1
        self._host_store[hid] = entry
        self.host_bytes += entry.nbytes
        for h in entry.digests:
            self._host_index[h] = hid
        while self.host_bytes > self.host_pool_bytes and self._host_store:
            old_id, old = self._host_store.popitem(last=False)
            for h in old.digests:
                self._host_index.pop(h, None)
            self.host_bytes -= old.nbytes
            self.host_evictions += 1
            self.prefix_evictions += 1   # a cached chunk died for real

    def _pop_host_entry(self, hid: int) -> _HostBlock:
        """Remove a host entry (swap-back claimed it): its digests leave
        the host index FIRST, so allocator work that spills other blocks
        mid-swap-in can never budget-evict the entry being claimed."""
        entry = self._host_store.pop(hid)
        for h in entry.digests:
            self._host_index.pop(h, None)
        self.host_bytes -= entry.nbytes
        return entry

    def _drop_host_digest(self, h: bytes) -> None:
        """Device-side registration of digest `h` supersedes any host
        copy (the freshly written device block serves future hits): drop
        the digest from its host entry, and the entry once no digest can
        reach it — the exclusivity half of the host-tier invariant."""
        hid = self._host_index.pop(h, None)
        if hid is None:
            return
        entry = self._host_store[hid]
        entry.digests.discard(h)
        if not entry.digests:
            del self._host_store[hid]
            self.host_bytes -= entry.nbytes

    def _swap_in_hits(self, slot: int, host_hits, n_full: int) -> None:
        """Swap host-resident prefix blocks back into the pool for row
        `slot`: each hit allocates a device block from the row's
        reservation (the ordinary `_alloc_block` path — eviction pressure
        this causes may itself spill other LRU blocks to host) and writes
        the host bytes back verbatim (`write_pool_block`). Full-chunk
        hits re-register their digests against the new device block, so
        concurrent same-prefix admissions share it like any cached block.
        A partial-chunk hit is NOT re-registered: the claiming row will
        append decode tokens into that block in place — exactly the
        "live row's partial block is never shared" invariant of the
        device path — and retirement re-registers the partial digest over
        the final bytes as usual."""
        for j, hid in host_hits:
            entry = self._pop_host_entry(hid)
            self._alloc_block(slot, j)
            blk = int(self._block_tab[slot, j])
            self.cache = self._write_block(
                self.cache, blk, entry.k, entry.v,
                entry.k_scale, entry.v_scale)
            self.swap_ins += 1
            self.host_hit_blocks += 1
            if j < n_full:
                for h in entry.digests:
                    self._prefix_index[h] = blk
                    self._block_hash.setdefault(blk, set()).add(h)

    # -- durable prefix index: export / import / save / load -----------------

    def _pool_geometry(self) -> dict:
        kv = self.cache.kv
        shape = (kv.k.shape[0], self.block_size, self.cfg.n_kv_heads,
                 self.cfg.head_dim)
        return {"block_size": self.block_size,
                "quantized": bool(kv.quantized),
                "kv_shape": list(int(x) for x in shape),
                "kv_dtype": str(kv.k.dtype)}

    def export_index(self) -> dict:
        """Snapshot every cached chunk the scheduler could serve a hit
        from — host-tier entries AND hashed device blocks (live or
        LRU-retained) — as a JSON-able dict: a versioned schema header
        with the pool geometry, a block list of base64 K/V bytes, and a
        digest → block-index map. Feeding it to `import_index` on a
        fresh scheduler (a rebuild for `max_ctx` growth, or a process
        restart via `save_index`/`load_index`) repopulates the HOST tier,
        so the first same-prefix admission swaps the chunks back in
        instead of re-prefilling cold. Digest chains are tier-scoped at
        hash time, so mixed-tier indexes survive round trips unchanged."""

        def b64(a) -> Optional[str]:
            if a is None:
                return None
            return base64.b64encode(np.ascontiguousarray(a).tobytes()) \
                .decode("ascii")

        blocks: List[dict] = []
        digests: Dict[str, int] = {}
        if self.paged:
            for blk, hs in self._block_hash.items():
                entry = dict(zip(("k", "v", "k_scale", "v_scale"),
                                 map(b64, self._host_planes(blk))))
                idx = len(blocks)
                blocks.append(entry)
                for h in hs:
                    digests[h.hex()] = idx
            for hb in self._host_store.values():
                entry = {"k": b64(hb.k), "v": b64(hb.v),
                         "k_scale": b64(hb.k_scale),
                         "v_scale": b64(hb.v_scale)}
                idx = len(blocks)
                blocks.append(entry)
                for h in hb.digests:
                    digests[h.hex()] = idx
        return {"schema": INDEX_SCHEMA, "version": INDEX_VERSION,
                **self._pool_geometry(),
                "blocks": blocks, "digests": digests}

    def import_index(self, data) -> int:
        """Load an `export_index` snapshot into the HOST tier (entries
        count against ``host_pool_bytes`` like any spill; the oldest are
        budget-evicted first when the snapshot exceeds it). Returns the
        number of digests now resolvable. NEVER raises on bad input —
        truncated or garbage files, a wrong schema version, a digest
        referencing an out-of-range block, or a geometry mismatch
        (different pool dtype/shape/block size) each warn and cold-start
        with 0 loaded, because a stale index must not take down a serving
        process that can simply re-prefill."""
        if not self.host_tier:
            if data:
                warnings.warn("prefix-index import skipped: the host-RAM "
                              "tier is disabled (host_pool_bytes=0)")
            return 0
        if not isinstance(data, dict) \
                or data.get("schema") != INDEX_SCHEMA:
            warnings.warn("prefix-index import: unrecognized payload "
                          "(not an index snapshot) — cold start")
            return 0
        if data.get("version") != INDEX_VERSION:
            warnings.warn(f"prefix-index import: unsupported version "
                          f"{data.get('version')!r} (want {INDEX_VERSION})"
                          " — cold start")
            return 0
        geo = self._pool_geometry()
        theirs = {k: data.get(k) for k in geo}
        if theirs != geo:
            warnings.warn(f"prefix-index import: pool geometry mismatch "
                          f"({theirs} != {geo}) — cold start")
            return 0
        blocks = data.get("blocks")
        digests = data.get("digests")
        if not isinstance(blocks, list) or not isinstance(digests, dict):
            warnings.warn("prefix-index import: malformed blocks/digests "
                          "tables — cold start")
            return 0
        by_block: Dict[int, set] = {}
        try:
            for hx, idx in digests.items():
                idx = int(idx)
                if not 0 <= idx < len(blocks):
                    warnings.warn(
                        f"prefix-index import: digest {hx!r} references "
                        f"out-of-range block {idx} (have {len(blocks)}) "
                        "— cold start")
                    return 0
                by_block.setdefault(idx, set()).add(bytes.fromhex(hx))
        except (TypeError, ValueError) as e:
            warnings.warn(f"prefix-index import: bad digest table ({e}) "
                          "— cold start")
            return 0
        L, bs, nkv, hd = geo["kv_shape"]
        kv_dt = self.cache.kv.k.dtype
        loaded_digests = 0
        entries: List[_HostBlock] = []
        try:
            for idx, hs in by_block.items():
                e = blocks[idx]
                k = np.frombuffer(base64.b64decode(e["k"]),
                                  dtype=kv_dt).reshape(L, bs, nkv, hd)
                v = np.frombuffer(base64.b64decode(e["v"]),
                                  dtype=kv_dt).reshape(L, bs, nkv, hd)
                ks = vs = None
                if geo["quantized"]:
                    ks = np.frombuffer(base64.b64decode(e["k_scale"]),
                                       dtype=np.float32) \
                        .reshape(L, bs, nkv, 1)
                    vs = np.frombuffer(base64.b64decode(e["v_scale"]),
                                       dtype=np.float32) \
                        .reshape(L, bs, nkv, 1)
                live = {h for h in hs if h not in self._prefix_index
                        and h not in self._host_index}
                if not live:
                    continue   # fresher resident copy wins
                entries.append(_HostBlock(
                    k=k, v=v, k_scale=ks, v_scale=vs, digests=live,
                    nbytes=self._host_block_nbytes()))
                loaded_digests += len(live)
        except (KeyError, TypeError, ValueError) as e:
            warnings.warn(f"prefix-index import: corrupt block payload "
                          f"({e}) — cold start")
            return 0
        for entry in entries:
            self._add_host_entry(entry)
        return loaded_digests

    def save_index(self, path) -> int:
        """Persist `export_index` to `path` as JSON. Returns the number
        of digests written."""
        data = self.export_index()
        with open(path, "w") as f:
            json.dump(data, f)
            f.write("\n")
        return len(data["digests"])

    def load_index(self, path) -> int:
        """Load a `save_index` file into the host tier via
        `import_index`. Missing, truncated, or corrupt files warn and
        cold-start with 0 — never raise (robustness contract shared with
        the kernel registry's plan cache)."""
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError) as e:
            warnings.warn(f"prefix-index load from {path!s} failed ({e}) "
                          "— cold start")
            return 0
        return self.import_index(data)

    def _take_free_block(self) -> int:
        if not self._free:
            self._evict_lru()
        return self._free.pop()

    def _alloc_block(self, slot: int, j: int) -> None:
        blk = self._take_free_block()
        self._refcnt[blk] = 1
        self._block_tab[slot, j] = blk
        self._reserved[slot] -= 1
        self._table_dirty = True
        self._touch_peak()

    def _decref(self, blk: int) -> None:
        """Drop one table reference. At refcount 0 a prefix-cached block is
        *retained* (LRU, evicted lazily under pool pressure so a repeat of
        the same prompt still hits); an uncached block frees immediately."""
        self._refcnt[blk] -= 1
        if self._refcnt[blk] == 0:
            if blk in self._block_hash:
                self._lru[blk] = None        # most-recently-used end
            else:
                self._free.append(blk)
            self._avail += 1

    def _ensure_private_block(self, b: int, j: int) -> None:
        """Make virtual block `j` of row `b` writable: allocate it if the
        table entry is empty, and copy-on-write when it is a block the row
        shares — with other rows (refcount > 1) or with the prefix cache
        itself (a registered digest describes its bytes, so even a sole
        referencer must not append in place: a future claimant of that
        digest trusts the covered slots). The sharers/cache keep the
        pristine block, the appender gets a private copy (charged to its
        reservation like any other allocation)."""
        blk = int(self._block_tab[b, j])
        if blk < 0:
            self._alloc_block(b, j)
        elif self._refcnt[blk] > 1 or blk in self._block_hash:
            dst = self._take_free_block()
            self._refcnt[dst] = 1
            self.cache = self._cow(self.cache, blk, dst)
            self._block_tab[b, j] = dst
            self._decref(blk)
            self._reserved[b] -= 1
            self._table_dirty = True
            self.cow_copies += 1
            self._touch_peak()

    def _alloc_boundary_blocks(self) -> None:
        """Back the position each live slot writes this step."""
        for b, req in enumerate(self._slots):
            if req is None or b in self._chunk_plans:
                continue  # mid-chunk-prefill rows don't decode-append yet
            j = int(self._pos_host[b]) // self.block_size
            if j >= self._max_blocks:
                continue
            self._ensure_private_block(b, j)

    def _alloc_blocks_through(self, b: int, last_pos: int) -> None:
        """Back every position row `b` writes in a speculation round —
        [pos, last_pos] spans the draft writes and the verify chunk — with
        writable (private) blocks, before any of them runs. Positions
        backed for draft tokens that verification then rejects stay
        allocated: they sit inside the row's admission reservation and the
        row's subsequent decode steps write them next anyway."""
        first = int(self._pos_host[b]) // self.block_size
        last = min(last_pos // self.block_size, self._max_blocks - 1)
        for j in range(first, last + 1):
            self._ensure_private_block(b, j)

    def _push_spec_table(self, spec_slots) -> None:
        """Device block table for the draft phase: only speculating rows
        keep their real blocks. Every other row — live decoders, chunk
        plans, free slots — is masked to -1, so the lockstep draft decode
        steps route their writes to the trash block and attend over
        nothing (their logits are discarded and their host `_cur` is
        untouched). Without this, a draft step would append *draft-policy*
        K/V at a non-speculating row's live position — possibly into a
        block it shares with other rows. Marks the table dirty so the
        real table is re-pushed before the normal decode."""
        tab = self._block_tab.copy()
        for b in range(self.max_batch):
            if b not in spec_slots:
                tab[b, :] = -1
        self.cache = dataclasses.replace(
            self.cache,
            kv=dataclasses.replace(self.cache.kv,
                                   block_table=jnp.asarray(tab)),
        )
        self._table_dirty = True

    def _sync_table(self) -> None:
        if self._table_dirty:
            tab = self._block_tab
            if self._chunk_plans:
                # A mid-chunk-prefill row is invisible to the decode step:
                # its DEVICE table row stays all -1 (decode's cache write
                # routes to the trash block, its attention sees no keys,
                # its logits are discarded). The chunk calls receive the
                # real blocks explicitly, so the host table is untouched.
                tab = tab.copy()
                for b in self._chunk_plans:
                    tab[b, :] = -1
            # A copy (`jnp.array`): the host keeps rewriting `_block_tab`
            # while steps that read this device table are still queued,
            # and on the CPU `jnp.asarray` may alias it.
            self.cache = dataclasses.replace(
                self.cache,
                kv=dataclasses.replace(
                    self.cache.kv, block_table=jnp.array(tab)
                ),
            )
            self._table_dirty = False

    def _release_slot(self, b: int) -> None:
        """Retire row `b`: *decref* its blocks (shared prefix blocks stay
        live under their other referencers; last-reference prefix blocks
        are retained in the LRU; everything else frees) and return its
        unclaimed reservation. The row's resident content — prompt AND
        decode-generated tokens — is registered in the prefix index here,
        not at admission, because a live row appends into its tail block
        in place; once the row stops writing, every written slot is
        immutable and safe to share (`_register_retired`). A row retired
        mid-chunk-plan (cancel/deadline/preemption) drops its service-
        queue entry and registers nothing new: its unwritten tail blocks
        hold no valid bytes (blocks earlier chunks fully covered were
        already registered progressively and stay valid)."""
        req = self._slots[b]
        tier = self._slot_tier[b]
        self._slots[b] = None
        self._slot_tier[b] = None
        if not self.paged:
            return
        plan = self._chunk_plans.pop(b, None)
        if plan is not None:
            self._chunk_queue.remove(b)
            self._slot_hashes[b] = None
        if self.prefix_cache:
            self._register_retired(b, req, tier)
        self._slot_hashes[b] = None
        row = self._block_tab[b]
        for blk in row[row >= 0]:
            self._decref(int(blk))
        row[:] = -1
        self._avail += int(self._reserved[b])
        self._reserved[b] = 0
        self._table_dirty = True

    # -- prefix cache: hash index, matching, claiming, registration --------

    def _hash_chunks(
        self, prompt, tier: Optional[str] = None
    ) -> Tuple[List[bytes], Optional[bytes]]:
        """Chain-hashes of the prompt at block granularity: one digest per
        *full* block-sized token chunk (each digest covers every token up
        to and including its chunk, so a hit at chunk j implies the whole
        prefix matches) plus one for the trailing partial chunk, tagged so
        a partial run never aliases a full block. The chain is seeded with
        the request's precision tier: a tier-T prompt's hidden states —
        and therefore its pool K/V bytes — differ from tier-T', so
        cross-tier requests must never share blocks (tier-None seeds are
        unchanged from the pre-tier format)."""
        toks = np.ascontiguousarray(np.asarray(prompt, np.int32))
        bs = self.block_size
        full, h = [], b"m4bram-prefix" + (tier.encode() if tier else b"")
        for j in range(len(toks) // bs):
            h = hashlib.blake2b(h + toks[j * bs:(j + 1) * bs].tobytes(),
                                digest_size=16).digest()
            full.append(h)
        r = len(toks) % bs
        partial = (
            hashlib.blake2b(h + toks[len(toks) - r:].tobytes() + b"#partial",
                            digest_size=16).digest()
            if r else None
        )
        return full, partial

    def _req_hashes(self, req: Request) -> Tuple[List[bytes], Optional[bytes]]:
        """Chain hashes for `req`'s *served* tokens (prompt ++ generated),
        memoized on the request object — the pool-full path re-checks the
        queue head every step, and the digests depend only on (served
        length, block_size, tier); the length key invalidates the memo
        when a preemption requeues the request with more tokens."""
        tier = getattr(req, "_tier_key", None)
        key = (self.block_size, tier, self._serve_len(req))
        cached = getattr(req, "_prefix_hashes", None)
        if cached is None or cached[0] != key:
            cached = (key, self._hash_chunks(self._serve_tokens(req), tier))
            req._prefix_hashes = cached
        return cached[1]

    def _match_prefix(self, req: Request):
        """Longest resident prefix for `req` — pure lookup, no allocator
        mutation. Returns (hits [(virtual j, pool block)], resident token
        count, revive count = hits that must leave the LRU, reservation =
        blocks the row may still allocate: uncovered virtual blocks plus
        one for a potential copy-on-write of a shared partial block,
        hashes = the (full, partial) chain digests, reused at
        registration time, host_hits [(virtual j, host id)] = chain
        positions resident in the host-RAM tier rather than the pool).

        The chain walk consults the device index first and falls back to
        the host index per digest, so a chain that is part-device,
        part-host still matches end to end. Host hits are counted in
        `resident` (their bytes swap back before prefill) but NOT
        subtracted from the reservation: each one consumes a device block
        through the ordinary `_alloc_block` at swap-in."""
        need = self._need_blocks(req)
        if not self.prefix_cache:
            return [], 0, 0, need, None, []
        hashes = self._req_hashes(req)
        full, partial = hashes
        hits: List[Tuple[int, int]] = []
        host_hits: List[Tuple[int, int]] = []
        for j, h in enumerate(full):
            blk = self._prefix_index.get(h)
            if blk is not None:
                hits.append((j, blk))
                continue
            hid = self._host_index.get(h) if self.host_tier else None
            if hid is not None:
                host_hits.append((j, hid))
                continue
            break
        dev_full = len(hits)     # device full-chunk hits claim for free
        full_hits = dev_full + len(host_hits)
        resident = full_hits * self.block_size
        if full_hits == len(full) and partial is not None:
            blk = self._prefix_index.get(partial)
            if blk is not None:
                hits.append((full_hits, blk))
                resident = self._serve_len(req)
            elif self.host_tier and partial in self._host_index:
                host_hits.append((full_hits, self._host_index[partial]))
                resident = self._serve_len(req)
        revive = sum(1 for _, b in hits if self._refcnt[b] == 0)
        return hits, resident, revive, need - dev_full, hashes, host_hits

    def _claim_hits(self, slot: int, hits) -> None:
        """Map matched pool blocks into row `slot`'s table, incref'ing
        each; refcount-0 blocks are revived out of the LRU (which consumes
        one unit of reclaimable capacity — accounted against `_avail`)."""
        for j, blk in hits:
            if self._refcnt[blk] == 0:
                self._lru.pop(blk)
                self._avail -= 1
            self._refcnt[blk] += 1
            self._block_tab[slot, j] = blk
        if hits:
            self._table_dirty = True

    def _register_full(self, slot: int, limit: Optional[int] = None) -> None:
        """Index row `slot`'s full prompt blocks once their content is
        final (appends only ever land past the prompt). Solo/suffix
        admissions register everything at admission; chunked plans pass
        ``limit`` to register progressively — only blocks the landed
        chunks fully cover, since the straddled tail block is still
        rewritten by the next chunk."""
        full, _ = self._slot_hashes[slot]
        if limit is not None:
            full = full[:limit]
        for j, h in enumerate(full):
            blk = int(self._block_tab[slot, j])
            if blk < 0 or h in self._prefix_index:
                continue
            # An already-hashed block may take a second digest (the
            # straddle block of a retired row carries both the prompt-
            # partial and the extended full-chunk digest); its bytes are
            # frozen from the first registration on. A host copy of the
            # digest is superseded by the fresh device bytes.
            self._drop_host_digest(h)
            self._prefix_index[h] = blk
            self._block_hash.setdefault(blk, set()).add(h)

    def _register_partial(self, slot: int) -> None:
        """Index the trailing partial prompt block at *retirement*. While
        the row lives it appends decode tokens into this block in place;
        deferring registration means a live row's partial block is never
        shared, so in-place appends need no reservation headroom beyond
        the exact `need - full_hits` the allocator holds."""
        if self._slot_hashes[slot] is None:
            return
        full, partial = self._slot_hashes[slot]
        if partial is None:
            return
        j = len(full)
        if j >= self._max_blocks:
            return
        blk = int(self._block_tab[slot, j])
        if blk < 0 or partial in self._prefix_index:
            return
        self._drop_host_digest(partial)
        self._prefix_index[partial] = blk
        self._block_hash.setdefault(blk, set()).add(partial)

    def _register_retired(self, b: int, req: Optional[Request],
                          tier: Optional[str]) -> None:
        """Register row `b`'s resident blocks — prompt AND decode-
        generated — in the prefix index at retirement or preemption.

        Two digest chains are registered, in priority order:

        1. the admission chain — the prompt's full blocks plus its partial
           tail, now immutable. This keeps the original contract: a later
           *same-prompt* request hits the whole prompt, shares the partial
           block, and copies-on-write when it appends.
        2. the extended chain over ``serve_tokens[:pos]`` (pos = next
           write position: everything written, excluding the final
           sampled token whose K/V never lands). Blocks holding generated
           tokens get fresh digests, and the straddle block (prompt tail
           + first generated tokens) takes the extended full-chunk digest
           as a *second* hash. A later admission of ``prompt ++
           generated`` — a preempted request resuming, or a multi-turn
           conversation re-submitting its history — then claims these
           blocks and prefills only the tail.

        Shared digests between the chains (every full prompt block; the
        whole chain when nothing was generated) are deduped by the usual
        ``h in _prefix_index`` guard."""
        if self._slot_hashes[b] is None or req is None:
            return
        self._register_full(b)
        self._register_partial(b)
        pos = int(self._pos_host[b])
        toks = self._serve_tokens(req)[:pos]
        self._slot_hashes[b] = self._hash_chunks(toks, tier)
        self._register_full(b)
        self._register_partial(b)

    def _lifecycle_stats(self) -> dict:
        """Lifecycle / fault-tolerance counters — meaningful in every
        cache mode (preemption/pressure counters stay 0 off-pool)."""
        return {
            "preemptions": self.preemptions,
            "cancellations": self.cancellations,
            "deadline_misses": self.deadline_misses,
            "pool_pressure_events": self.pool_pressure_events,
            "queue_wait_steps": self.queue_wait_steps,
            "head_bypasses": self.head_bypasses,
            "degrade": self.degrade,
            "degraded_requests": self.degraded_requests,
            "preempt": self.preempt,
            "victim_policy": self.victim_policy,
            "callback_errors": self.callback_errors,
            "nan_logit_events": self.nan_logit_events,
            "kernel_fallbacks": self.kernel_fallbacks,
            "chaos": self.chaos.counts() if self.chaos else None,
        }

    def pool_stats(self) -> dict:
        """KV-memory utilization: resident bytes actually backing live
        tokens vs. the contiguous worst-case reservation — plus the
        lifecycle / preemption / fault-injection counters."""
        kv = self.cache.kv
        if kv is None:
            return {"paged": False, "resident_kv_bytes": 0,
                    "reserved_kv_bytes": 0, **self._lifecycle_stats()}
        if not self.paged:
            # Count every cache plane (incl. int8 scale planes) — the
            # whole reservation is resident for the scheduler's lifetime.
            total = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                        for a in (kv.k, kv.v, kv.k_scale, kv.v_scale)
                        if a is not None)
            return {"paged": False,
                    "resident_kv_bytes": total,
                    "reserved_kv_bytes": total,
                    **self._lifecycle_stats()}
        per_token = (kv.k.shape[0] * int(np.prod(kv.k.shape[3:]))
                     * 2 * kv.k.dtype.itemsize)
        if kv.quantized:
            # int8 pool: add the per-(slot, head) fp32 k/v scale planes.
            per_token += kv.k.shape[0] * self.cfg.n_kv_heads * 2 * 4
        allocated = self._live_blocks
        hit_rate = (self.prefix_hit_tokens / self.prompt_tokens_seen
                    if self.prompt_tokens_seen else 0.0)
        return {
            "paged": True,
            "block_size": self.block_size,
            "pool_blocks": self.pool_blocks,
            "free_blocks": len(self._free),
            # Live = referenced by a row's table. Retained = refcount-0
            # prefix blocks kept for future hits; they are reclaimable on
            # demand, so "resident" (what a right-sized pool must hold)
            # counts only live blocks.
            "allocated_blocks": allocated,
            "retained_prefix_blocks": len(self._lru),
            "peak_allocated_blocks": self._peak_blocks,
            "capacity_tokens": self.pool_blocks * self.block_size,
            "resident_kv_bytes": allocated * self.block_size * per_token,
            "peak_resident_kv_bytes":
                self._peak_blocks * self.block_size * per_token,
            # What the contiguous scheduler would allocate for the same
            # settings: max_ctx + decode headroom per slot (matches the
            # non-paged branch, which measures the actual arrays).
            "reserved_kv_bytes":
                self.max_batch * (self.max_ctx + _contig_headroom())
                * per_token,
            # -- cross-request prefix cache --
            "prefix_cache": self.prefix_cache,
            "prefix_hit_blocks": self.prefix_hit_blocks,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prompt_tokens": self.prompt_tokens_seen,
            "prefix_hit_rate": hit_rate,
            "cow_copies": self.cow_copies,
            "prefix_evictions": self.prefix_evictions,
            "cached_prefix_blocks": len(self._prefix_index),
            "prefill_tokens_computed": self.prefill_tokens_computed,
            # -- host-RAM block tier (HBM-vs-host split, FINN-style
            #    capacity modeling: device pool = BRAM/HBM working set,
            #    host store = the spill capacity behind it) --
            "host_tier": self.host_tier,
            "host_pool_bytes": self.host_pool_bytes,
            "host_blocks": len(self._host_store),
            "host_bytes": self.host_bytes,
            "swap_ins": self.swap_ins,
            "swap_outs": self.swap_outs,
            "host_evictions": self.host_evictions,
            "host_hit_blocks": self.host_hit_blocks,
            "host_hit_tokens": self.host_hit_tokens,
            "host_hit_rate": (self.host_hit_tokens / self.prompt_tokens_seen
                              if self.prompt_tokens_seen else 0.0),
            # -- Sarathi-style chunked prefill / decode interleave --
            "chunked_prefill": self.chunked_prefill,
            "prefill_budget": self.prefill_budget,
            "prefill_chunks_run": self.prefill_chunks_run,
            "decode_steps_stalled": self.decode_steps_stalled,
            # Prompt tokens prefilled per chunk-spending step — the
            # interleave ratio. Divided by the steps that actually ran a
            # chunk, not total decode steps: the old steps_run denominator
            # kept shrinking the ratio long after the last plan retired,
            # so the "same" workload read differently depending on how
            # many pure-decode steps followed it.
            "prefill_tokens_per_step":
                self.prefill_chunk_tokens / max(self.prefill_chunk_steps, 1),
            "prefill_chunk_steps": self.prefill_chunk_steps,
            # -- self-speculative decoding --
            "speculate": self.speculate,
            "spec_rounds": self.spec_rounds,
            "spec_draft_tokens": self.spec_draft_tokens,
            "spec_accepted_tokens": self.spec_accepted_tokens,
            "spec_acceptance_rate":
                (self.spec_accepted_tokens / self.spec_draft_tokens
                 if self.spec_draft_tokens else 0.0),
            "spec_verify_calls": self.spec_verify_calls,
            "spec_verify_rows": self.spec_verify_rows,
            # -- lifecycle / preemption / fault injection --
            **self._lifecycle_stats(),
            # -- per-request precision tiers --
            "tier_serving": bool(self._tier_cfgs),
            "tiers": {
                (k or "base"): {
                    **tc,
                    "spec_acceptance_rate":
                        (tc["spec_accepted_tokens"] / tc["spec_draft_tokens"]
                         if tc["spec_draft_tokens"] else 0.0),
                }
                for k, tc in self.tier_counters.items()
            },
        }

    def reset_pool_peak(self) -> None:
        if self.paged:
            self._peak_blocks = self._live_blocks

    # -- admission / retirement --------------------------------------------

    def _fail(self, req: Request, reason: str) -> None:
        req.error = reason
        if req.out_tokens is None:
            req.out_tokens = []
        req.t_done = self._now()

    def _claim_tier(self, req: Request, slot: int) -> Optional[str]:
        """Record `req`'s (already validated) precision tier on the slot
        it is being admitted into and count the admission. Every compute
        call the slot makes — prefill, chunk, decode group, verify — then
        uses the tier's plane-truncated params view."""
        tier = getattr(req, "_tier_key", None)
        self._slot_tier[slot] = tier
        self.tier_counters[tier]["requests"] += 1
        return tier

    def _admit(self, req: Request, slot: int, match=None) -> Optional[Request]:
        """Prefill `req` — solo cold, or suffix-only on a prefix-cache hit
        — and scatter its state into batch row `slot`. A preempted request
        re-admits here with its served tokens = prompt ++ generated, so
        the warm path picks up its registered blocks. Returns the request
        if it finished on its very first token."""
        toks = self._serve_tokens(req)
        n = len(toks)
        tier = self._claim_tier(req, slot)
        if self.paged:
            hits, resident, revive, reserve, hashes, host_hits = (
                match if match is not None else self._match_prefix(req)
            )
            self.prompt_tokens_seen += n
            self.prefix_hit_blocks += len(hits) + len(host_hits)
            self.prefix_hit_tokens += resident
            if host_hits:
                self.host_hit_tokens += sum(
                    min(self.block_size, n - j * self.block_size)
                    for j, _ in host_hits)
            if self.prefix_cache:
                self._slot_hashes[slot] = hashes
            self._avail -= reserve
            self._reserved[slot] = reserve
            self._claim_hits(slot, hits)   # revives pay into _avail here
            if host_hits:
                self._swap_in_hits(slot, host_hits, len(hashes[0]))
            for j in range(-(-n // self.block_size)):
                if self._block_tab[slot, j] < 0:
                    self._alloc_block(slot, j)
            self._touch_peak()
        else:
            resident = 0
        if resident:
            logits = self._prefill_suffix(req, slot, resident)
        else:
            L = self._bucketed(n)
            if self.paged:
                self.prefill_tokens_computed += L
            tokens = np.zeros((1, L), np.int32)
            tokens[0, :n] = toks        # right-pad; real length via `lengths`
            solo, logits = self._prefill_fn(L)(
                self._tier_views[tier],
                {"tokens": jnp.asarray(tokens),
                 "lengths": jnp.asarray([n], jnp.int32)},
            )
            if self.paged:
                # scatter_into_paged also writes this row's table device-
                # side; _table_dirty stays set so rows freed earlier sync.
                self.cache = self._scatter_paged(
                    self.cache, solo, slot, jnp.array(self._block_tab[slot])
                )
            else:
                self.cache = self._scatter(self.cache, solo, slot)
        if self.paged and self.prefix_cache:
            self._register_full(slot)
        self._pos_host[slot] = n
        return self._first_token(req, slot, logits)

    def _first_token(self, req: Request, slot: int, logits) -> Optional[Request]:
        """Sample the request's first output token from its prefill logits
        and arm the slot's decode state — the shared admission tail of the
        solo, suffix and chunked prefill paths. A resumed (previously
        preempted) request keeps its earlier tokens: its next token is
        sampled at PRNG step `len(out_tokens)`, exactly the stream index
        an uninterrupted run would use, so resume is bit-identical even at
        temperature > 0. Returns the request if it finished on that very
        first token (slot released)."""
        step0 = len(req.out_tokens or ())
        key = sampling.request_key(self.seed, req.rid)
        tok = int(np.asarray(sampling.sample_tokens(
            logits[:, -1, :],
            np.asarray([req.temperature], np.float32),
            np.asarray([req.top_k], np.int32),
            key[None],
            np.asarray([step0], np.int32),
        ))[0])
        self._cur[slot, 0] = tok
        self._temps[slot] = req.temperature
        self._top_ks[slot] = req.top_k
        self._keys[slot] = key
        self._steps[slot] = step0 + 1
        self._slots[slot] = req
        if req.out_tokens:
            req.out_tokens.append(tok)     # resumed: extend, don't reset
        else:
            req.out_tokens = [tok]
        if req.t_first is None:
            req.t_first = self._now()
        self._emit(req, tok)
        if self._finished(req, tok):
            self._release_slot(slot)
            return req
        return None

    def _suffix_fn(self, length: int):
        length = self._bucketed(length)  # see _prefill_fn
        if length not in self._suffix_cache:
            self._suffix_cache[length] = jax.jit(self.model.prefill_suffix)
        return self._suffix_cache[length]

    def _prefill_suffix(self, req: Request, slot: int, resident: int):
        """Run the suffix-only prefill for a prefix-cache hit: gather the
        resident prefix K/V from the row's (already claimed) pool blocks,
        prefill only the uncached tail, scatter the tail's K/V into the
        row's fresh blocks. At least the last prompt token is always
        prefilled — the first sampled token comes from its logits — but
        positions already resident are never re-written, so a fully
        cached prompt admits without moving any KV data."""
        toks = self._serve_tokens(req)
        n = len(toks)
        start = min(resident, n - 1)
        ls = n - start
        Ls = self._bucketed(ls)
        self.prefill_tokens_computed += Ls
        tokens = np.zeros((1, Ls), np.int32)
        tokens[0, :ls] = toks[start:]
        kv = self.cache.kv
        # Clamp the per-layer pool gather to the blocks that actually
        # cover the prefix (host-known bound, same trick as
        # paged_gather(max_blocks=...)), bucketed so the compiled
        # signature count stays bounded instead of always paying the
        # full max_blocks table width.
        gran = max(self.bucket // self.block_size, 1)
        covering = -(-start // self.block_size)     # blocks holding [0, start)
        nbp = min(self._max_blocks, max(gran, -(-covering // gran) * gran))
        batch = {
            "tokens": jnp.asarray(tokens),
            "lengths": jnp.asarray([ls], jnp.int32),
            "start": jnp.asarray(start, jnp.int32),
            "pool_k": kv.k,
            "pool_v": kv.v,
            "prefix_blocks": jnp.array(self._block_tab[slot, :nbp]),
        }
        if kv.quantized:
            batch["pool_k_scale"] = kv.k_scale
            batch["pool_v_scale"] = kv.v_scale
        solo, logits = self._suffix_fn(Ls)(
            self._tier_views[self._slot_tier[slot]], batch)
        if resident < n:
            # Below a full-prompt hit only whole blocks are shared, so the
            # suffix starts exactly at the block boundary `resident`.
            self.cache = self._scatter_suffix(
                self.cache, solo, slot, jnp.array(self._block_tab[slot]),
                resident // self.block_size,
            )
        else:
            self.cache = self._set_row(
                self.cache, solo, slot, jnp.array(self._block_tab[slot])
            )
        return logits

    def _admit_chunked(self, req: Request, slot: int, match) -> None:
        """Claim row `slot` for `req` and enqueue a chunk *plan* instead of
        prefilling solo: the same allocator work as `_admit` (reservation,
        prefix-hit claiming, prompt-block allocation) happens up front, but
        the prompt KV is computed `prefill_budget` tokens at a time by
        `_run_chunk`, one call per scheduler step, interleaved with the
        live batch's decode steps. Until the last chunk lands, the slot is
        masked out of decoding (device table row all -1, see `_sync_table`)
        and out of sampling, and its prompt blocks stay unregistered in
        the prefix index (their bytes don't exist yet)."""
        toks = self._serve_tokens(req)
        n = len(toks)
        self._claim_tier(req, slot)
        hits, resident, revive, reserve, hashes, host_hits = match
        self.prompt_tokens_seen += n
        self.prefix_hit_blocks += len(hits) + len(host_hits)
        self.prefix_hit_tokens += resident
        if host_hits:
            self.host_hit_tokens += sum(
                min(self.block_size, n - j * self.block_size)
                for j, _ in host_hits)
        if self.prefix_cache:
            self._slot_hashes[slot] = hashes
        self._avail -= reserve
        self._reserved[slot] = reserve
        self._claim_hits(slot, hits)   # revives pay into _avail here
        if host_hits:
            self._swap_in_hits(slot, host_hits, len(hashes[0]))
        for j in range(-(-n // self.block_size)):
            if self._block_tab[slot, j] < 0:
                self._alloc_block(slot, j)
        self._touch_peak()
        self._pos_host[slot] = 0
        self._cur[slot, 0] = 0         # dummy decode input while prefilling
        self._slots[slot] = req
        # Chunks start at the warm-prefix boundary: `resident` below a
        # full-prompt hit is whole blocks only, so chunk writes begin at a
        # block boundary and never touch a block shared with other rows.
        self._chunk_plans[slot] = {"req": req, "toks": toks,
                                   "next": resident, "n": n}
        self._chunk_queue.append(slot)
        self._table_dirty = True       # mask this row on the next sync

    def _run_chunk(self, slot: int) -> Optional[Request]:
        """Run one `prefill_budget`-token chunk of row `slot`'s plan
        through the fused paged-prefill kernel: the chunk attends over
        [pool-resident prefix ++ chunk] and its K/V lands in the row's own
        pool blocks from the kernel epilogue — no scatter round trip, no
        per-layer prefix gather. On the final chunk the prompt is fully
        resident: the row's full blocks are registered in the prefix
        index, the device table is unmasked, and the first output token is
        sampled from the chunk's last-token logits. Returns the request
        if it finished on that first token. Runs in the ``serving.chunk``
        profiler span (chunk ``start`` and ``len``, ``plans`` in flight)."""
        plan = self._chunk_plans[slot]
        req, n, start = plan["req"], plan["n"], plan["next"]
        Lc = self.prefill_budget
        t = min(Lc, n - start)
        with TraceAnnotation("serving.chunk", start=start, len=t,
                             plans=len(self._chunk_plans)):
            tokens = np.zeros((1, Lc), np.int32)
            tokens[0, :t] = plan["toks"][start:start + t]
            # Clamp the kernel's block-table operand to the blocks
            # covering [0, start + t), bucketed like _prefill_suffix's
            # gather clamp so the compiled signature count stays bounded:
            # one executable per (budget, bucketed covering-blocks) pair.
            gran = max(self.bucket // self.block_size, 1)
            covering = -(-(start + t) // self.block_size)
            nbp = min(self._max_blocks,
                      max(gran, -(-covering // gran) * gran))
            batch = {
                "tokens": jnp.asarray(tokens),
                "lengths": jnp.asarray([t], jnp.int32),
                "start": jnp.asarray(start, jnp.int32),
                "slot": jnp.asarray(slot, jnp.int32),
                "blocks": jnp.array(self._block_tab[slot, :nbp]),
            }
            self.cache, logits = self._chunk(
                self._tier_views[self._slot_tier[slot]], self.cache, batch)
            self.prefill_chunks_run += 1
            self.prefill_chunk_tokens += t
            self.prefill_tokens_computed += Lc
            plan["next"] = start + t
            if self.prefix_cache:
                # Blocks this chunk fully covered are final — index them
                # now so a same-prefix request admitted on a later step
                # shares them instead of re-prefilling.
                self._register_full(slot,
                                    limit=plan["next"] // self.block_size)
            if plan["next"] < n:
                return None
            # Prompt fully resident: the slot graduates to decoding.
            del self._chunk_plans[slot]
            self._pos_host[slot] = n
            if self.prefix_cache:
                self._register_full(slot)
            self._table_dirty = True   # unmask the row for the decode step
            return self._first_token(req, slot, logits)

    def _emit(self, req: Request, tok: int) -> None:
        """Count the token and stream it to the per-request and scheduler-
        level `on_token` callbacks. Callbacks are USER code: one raising
        must never kill the engine loop (it used to propagate out of
        `step()` and take every live slot down with it) — it marks only
        this request errored, and `_finished` retires it at the caller."""
        self.tokens_emitted += 1
        self.tier_counters[getattr(req, "_tier_key", None)]["tokens"] += 1
        callbacks = [cb for cb in (req.on_token, self.on_token)
                     if cb is not None]
        if not callbacks:
            return
        try:
            if self.chaos is not None and self.chaos.fire("callback"):
                raise InjectedFault("on_token callback")
            for cb in callbacks:
                cb(req, tok)
        except Exception as e:  # noqa: BLE001 — isolate user-code faults
            self.callback_errors += 1
            req.error = f"on_token callback raised: {e!r}"

    @staticmethod
    def _finished(req: Request, tok: int) -> bool:
        return (req.failed
                or len(req.out_tokens) >= req.max_new_tokens
                or (req.eos_id is not None and tok == req.eos_id))

    # -- self-speculative decoding -----------------------------------------

    def _spec_phase(self) -> List[Request]:
        """One speculation round: draft up to ``speculate`` tokens per
        eligible slot with the truncated-plane view params (draft K/V
        lands speculatively in the row's own pool blocks), then verify
        each slot's ``[current token, drafts]`` window in ONE full-policy
        chunk-shaped call and emit the longest matching prefix.

        Eligibility: greedy slots only (acceptance compares argmaxes; a
        sampled slot has no deterministic token to match), not mid-chunk-
        prefill, and at least 2 tokens still owed (with 1 owed the normal
        trailing decode is strictly cheaper than draft + verify).

        Rollback is a metadata write: verification recomputes all k+1
        positions at the full policy — per-token K/V overwrites the
        draft's bytes in place — so rejecting a tail only requires
        restoring ``pos``/``length`` to the accepted frontier
        (:func:`set_decode_positions`). Rejected positions' stale pool
        bytes are dead: decode attention masks ``kpos >= length`` and the
        row's next steps write those very positions before reading them.
        No copy-on-write is needed because every speculative write lands
        at position >= the prompt length, inside blocks the round made
        private up front (:meth:`_alloc_blocks_through`) — shared prefix
        blocks are never touched, so the prefix cache's
        partial-block-registers-at-retirement invariant survives."""
        spec: Dict[int, int] = {}       # slot -> draft count this round
        for b, req in enumerate(self._slots):
            if req is None or b in self._chunk_plans:
                continue
            if req.temperature > 0:
                continue
            tier = self._slot_tier[b]
            if (tier is not None
                    and self._tier_cfgs[tier].w_bits
                    <= self._draft_cfg.w_bits):
                # Speculation composes with tiers only when the draft
                # truncates strictly below the slot's tier — a w2 slot
                # has nothing cheaper than itself to draft with, so it
                # just decodes normally.
                continue
            k_eff = min(self.speculate,
                        req.max_new_tokens - len(req.out_tokens) - 1)
            if k_eff >= 1:
                spec[b] = k_eff
        if not spec:
            return []
        # Back every position the round writes — draft appends at
        # [pos, pos+k) and the verify chunk at [pos, pos+k] — before any
        # kernel runs. All writes sit inside the row's admission
        # reservation (pos + k <= prompt + max_new - 2).
        for b, k_eff in spec.items():
            self._alloc_blocks_through(b, int(self._pos_host[b]) + k_eff)
        self._push_spec_table(set(spec))

        # Lockstep draft: every speculating row advances one token per
        # iteration through the ordinary decode step, but with the view
        # params — same kernels, plane-truncated contraction. Rows that
        # hit their own draft count early are masked out (their surplus
        # writes would overrun their allocation).
        active = set(spec)
        drafts: Dict[int, List[int]] = {b: [] for b in spec}
        cur = self._cur.copy()
        for i in range(max(spec.values())):
            todo = {b for b in active if len(drafts[b]) < spec[b]}
            if todo != active:
                active = todo
                self._push_spec_table(active)
            logits = self._decode_call(self._draft_params, cur)
            toks = np.asarray(jnp.argmax(
                logits[:, -1, :].astype(jnp.float32), axis=-1))
            for b in active:
                drafts[b].append(int(toks[b]))
                cur[b, 0] = int(toks[b])

        # Verify: one chunk-shaped full-policy call per slot over
        # [current token, d_1 .. d_k]. Fixed window (speculate + 1) keeps
        # one compiled signature per bucketed block count; position i's
        # argmax is the token sequential greedy decode would emit there.
        finished: List[Request] = []
        Lc = self.speculate + 1
        R = self.max_batch
        gran = max(self.bucket // self.block_size, 1)
        vgroups: Dict[Optional[str], List[int]] = {}
        for b in spec:
            vgroups.setdefault(self._slot_tier[b], []).append(b)
        for tkey in sorted(vgroups, key=lambda k: (k is not None, k or "")):
            slots_g = vgroups[tkey]
            # One bucketed block-table width for the whole group: extra
            # -1 entries on shorter rows are dead (masked exactly), so
            # the widest row sets the compiled signature.
            covering = max(
                -(-(int(self._pos_host[b]) + spec[b] + 1) // self.block_size)
                for b in slots_g)
            nbp = min(self._max_blocks,
                      max(gran, -(-covering // gran) * gran))
            tokens = np.zeros((R, Lc), np.int32)
            lengths = np.zeros((R,), np.int32)
            starts = np.zeros((R,), np.int32)
            slot_ids = np.full((R,), -1, np.int32)
            btab = np.full((R, nbp), -1, np.int32)
            for b in slots_g:
                t = spec[b] + 1
                tokens[b, 0] = self._cur[b, 0]
                tokens[b, 1:t] = drafts[b]
                lengths[b] = t
                starts[b] = int(self._pos_host[b])
                slot_ids[b] = b
                btab[b] = self._block_tab[b, :nbp]
            batch = {
                "tokens": jnp.asarray(tokens),
                "lengths": jnp.asarray(lengths),
                "starts": jnp.asarray(starts),
                "slots": jnp.asarray(slot_ids),
                "blocks": jnp.asarray(btab),
            }
            self.cache, logits = self._verify(self._tier_views[tkey],
                                              self.cache, batch)
            self.spec_verify_calls += 1
            self.spec_verify_rows += len(slots_g)
            lg = np.asarray(jnp.argmax(logits.astype(jnp.float32), axis=-1))
            tc = self.tier_counters[tkey]
            for b in slots_g:
                k_eff = spec[b]
                req = self._slots[b]
                p = int(self._pos_host[b])
                emitted = greedy_accept(lg[b, :k_eff + 1], drafts[b])
                self.spec_draft_tokens += k_eff
                self.spec_accepted_tokens += len(emitted) - 1
                tc["spec_draft_tokens"] += k_eff
                tc["spec_accepted_tokens"] += len(emitted) - 1
                req.spec_drafted += k_eff
                req.spec_accepted += len(emitted) - 1
                m = 0
                done = False
                for tok in emitted:
                    req.out_tokens.append(tok)
                    self._emit(req, tok)
                    m += 1
                    if self._finished(req, tok):
                        done = True
                        break
                self._pos_host[b] = p + m
                self._steps[b] += m
                if done:
                    self._release_slot(b)
                    finished.append(req)
                else:
                    self._cur[b, 0] = emitted[m - 1]
        # Roll every row back to its accepted frontier in one device
        # write. Clobbering non-speculating rows is safe: chunk plans
        # drive the chunk kernel with explicit start/length operands (the
        # final chunk re-sets the device row), free rows already carry
        # stale positions behind an all--1 table, and live decoders were
        # position-synced with _pos_host before this round began.
        pos = jnp.asarray(self._pos_host, jnp.int32)
        self.cache = self._set_positions(self.cache, pos, pos)
        self._table_dirty = True       # real table re-pushed before decode
        self.spec_rounds += 1
        return finished

    def _decode_tier_groups(self, groups) -> jnp.ndarray:
        """Mixed-tier batched decode: one decode call per tier group, each
        with that group's truncated-plane view params and a block table
        masking every non-group row to -1 (writes route to the trash
        block, attention sees no keys — :meth:`_push_spec_table`, reused
        verbatim from the speculation machinery). Per-token activation
        scales make row b's logits independent of the other rows' content,
        so a group call computes exactly what a solo tier-T engine's
        decode computes for those rows — the tier bit-identity contract.

        Each jitted decode call advances EVERY row's device pos/length by
        one, so with G group calls the naive result would be +G. Between
        calls positions are reset to the pre-decode frontier and after the
        last call set to frontier+1 for all rows — precisely the state one
        single-call decode leaves behind (one metadata write each, same
        :func:`set_decode_positions` the speculation rollback uses).

        Returns the (B, V) last-position logits matrix with each row taken
        from its own group's call, ready for the shared sampling path."""
        pos0 = np.asarray(self._pos_host, np.int32).copy()
        # Copies, here and for every `_block_tab` row handed to a jitted
        # call: the host rewrites these arrays before an asynchronous call
        # has read them, and on the CPU `jnp.asarray` may alias them.
        cur = jnp.array(self._cur)
        out = None
        order = sorted(groups, key=lambda k: (k is not None, k or ""))
        for i, key in enumerate(order):
            if i:
                p = jnp.asarray(pos0)
                self.cache = self._set_positions(self.cache, p, p)
            self._push_spec_table(set(groups[key]))
            logits = self._decode_call(self._tier_views[key], cur)
            self.tier_counters[key]["decode_calls"] += 1
            rows = np.asarray(logits[:, -1, :])
            if out is None:
                out = np.zeros_like(rows)
            for b in groups[key]:
                out[b] = rows[b]
        p1 = jnp.asarray(pos0 + 1)
        self.cache = self._set_positions(self.cache, p1, p1)
        self._table_dirty = True       # real table re-pushed next step
        return jnp.asarray(out)

    # -- the decode loop ----------------------------------------------------

    def step(self) -> List[Request]:
        """One scheduler step: process lifecycle events (cancellations,
        deadline expiries), admit waiting requests into free slots
        (chunked-prefill plan by default; suffix-only prefill on a
        full-prompt prefix hit), spend at most one ``prefill_budget``-token
        chunk of in-flight admission prefill, run one batched decode step,
        sample, retire finished slots. Live slots always decode — a chunk
        costs them one kernel call of extra latency per step, never a
        skipped step.

        When the pool can't cover an admission's revive + reservation
        draw the request queues FIFO — but first the scheduler may (a)
        preempt one victim whose release alone covers the shortfall
        (``preempt``, warm bit-identical resume) and (b) admit a smaller
        admissible request past the blocked head, at most
        ``max_head_bypass`` consecutive times (head-of-line mitigation,
        starvation-free). With ``degrade``, admissions during sustained
        pressure are served at the cheapest configured tier. Returns the
        requests that finished this step (including any rejected as
        oversized, cancelled, past deadline, or individually failed —
        those carry ``error``).

        Each phase runs in a ``serving.*`` profiler span (see `_step`);
        with no profiler running a span costs about a microsecond."""
        self._step_calls += 1
        with TraceAnnotation("serving.step", step=self._step_calls):
            return self._step()

    def _step(self) -> List[Request]:
        """The body of `step()`, one profiler span per phase:
        ``serving.lifecycle``, ``serving.admit``, ``serving.chunk`` (in
        `_run_chunk`), ``serving.spec``, ``serving.table_sync``,
        ``serving.decode``, ``serving.nan_check``, ``serving.sample`` and
        ``serving.emit``. Span arguments come from host state only."""
        with TraceAnnotation("serving.lifecycle"):
            finished: List[Request] = list(self._lifecycle_phase())
        with TraceAnnotation("serving.admit"):
            self._admission_phase(finished)

        # Spend one budgeted chunk of admission prefill alongside this
        # step's decode — round-robin across queued plans: the serviced
        # plan rotates to the back, so with several admissions in flight
        # each spends one chunk every len(queue) steps and no prompt's
        # first token waits for every earlier prompt to finish prefilling.
        chunk_ran = False
        if self._chunk_queue:
            slot = self._chunk_queue.popleft()
            chunk_ran = True
            done = self._run_chunk(slot)
            if slot in self._chunk_plans:
                self._chunk_queue.append(slot)  # unfinished: back of line
            elif done is not None:
                finished.append(done)
            self.prefill_chunk_steps += 1

        if not any(r is not None and b not in self._chunk_plans
                   for b, r in enumerate(self._slots)):
            return finished  # nothing decodes: only chunk plans in flight

        if self.speculate:
            # Speculation rounds replace several sequential decode steps
            # for greedy slots; survivors still join the trailing decode
            # below, which is exactly their next sequential step.
            with TraceAnnotation("serving.spec"):
                finished.extend(self._spec_phase())
            if not any(r is not None and b not in self._chunk_plans
                       for b, r in enumerate(self._slots)):
                return finished  # every live slot retired mid-round

        if chunk_ran:
            self.decode_steps_stalled += 1
        if self.paged:
            with TraceAnnotation("serving.table_sync"):
                self._alloc_boundary_blocks()
                self._sync_table()
        groups: Dict[Optional[str], List[int]] = {}
        for b, r in enumerate(self._slots):
            if r is not None and b not in self._chunk_plans:
                groups.setdefault(self._slot_tier[b], []).append(b)
        live = sorted(b for g in groups.values() for b in g)
        with TraceAnnotation(
                "serving.decode", rows=len(live),
                keys=int(self._pos_host[live].sum()) + len(live)):
            if len(groups) <= 1:
                # Homogeneous batch (incl. the no-tiers engine): one decode
                # with the group's view params — exactly what a solo
                # engine whose whole policy is this tier runs, so
                # bit-identity for the single-tier case holds by
                # construction.
                key = next(iter(groups), None)
                logits = self._decode_call(self._tier_views[key], self._cur)
                self.tier_counters[key]["decode_calls"] += 1
                last = logits[:, -1, :]
            else:
                last = self._decode_tier_groups(groups)
        if self.chaos is not None and live and self.chaos.fire("nan"):
            # Chaos: poison one live row's logits; the detector below
            # must catch it and fail that request alone.
            bad_row = live[self.chaos.pick(len(live))]
            last = jnp.asarray(last).at[bad_row].set(jnp.nan)
        # Always-on poisoned-logits detector: a non-finite logits row
        # (numerics blow-up, corrupted weights, injected fault) cannot
        # sample a meaningful token — retire just that request with
        # error="nan-logits" before sampling; its K/V writes this step
        # were row-local, so batch neighbours are untouched.
        with TraceAnnotation("serving.nan_check"):
            bad = np.asarray(jnp.any(
                ~jnp.isfinite(jnp.asarray(last).astype(jnp.float32)),
                axis=-1))
        for b in live:
            if bad[b]:
                self.nan_logit_events += 1
                finished.append(self._retire_abnormal(b, "nan-logits"))
        with TraceAnnotation("serving.sample"):
            toks = np.asarray(sampling.sample_tokens(
                last, self._temps, self._top_ks,
                self._keys, self._steps,
            ))
        self._steps += 1
        self.steps_run += 1
        with TraceAnnotation("serving.emit"):
            for b, req in enumerate(self._slots):
                if req is None or b in self._chunk_plans:
                    continue  # mid-chunk-prefill slots don't sample yet
                self._pos_host[b] += 1
                tok = int(toks[b])
                req.out_tokens.append(tok)
                self._emit(req, tok)
                if self._finished(req, tok):
                    self._release_slot(b)
                    finished.append(req)
                else:
                    self._cur[b, 0] = tok
        return finished

    def _admission_phase(self, finished: List[Request]) -> None:
        """Admit waiting requests into free slots for one step (see
        `step()`); rejected requests and those that finish on their
        prefill token go onto `finished`."""
        pressure = False
        chunk_admitted = False
        preempted = False
        admitted_now: set = set()
        free: Deque[int] = collections.deque(
            b for b in range(self.max_batch) if self._slots[b] is None)
        deg = self.degrade and self._pressure_streak >= self.degrade_after
        while free and self.waiting and not chunk_admitted:
            slot = free[0]
            head = self.waiting[0]
            reason = self._reject_reason(head)
            if reason is not None:
                # Oversized / bad tier: reject just this request.
                self.waiting.popleft()
                self._fail(head, reason)
                finished.append(head)
                continue
            idx = 0
            was_degraded = self._degrade_tier(head) if deg else False
            match = self._match_prefix(head) if self.paged else None
            if self.paged:
                # revive + reserve is the admission's true capacity draw
                # (shared live blocks are free).
                short = match[2] + match[3] > self._avail
                if (not short and self.chaos is not None
                        and self.chaos.fire("alloc")):
                    short = True   # injected transient reservation failure
                if short:
                    pressure = True
                    self.pool_pressure_events += 1
                    shortfall = match[2] + match[3] - self._avail
                    # (1) Preempt one victim for the head — never for a
                    # head that was itself preempted (ping-pong guard),
                    # and at most once per step.
                    if (self.preempt and not preempted
                            and head.preemptions == 0):
                        victim = self._pick_victim(shortfall, admitted_now)
                        if victim is not None:
                            self._preempt(victim)
                            preempted = True
                            free.append(victim)
                            continue   # retry head against freed blocks
                    # (2) Bounded bypass: admit a smaller admissible
                    # request past the blocked head.
                    idx, match, was_degraded = self._bypass_candidate(deg)
                    if idx is None:
                        break          # head keeps FIFO priority: wait
                    self.head_bypasses += 1
                    self._head_bypass += 1
            req = self.waiting[idx]
            del self.waiting[idx]
            if idx == 0:
                self._head_bypass = 0  # the head itself is admitting
            if was_degraded and req.degraded_to is None:
                req.degraded_to = req._tier_key
                self.degraded_requests += 1
            if (self.chunked_prefill and match is not None
                    and match[1] < self._serve_len(req)):
                # Uncached prompt tail → chunk plan. (A full-prompt
                # prefix hit moves no KV and stays on the suffix
                # path: its one-token "prefill" reads shared blocks
                # the chunk kernel must never write.) One chunked
                # admission per step: a same-prefix follower admitted
                # in this same step would match against an index this
                # plan hasn't written to yet and cold-prefill blocks
                # it could share — admitted next step, it hits the
                # blocks the chunks have landed (and registered) by
                # then.
                self._admit_chunked(req, slot, match)
                admitted_now.add(slot)
                chunk_admitted = True
                free.popleft()
                continue
            done = self._admit(req, slot, match)
            if done is not None:
                # Finished on its prefill token (max_new <= 1 /
                # instant EOS) — the slot is free again, keep
                # admitting into it this same step.
                finished.append(done)
                continue
            admitted_now.add(slot)
            free.popleft()
        self._pressure_streak = self._pressure_streak + 1 if pressure else 0
        self.queue_wait_steps += len(self.waiting)

    def run(self, requests=()) -> List[Request]:
        """Serve a workload to completion, admitting each request no
        earlier than its ``arrival_time`` (seconds from now). Returns the
        requests in completion order with ``t_first``/``t_done`` filled;
        oversized requests come back failed (``error`` set) without
        aborting the loop."""
        pending = sorted(requests, key=lambda r: r.arrival_time)
        self._t0 = time.perf_counter()
        done: List[Request] = []
        while pending or self.waiting or self.num_active:
            now = time.perf_counter() - self._t0
            while pending and pending[0].arrival_time <= now:
                self.submit(pending.pop(0))
            if not self.waiting and self.num_active == 0:
                # Idle: sleep up to the next arrival.
                time.sleep(min(max(pending[0].arrival_time - now, 0.0), 0.05))
                continue
            for req in self.step():
                req.t_done = time.perf_counter() - self._t0
                done.append(req)
        self._t0 = None
        return done
