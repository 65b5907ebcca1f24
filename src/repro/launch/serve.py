"""Serving driver: load (or init) a checkpointed model and serve a batch
of synthetic requests through the quantized engine.

  PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --reduced \
      [--quant w4a8] [--policy "w4a8;wo=w8a8;head=w8a8"] [--backend interpret] \
      [--kv-int8] [--ckpt /tmp/ckpt] [--requests 8] \
      [--continuous] [--rate 20] [--static]

--quant applies one uniform QuantConfig; --policy is a per-layer
PrecisionPolicy spec ("default;pattern=wXaY[rZZ];..." matched against
parameter paths). --backend selects the kernel backend through the
registry (interpret | mosaic | reference; default = platform default).

--continuous serves through the continuous-batching scheduler with
Poisson-ish staggered arrivals at --rate requests/s (0 = all at once);
--static keeps the classic static batch. Either way the driver runs one
warmup pass first, so steady-state throughput (what the hardware does)
and total throughput (including compile) are reported separately.

Full-attention archs serve from the paged block-pool KV cache by default:
--block-size sets the pool granularity, --pool-blocks caps the shared
pool (defaults to the contiguous worst case; set it lower to overcommit —
admission then queues on actual free blocks), --no-paged forces the
contiguous per-slot max_ctx reservation. Pool utilization is reported
after a continuous run. --kv-int8 composes with the paged pool: blocks
hold int8 codes plus fp32 scale planes and the fused paged-attention
decode kernel dequantizes in-kernel (~2× tokens per pooled byte).

Cross-request prefix caching is on by default whenever the pool is paged
(and the arch supports suffix-only prefill): prompts sharing a prefix —
--shared-prefix N prepends a common N-token system prompt to every
synthetic request — reuse each other's resident prompt blocks with
refcounts and copy-on-write, and admission prefills only the uncached
suffix, bit-identical to a cold prefill. --no-prefix-cache disables it
(--prefix-cache forces it on, erroring if unsupported); the hit rate is
reported after a continuous run.

Chunked prefill (Sarathi-style) is also on by default on the paged pool:
admission enqueues a chunk *plan* and the scheduler spends at most
--prefill-budget prompt tokens of prefill per step through the fused
paged chunked-prefill kernel, interleaved with the live batch's decode
steps — a long prompt never stalls decoding for more than one budgeted
chunk. --no-chunked-prefill reverts to solo whole-prompt prefill at
admission. Chunk/stall counters are reported after a continuous run.

Self-speculative decoding: --speculate K drafts K tokens per scheduler
step from a truncated-plane view of the resident packed weights (the
draft reads only the top bit-planes — no second weight copy) and
verifies all K+1 positions in one chunk-shaped full-policy call,
emitting the longest matching prefix. Greedy requests' tokens are
bitwise identical to --speculate 0; sampled requests decode normally.
--draft-policy picks the draft precision (w4a8 / w2a8 — the plane
subset to keep). Requires a quant policy (--quant/--policy) and the
paged pool. Draft/acceptance counters are reported after a continuous
run.

Per-request precision tiers: --tiers "w8a8,w4a8,w2a8" assigns each
synthetic request a quality–latency class round-robin, all served from
the ONE packed weight set inside the same continuous batch — a tier is a
plane-truncated view of the stored weights (w4 reads half the weight
bytes of w8, w2 a quarter), and the scheduler runs one decode call per
tier group per step. A request served at tier T is greedy bit-identical
to a solo engine whose whole policy is T. Composes with --speculate (the
draft must sit strictly below a slot's tier to speculate) and with the
prefix cache (hashes are tier-scoped). Requires --continuous and a quant
policy; per-tier counters are reported after the run.

Request lifecycle & robustness: --deadline-ms gives every synthetic
request a wall-clock deadline (missed ones retire with error="deadline",
freeing their blocks like any retirement). On the paged pool, admission
under pool pressure preempts a victim slot by default (--no-preempt to
queue instead): the victim's resident blocks are registered into the
prefix index and the request is requeued as prompt ++ generated, so it
resumes warm — its greedy tokens are bitwise the uninterrupted stream.
--victim-policy picks the victim (most-blocks | lowest-tier |
latest-deadline). --degrade admits at the lowest precision tier once
pool pressure persists (needs --tiers). --chaos-seed N arms a seeded
FaultInjector that fires alloc/kernel/nan/callback faults at
--chaos-rate per seam visit — the engine must survive every fault by
degrading one request or one call; the chaos report prints what fired.

--plans FILE persists the kernel registry's block-plan cache (autotune
winners, e.g. the paged-attention bh knob) across process restarts:
loaded before serving if the file exists, written back on exit.

Tiered block pool: --host-pool-bytes N arms a host-RAM spill tier under
the paged pool — refcount-0 cached blocks evicted under pool pressure
move to a pinned numpy store instead of dying, and a prefix hit on a
host-resident chain swaps the blocks back into free device slots before
admission (warm-from-host greedy streams are bitwise the cold streams).
--victim-policy block-to-host makes preemption spill the victim's
resident K/V to host too, so it resumes warm even after its device
blocks were reclaimed. --index FILE persists the prefix index itself
(digest chains + block bytes, versioned JSON) across process restarts,
mirroring --plans: loaded before serving if the file exists, written
back on exit — a restarted server serves repeat prefixes warm from
host instead of re-prefilling cold. Swap/host-hit counters are
reported after a continuous run.
"""
import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache


def build_parser() -> argparse.ArgumentParser:
    """The serving CLI's flags (see the module docstring)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--quant", default=None)
    ap.add_argument("--policy", default=None,
                    help="per-layer precision spec, e.g. 'w4a8;wo=w8a8'")
    ap.add_argument("--backend", default=None,
                    choices=("interpret", "mosaic", "reference"))
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--continuous", action="store_true",
                    help="serve via the continuous-batching scheduler")
    ap.add_argument("--static", action="store_true",
                    help="serve via the static batch baseline")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="continuous mode: Poisson arrival rate in "
                         "requests/s (0 = all requests queued at t=0)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged KV cache block size (tokens per block)")
    ap.add_argument("--pool-blocks", type=int, default=None,
                    help="shared KV pool size in blocks (default: the "
                         "contiguous worst case max_batch * max_ctx)")
    ap.add_argument("--no-paged", action="store_true",
                    help="force the contiguous per-slot KV reservation")
    ap.add_argument("--prefix-cache", dest="prefix_cache",
                    action="store_true", default=None,
                    help="force cross-request prefix caching on (default: "
                         "auto — on whenever the pool is paged and the "
                         "arch supports suffix-only prefill)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false",
                    help="disable cross-request prefix caching")
    ap.add_argument("--prefill-budget", type=int, default=32,
                    help="chunked prefill: max prompt tokens prefilled "
                         "per scheduler step (the decode-stall bound)")
    ap.add_argument("--no-chunked-prefill", dest="chunked_prefill",
                    action="store_false", default=None,
                    help="disable Sarathi-style chunked prefill (solo "
                         "whole-prompt prefill at admission instead)")
    ap.add_argument("--speculate", type=int, default=0,
                    help="self-speculative decoding: draft tokens per "
                         "scheduler step from the truncated-plane view "
                         "of the packed weights (0 = off; greedy "
                         "requests only, needs --quant/--policy)")
    ap.add_argument("--draft-policy", default="w4a8",
                    help="draft precision for --speculate: the plane "
                         "subset of the resident weights the draft "
                         "contracts (e.g. w4a8, w2a8)")
    ap.add_argument("--tiers", default=None,
                    help="per-request precision tiers, e.g. "
                         "'w8a8,w4a8,w2a8': requests are assigned a tier "
                         "round-robin and served through plane-truncated "
                         "views of the one packed weight set inside the "
                         "same continuous batch (needs --continuous and "
                         "--quant/--policy)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request wall-clock deadline: requests not "
                         "finished this many ms after arrival retire "
                         "with error='deadline'")
    ap.add_argument("--no-preempt", dest="preempt", action="store_false",
                    default=None,
                    help="never preempt a live slot under pool pressure "
                         "(queue instead; default: preempt on the paged "
                         "pool, resume warm from prefix-cached blocks)")
    ap.add_argument("--victim-policy", default="most-blocks",
                    choices=("most-blocks", "lowest-tier",
                             "latest-deadline", "block-to-host"),
                    help="which live slot pool-pressure preemption evicts "
                         "(block-to-host picks like most-blocks and spills "
                         "the victim's resident K/V to the host tier; "
                         "needs --host-pool-bytes)")
    ap.add_argument("--degrade", action="store_true",
                    help="under sustained pool pressure admit new "
                         "requests at the lowest precision tier "
                         "(needs --tiers; sticky for the request's life)")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="arm the seeded fault injector (alloc/kernel/"
                         "nan/callback seams) with this seed")
    ap.add_argument("--chaos-rate", type=float, default=0.05,
                    help="per-seam-visit fault probability when "
                         "--chaos-seed is set")
    ap.add_argument("--chaos-max-faults", type=int, default=None,
                    help="cap total injected faults (default unbounded)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend a common N-token system prompt to every "
                         "synthetic request (exercises the prefix cache)")
    ap.add_argument("--plans", default=None,
                    help="block-plan cache JSON: loaded at startup if it "
                         "exists, saved back (with any new plans) on exit")
    ap.add_argument("--host-pool-bytes", type=int, default=0,
                    help="host-RAM spill tier budget in bytes (0 = off): "
                         "evicted refcount-0 prefix blocks move to a "
                         "pinned host store and swap back bit-identically "
                         "on a prefix hit")
    ap.add_argument("--index", default=None,
                    help="prefix-index JSON (digest chains + block bytes): "
                         "loaded into the host tier at startup if it "
                         "exists, saved back on exit (needs "
                         "--host-pool-bytes)")
    return ap


def build_engine(args):
    """Check the flag combination, select the kernel backend, build
    the model (restored from --ckpt, else random weights from
    PRNGKey(0)) and the ServingEngine. Returns (cfg, engine)."""
    if args.index and not args.host_pool_bytes:
        raise SystemExit("--index persists blocks into the host tier; "
                         "add --host-pool-bytes")
    if args.victim_policy == "block-to-host" and not args.host_pool_bytes:
        raise SystemExit("--victim-policy block-to-host spills to the host "
                         "tier; add --host-pool-bytes")

    if args.quant and args.policy:
        raise SystemExit("--quant and --policy are mutually exclusive")
    if args.continuous and args.static:
        raise SystemExit("--continuous and --static are mutually exclusive")
    if args.speculate and not args.continuous:
        raise SystemExit("--speculate runs inside the continuous "
                         "scheduler; add --continuous")
    if args.speculate and not (args.quant or args.policy):
        raise SystemExit("--speculate drafts from the resident bit-plane "
                         "weights; add a quant policy (e.g. --quant w8a8)")
    if args.tiers and not args.continuous:
        raise SystemExit("--tiers groups slots inside the continuous "
                         "scheduler; add --continuous")
    if args.tiers and not (args.quant or args.policy):
        raise SystemExit("--tiers serves plane-truncated views of packed "
                         "weights; add a quant policy (e.g. --quant w8a8)")
    if args.degrade and not args.tiers:
        raise SystemExit("--degrade lowers admissions to the floor tier; "
                         "add --tiers")
    from repro.kernels import get_registry

    if args.backend:
        get_registry().set_active(args.backend)
    if args.plans:
        import os

        if os.path.exists(args.plans):
            n = get_registry().load_plans(args.plans)
            print(f"loaded {n} block plans from {args.plans}")

    from repro.configs import get_config, get_reduced_config
    from repro.models import build_model
    from repro.serving import ServingEngine

    cfg = (get_reduced_config if args.reduced else get_config)(args.arch)
    if cfg.family == "encoder":
        raise SystemExit("encoder-only arch has no decode step")
    cfg = dataclasses.replace(cfg, kv_cache_quant=args.kv_int8)
    model = build_model(cfg)

    if args.ckpt:
        from repro.checkpoint import CheckpointManager
        from repro.configs.base import TrainConfig
        from repro.train.loop import init_train_state

        mgr = CheckpointManager(args.ckpt)
        state, _, step = mgr.restore(
            lambda: init_train_state(model.init(jax.random.PRNGKey(0)),
                                     TrainConfig())
        )
        params = state.params
        print(f"restored checkpoint step {step}")
    else:
        params = model.init(jax.random.PRNGKey(0))
        print("serving randomly initialized weights (no --ckpt)")

    quant = None
    if args.policy:
        from repro.core.precision import parse_policy_spec

        quant = parse_policy_spec(args.policy)
        print(f"precision policy: {quant.describe()}")
    elif args.quant:
        from repro.launch.dryrun import _parse_quant

        quant = _parse_quant(args.quant)
    chaos = None
    if args.chaos_seed is not None:
        from repro.serving import FaultInjector

        p = args.chaos_rate
        chaos = FaultInjector(args.chaos_seed, p_alloc=p, p_kernel=p,
                              p_nan=p, p_callback=p,
                              max_faults=args.chaos_max_faults)
    engine = ServingEngine(cfg, params, max_batch=args.max_batch,
                           quant=quant, bucket=32,
                           paged=False if args.no_paged else None,
                           block_size=args.block_size,
                           pool_blocks=args.pool_blocks,
                           prefix_cache=args.prefix_cache,
                           chunked_prefill=args.chunked_prefill,
                           prefill_budget=args.prefill_budget,
                           speculate=args.speculate,
                           draft_policy=args.draft_policy,
                           tiers=args.tiers,
                           preempt=args.preempt,
                           victim_policy=args.victim_policy,
                           degrade=args.degrade,
                           chaos=chaos,
                           host_pool_bytes=args.host_pool_bytes)
    if args.index:
        import os

        if os.path.exists(args.index):
            n = engine.load_index(args.index)
            print(f"loaded {n} prefix digests from {args.index}")
    return cfg, engine


def make_requests(args, cfg):
    """The synthetic request stream. Self-contained: every call
    reproduces the exact same requests (shared system prompt, tails,
    arrivals), so the timed pass serves precisely the stream the warmup
    pass compiled for."""
    from repro.serving import Request

    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab, args.shared_prefix)
    tier_list = (args.tiers.split(",") if args.tiers else [None])
    reqs = [Request(rid=i,
                    prompt=np.concatenate([
                        shared, rng.integers(0, cfg.vocab, 8 + (i % 5))
                    ]).astype(np.int64),
                    max_new_tokens=args.max_new,
                    temperature=0.0 if i % 2 == 0 else 0.7,
                    tier=tier_list[i % len(tier_list)],
                    deadline_s=(args.deadline_ms / 1e3
                                if args.deadline_ms else None))
            for i in range(args.requests)]
    if args.continuous and args.rate > 0:
        t = 0.0
        for r in reqs:
            r.arrival_time = t
            t += float(rng.exponential(1.0 / args.rate))
    return reqs


def serve_passes(args, cfg, engine):
    """Serve the request stream twice: a warm-up pass that compiles,
    then the timed pass. Returns (done, t_warm, dt) in seconds."""
    serve = engine.generate if args.continuous else engine.generate_static
    # Warmup: one full pass compiles every prefill bucket + the decode
    # step, so the timed pass measures steady-state serving.
    t0 = time.perf_counter()
    serve(make_requests(args, cfg))
    t_warm = time.perf_counter() - t0

    reqs = make_requests(args, cfg)  # identical request stream, warm jit
    t1 = time.perf_counter()
    done = serve(reqs)
    dt = time.perf_counter() - t1
    return done, t_warm, dt


def report(args, engine, done, t_warm, dt):
    """Print throughput, latency and the pool counters of a run."""
    total = sum(len(r.out_tokens or ()) for r in done)
    mode = "continuous" if args.continuous else "static"
    print(f"{len(done)} requests, {total} tokens, {dt:.1f}s [{mode}]")
    print(f"  steady-state: {total/dt:.1f} tok/s | "
          f"total incl. compile: {total/(t_warm + dt):.1f} tok/s "
          f"(warmup {t_warm:.1f}s)")
    if args.continuous:
        lat = [r.t_done - r.arrival_time for r in done if r.t_done is not None]
        print(f"  mean request latency: {np.mean(lat)*1e3:.0f} ms "
              f"(rate={args.rate or 'inf'}/s)")
        stats = engine.pool_stats()
        if stats and stats.get("paged"):
            print(f"  paged KV pool: {stats['peak_allocated_blocks']}/"
                  f"{stats['pool_blocks']} blocks peak "
                  f"(block_size={stats['block_size']}) — peak resident "
                  f"{stats['peak_resident_kv_bytes']/1e6:.2f} MB vs "
                  f"{stats['reserved_kv_bytes']/1e6:.2f} MB contiguous "
                  "reservation")
            if stats.get("prefix_cache"):
                print(f"  prefix cache: {stats['prefix_hit_rate']:.0%} of "
                      f"prompt tokens served from resident blocks "
                      f"({stats['prefix_hit_blocks']} block hits, "
                      f"{stats['cow_copies']} CoW copies, "
                      f"{stats['prefix_evictions']} evictions, "
                      f"{stats['retained_prefix_blocks']} retained)")
            if stats.get("host_tier"):
                print(f"  host tier: {stats['host_hit_rate']:.0%} of "
                      f"prompt tokens served warm-from-host "
                      f"({stats['host_hit_blocks']} block hits, "
                      f"{stats['swap_outs']} swap-outs, "
                      f"{stats['swap_ins']} swap-ins, "
                      f"{stats['host_blocks']} resident / "
                      f"{stats['host_bytes']/1e6:.2f} MB of "
                      f"{stats['host_pool_bytes']/1e6:.2f} MB budget, "
                      f"{stats['host_evictions']} host evictions)")
            if stats.get("chunked_prefill"):
                print(f"  chunked prefill: {stats['prefill_chunks_run']} "
                      f"chunks (budget={stats['prefill_budget']}), "
                      f"{stats['decode_steps_stalled']} decode steps "
                      f"shared a step with a chunk, "
                      f"{stats['prefill_tokens_per_step']:.1f} prefill "
                      f"tok/step")
            if stats.get("speculate"):
                print(f"  speculative decode: k={stats['speculate']}, "
                      f"{stats['spec_accepted_tokens']}/"
                      f"{stats['spec_draft_tokens']} drafts accepted "
                      f"({stats['spec_acceptance_rate']:.0%}) over "
                      f"{stats['spec_rounds']} rounds, "
                      f"{stats['spec_verify_rows']} rows in "
                      f"{stats['spec_verify_calls']} verify calls")
            if stats.get("tier_serving"):
                print("  precision tiers:")
                for name, tc in stats["tiers"].items():
                    if not tc["requests"]:
                        continue
                    line = (f"    {name}: {tc['requests']} requests, "
                            f"{tc['tokens']} tokens, "
                            f"{tc['decode_calls']} decode calls")
                    if tc["spec_draft_tokens"]:
                        line += (f", {tc['spec_accepted_tokens']}/"
                                 f"{tc['spec_draft_tokens']} drafts "
                                 f"accepted "
                                 f"({tc['spec_acceptance_rate']:.0%})")
                    print(line)
        elif stats:
            print(f"  contiguous KV cache: "
                  f"{stats['resident_kv_bytes']/1e6:.2f} MB resident "
                  "(full per-slot reservation)")
        if stats:
            failed = [r for r in done if r.error]
            if (failed or stats["preemptions"] or stats["deadline_misses"]
                    or stats["pool_pressure_events"]):
                print(f"  lifecycle: {stats['preemptions']} preemptions "
                      f"(policy={stats['victim_policy']}), "
                      f"{stats['deadline_misses']} deadline misses, "
                      f"{stats['cancellations']} cancellations, "
                      f"{stats['pool_pressure_events']} pressure events, "
                      f"{stats['head_bypasses']} head-of-line bypasses, "
                      f"{stats['degraded_requests']} degraded admissions")
            if stats["chaos"]:
                ch = stats["chaos"]
                fired = ", ".join(f"{k}={v}" for k, v in ch["fired"].items())
                print(f"  chaos: seed={ch['seed']} "
                      f"{ch['total_fired']} faults fired ({fired}); "
                      f"{stats['kernel_fallbacks']} reference-backend "
                      f"fallbacks, {stats['nan_logit_events']} NaN-logit "
                      f"retirements, {stats['callback_errors']} callback "
                      f"errors survived")
            for r in failed[:4]:
                print(f"  req {r.rid} failed: {r.error}")
    print(f"  quant={args.policy or args.quant or 'off'} "
          f"kv_int8={args.kv_int8}")
    for r in sorted(done, key=lambda r: r.rid)[:4]:
        print(f"  req {r.rid}: {(r.out_tokens or [])[:10]}")


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    cfg, engine = build_engine(args)
    done, t_warm, dt = serve_passes(args, cfg, engine)
    report(args, engine, done, t_warm, dt)
    if args.plans:
        from repro.kernels import get_registry

        n = get_registry().save_plans(args.plans)
        print(f"saved {n} block plans to {args.plans}")
    if args.index:
        n = engine.save_index(args.index)
        print(f"saved {n} prefix digests to {args.index}")


if __name__ == "__main__":
    main()
