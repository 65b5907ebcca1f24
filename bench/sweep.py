"""Find an open-loop cell's knee once, on the chip: its latency limits
from the unloaded system, then the share of requests meeting them at a
series of fixed rates.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds 30 \
        --rates 0.5,1,2,3,4 --unloaded 24 --multiple 3

One process, one set-up. First `--unloaded` requests of the mix are
served one at a time on an idle system, after the mix's prewarm (the
tenants' system prompts resident, as in every window); the limits are `--multiple`
times the 90th percentile of their time to first token and of their
mean gap between tokens. Then one window per rate, each with the mix's
lead-in and drain, reports TTFT p90, ITL p95 and the share of requests
that met both limits. Every result is one JSON line. The benchmark's own
runs never run this; the chosen rate goes into the traffic file and the
sweep into PERF.md, by hand.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def unloaded(c, seed: int, n: int, rid0: int) -> dict:
    """p90 TTFT and p90 mean inter-token gap of `n` requests of the mix,
    each served alone, after the mix's prewarm as in a window."""
    import numpy as np

    from benchlib import harness, stats, traffic

    prewarm, plan = traffic.open_loop(c.mix, c.m["context_length"],
                                      c.m["vocab"], seed, 60.0)
    for i, toks in enumerate(prewarm):
        c.sched.submit(harness.request(rid0 - 1 - i, toks, 1, 0.0))
    harness.drain(c.sched, c.rec, c.ann)
    ttft, tpot = [], []
    for p in plan[:n]:
        req = harness.request(p.rid + rid0, p.prompt, p.max_new,
                               p.temperature)
        t = time.perf_counter()
        c.rec.track(req, t)
        c.sched.submit(req)
        while c.sched.num_waiting or c.sched.num_active:
            c.rec.step(c.sched, c.ann)
        tl = c.rec.timelines[req.rid]
        ttft.append(tl.tokens[0] - t)
        g = stats.gaps(tl)
        if len(g):
            tpot.append(float(g.mean()))
    return {"ttft_p90_ms": float(np.percentile(ttft, 90)) * 1e3,
            "tpot_p90_ms": float(np.percentile(tpot, 90)) * 1e3,
            "requests": len(ttft)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--unloaded", type=int, default=24)
    ap.add_argument("--multiple", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

    from benchlib import harness, spec, stats

    bench = spec.load_benchmark(ROOT)
    try:
        c = harness.set_up(bench, args.workload, args.seed, False,
                           t_process=T_PROCESS, root=ROOT)
    except harness.NoChip as e:
        print(f"bench/sweep.py: {e}", file=sys.stderr)
        return 2
    if c.mix["loop"] != "open":
        print("bench/sweep.py: only open-loop cells have a knee",
              file=sys.stderr)
        return 2
    base = unloaded(c, args.seed, args.unloaded, 10**6)
    limits = {"ttft_ms": args.multiple * base["ttft_p90_ms"],
              "itl_ms": args.multiple * base["tpot_p90_ms"]}
    print(json.dumps({"unloaded": base, "multiple": args.multiple,
                      "limits": limits}), flush=True)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        win = harness.run_window(c, args.seed + k + 1, args.seconds,
                                 rate=rate, rid0=(k + 2) * 10**6)
        e2e = stats.open_loop(c.rec.timelines, win["t0"], args.seconds,
                              win["t_end"], limits)
        print(json.dumps({"rate_per_s": rate, **e2e,
                          "steps": len(win["steps"])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
