"""The control of the comparison that decides `correct`: the program's own
next-lower weight precision, read against the same reference.

    python3 bench/control.py --workload <name> --seeds 11,12,13 --seconds 10

For each seed, one run of the cell as `bench/run.py` makes it (set-up,
window at the cell's load, the reference comparison), and beside it the
control: the program's plane-truncated w2a8 view of the same packed
weights (`repro.core.precision.truncate_policy_view`, the path the
program serves its w2 tier from) runs a full forward over every compared
prompt with its served tokens, and the token it puts first at each
compared position goes through the same comparison in the served
token's place (`correct.check` and `correct.passed`, the same limits).
The control has to come out not correct; the served tokens' readings
set the limit's lower end. Each seed prints its checks and the
control's on standard error and one JSON line
{"seed", "correct", "max_logit_gap", "compared_tokens",
"control_correct", "control_max_logit_gap", "limit"}. The benchmark's
own runs never run this.
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
TIER = "w2a8"
PAD = 256


def lower_tier_picks(engine, m: dict, seqs, tier: str = TIER) -> list:
    """Per compared sequence, the argmax tokens of the program's `tier`
    view over its prompt with its served tokens, at the compared
    positions."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.precision import truncate_policy_view
    from repro.models import transformer

    cfg = engine.cfg
    view, _ = truncate_policy_view(engine.params, tier)

    @jax.jit
    def first_choice(params, tokens):
        hidden, _ = transformer.forward_hidden(params, cfg, {"tokens": tokens})
        return jnp.argmax(transformer.compute_logits(params, cfg, hidden), -1)

    out = []
    for toks, first, served in seqs:
        padded = np.zeros(-(-len(toks) // PAD) * PAD, np.int32)
        padded[:len(toks)] = toks
        best = np.asarray(first_choice(view, jnp.asarray(padded)[None]))[0]
        out.append(best[first:len(toks)])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

    from benchlib import harness, spec

    bench = spec.load_benchmark(ROOT)
    t = T_PROCESS
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            r = harness.run_cell(bench, args.workload, seed, args.seconds,
                                 False, t_process=t, root=ROOT,
                                 control=lower_tier_picks)
        except harness.NoChip as e:
            print(f"bench/control.py: {e}", file=sys.stderr)
            return 2
        ctl = r["control"]
        for who, checks in (("served", r["checks"]),
                            ("control", ctl["checks"])):
            for name, c in checks.items():
                print(f"[{who}] {name} {c['value']} limit {c['limit']}",
                      file=sys.stderr)
        gap = r["checks"]["max_logit_gap"]
        print(json.dumps({
            "seed": seed, "correct": r["correct"],
            "max_logit_gap": gap["value"],
            "compared_tokens": r["checks"]["compared_tokens"]["value"],
            "control_correct": ctl["correct"],
            "control_max_logit_gap": ctl["checks"]["max_logit_gap"]["value"],
            "limit": gap["limit"]}), flush=True)
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
