"""The comparison that decides `correct`.

After the window, a sample of the greedy requests the program finished,
drawn from the seed with the longest among them, is read by the plain
reference of the configuration (`references/<name>.py`) over each prompt
with its served tokens. At every served position the gap is the
reference's best logit minus the reference's logit of the token the
program served; `max_logit_gap` is the widest gap over the sample. The
served tokens went through everything the window timed: the packed
weights, the prefill chunks after prefix hits, paged attention over the
int8 pool and decoding through the cache.
"""
from __future__ import annotations

from pathlib import Path
from typing import List

import numpy as np

from benchlib import spec

def sample(finished: list, seed: int, tokens: int, max_requests: int) -> list:
    """The longest finished greedy request, then others in an order drawn
    from the seed, until `tokens` served tokens or `max_requests`."""
    greedy = [r for r in finished if r.temperature == 0.0 and not r.error
              and r.out_tokens]
    if not greedy:
        return []
    greedy.sort(key=lambda r: (-len(r.out_tokens), r.rid))
    rest = greedy[1:]
    order = np.random.default_rng(seed).permutation(len(rest))
    out, n = [greedy[0]], len(greedy[0].out_tokens)
    for i in order:
        if n >= tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += len(rest[i].out_tokens)
    return out


def sequences(reqs: list) -> List[tuple]:
    """(tokens the model reads, first scored position, served tokens)."""
    out = []
    for r in reqs:
        prompt = np.asarray(r.prompt, np.int32)
        served = np.asarray(r.out_tokens, np.int32)
        toks = np.concatenate([prompt, served[:-1]])
        out.append((toks, len(prompt) - 1, served))
    return out


def reference_gaps(m: dict, seed: int, seqs, base: Path, control=None):
    """Per sequence, {"served": gaps}, plus {"control": gaps} of the
    `control` picks (one array of tokens per sequence) read in the same
    reference pass."""
    ref = spec.reference(m["reference"], base)
    picks = []
    for i, (toks, first, served) in enumerate(seqs):
        p = {"served": served}
        if control is not None:
            p["control"] = control[i]
        picks.append((toks, first, p))
    return ref.logit_gaps(m, seed, picks)


def check(m: dict, seed: int, seqs, errors: int, base: Path,
          control=None) -> dict:
    """{"served": checks} of the program's tokens and, where `control`
    picks are given, {"control": checks} of those picks put in the served
    tokens' place and held to the same limits. Each check is
    {"value", "limit"}."""
    gaps = reference_gaps(m, seed, seqs, base, control) if seqs else []
    cc = m["correct"]
    out = {}
    for name in ("served", "control") if control is not None else ("served",):
        g = (np.concatenate([x[name] for x in gaps]) if gaps
             else np.zeros(0, np.float32))
        out[name] = {
            "request_errors": {"value": errors, "limit": 0},
            "compared_tokens": {"value": int(len(g)),
                                "limit": cc["min_compared"]},
            "max_logit_gap": {"value": float(np.max(g)) if len(g) else None,
                              "limit": cc["limit"]},
        }
    return out


def passed(checks: dict) -> bool:
    """Every check within its limit; `compared_tokens` is a floor."""
    for name, c in checks.items():
        v, lim = c["value"], c["limit"]
        if v is None or lim is None:
            return False
        if (v < lim) if name == "compared_tokens" else (v > lim):
            return False
    return True
