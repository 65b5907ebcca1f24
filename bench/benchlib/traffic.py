"""The one traffic generator. A traffic mix is a JSON file of parameters
under `bench/traffic/`; this module turns it, a context length, a
vocabulary and a seed into requests.

Two loops:

  * "open": independent users. Sessions start at exponential gaps and
    their turns follow after a think time, whether or not the previous
    turn has been answered. A session may belong to a tenant, whose fixed
    system prompt opens every one of its prompts; a follow-up turn's
    prompt is the previous prompt plus a synthetic previous answer of the
    drawn output length plus the new user turn. Arrivals run from
    `-lead_in_s` to the end of the window (times relative to its start).
  * "closed": `clients` callers, each sending its next request when the
    previous one completes. Each client's first request is cut to a
    uniform share of its drawn output (`stagger`), so completions are
    spread as in steady state from the start.

The sizes, session shapes, gaps, think times and greedy flags come from
the mix's fixed `shape_seed`, so every seed gets the same work; the run's
seed draws the token ids. In the open loop it also draws the order: it
permutes the gaps between session starts and which session starts at
which of them. (Where a session's later turns would fall past the
window's end they are not sent, so the set of requests is the same up to
those.) In the closed loop the order is the work: which of the 32 lanes
is admitted first decides how many slots are prefilling in the window,
and seeds that permuted the lanes read output tokens/s 41.6-44.8 (one
TPU v5e, stablelm-12b-pp4 batch-decode) where two runs of one seed read
the same. So there every seed sends the same sizes in the same order.
Lengths are lognormal (median, sigma) rounded and clipped to [min, max].
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Planned:
    rid: int
    prompt: np.ndarray          # int32 token ids
    max_new: int
    temperature: float
    arrival: Optional[float] = None   # seconds from the window's start
    client: Optional[int] = None


def lognormal_ints(rng, d: dict, n: int) -> np.ndarray:
    x = d["median"] * np.exp(d["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), d["min"], d["max"]).astype(np.int64)


def max_prompt(spec: dict, ctx: int) -> int:
    """The longest prompt the mix can send under context `ctx`."""
    user, out = spec["user"], spec["output"]
    n = user["max"]
    ten, turns = spec.get("tenants"), spec.get("turns")
    if ten:
        n += ten["system_max"]
    if turns:
        n += (turns["max"] - 1) * (user["max"] + out["max"])
    return min(n, ctx - out["min"])


def zipf_shares(count: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, count + 1) ** s
    return w / w.sum()


class _Ids:
    def __init__(self):
        self.n = 0

    def __call__(self) -> int:
        self.n += 1
        return self.n - 1


def _flags(shape, n: int, share: float) -> np.ndarray:
    """Exactly round(share * n) True flags in a fixed shuffled order."""
    f = np.zeros(n, bool)
    f[:int(round(share * n))] = True
    shape.shuffle(f)
    return f


def open_loop(spec: dict, ctx: int, vocab: int, seed: int, seconds: float,
              rate: Optional[float] = None):
    """(prewarm prompts, planned requests sorted by arrival)."""
    rate = spec["rate_per_s"] if rate is None else rate
    shape = np.random.default_rng(spec["shape_seed"])
    rng = np.random.default_rng(seed)
    ten, turns = spec.get("tenants"), spec.get("turns")
    horizon = spec["lead_in_s"] + seconds
    mean_turns = (turns["min"] + turns["max"]) / 2 if turns else 1.0
    n = max(1, int(round(horizon * rate / mean_turns)))
    # Fixed shapes, drawn in session order.
    n_turns = (shape.integers(turns["min"], turns["max"] + 1, n) if turns
               else np.ones(n, np.int64))
    tenant = (shape.choice(ten["count"], n, p=zipf_shares(ten["count"],
                                                          ten["zipf"]))
              if ten else np.full(n, -1))
    total = int(n_turns.sum())
    users = lognormal_ints(shape, spec["user"], total)
    outs = lognormal_ints(shape, spec["output"], total)
    think = (np.exp(np.log(turns["think_median_s"]) + turns["think_sigma"]
                    * shape.standard_normal(total)) if turns
             else np.zeros(total))
    greedy = _flags(shape, total, spec["greedy_share"])
    gaps = shape.exponential(1.0, n)
    gaps = rng.permutation(gaps * (horizon / gaps.sum()))
    order = rng.permutation(n)           # the session at each start
    sys_len = (shape.integers(ten["system_min"], ten["system_max"] + 1,
                              ten["count"]) if ten else [])
    starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) - spec["lead_in_s"]
    systems = [rng.integers(0, vocab, int(k)).astype(np.int32)
               for k in sys_len]
    first_turn = np.concatenate([[0], np.cumsum(n_turns)[:-1]])
    ids, reqs = _Ids(), []
    for s, start in zip(order, starts):
        t0 = first_turn[s]
        ctx_toks = systems[tenant[s]] if ten else np.zeros(0, np.int32)
        at = start
        for j in range(n_turns[s]):
            i = t0 + j
            if j:
                at += think[i]
                answer = rng.integers(0, vocab, outs[i - 1]).astype(np.int32)
                ctx_toks = np.concatenate([ctx_toks, answer])
            user = rng.integers(0, vocab, users[i]).astype(np.int32)
            ctx_toks = np.concatenate([ctx_toks, user])
            if len(ctx_toks) + outs[i] > ctx or at >= seconds:
                break
            reqs.append(Planned(ids(), ctx_toks, int(outs[i]),
                                0.0 if greedy[i] else spec["temperature"],
                                arrival=float(at)))
    reqs.sort(key=lambda r: r.arrival)
    for k, r in enumerate(reqs):
        r.rid = k
    prewarm = systems if ten and ten.get("prewarm") else []
    return prewarm, reqs


def closed_loop(spec: dict, ctx: int, vocab: int,
                seed: int) -> List[List[Planned]]:
    """Per client, the `per_client` requests it sends in turn (the first
    staggered)."""
    shape = np.random.default_rng(spec["shape_seed"])
    rng = np.random.default_rng(seed)
    c, per_client = spec["clients"], spec["per_client"]
    total = c * per_client
    users = lognormal_ints(shape, spec["user"], total)
    outs = lognormal_ints(shape, spec["output"], total)
    outs = np.minimum(outs, ctx - users)
    greedy = _flags(shape, total, spec["greedy_share"])
    frac = shape.uniform(0.0, 1.0, c)
    if spec.get("stagger"):
        first = np.arange(c) * per_client
        outs[first] = np.maximum(spec["output"]["min"],
                                 np.ceil(frac * outs[first])).astype(np.int64)
    ids, clients = _Ids(), []
    for client in range(c):
        queue = []
        for j in range(per_client):
            i = client * per_client + j
            prompt = rng.integers(0, vocab, users[i]).astype(np.int32)
            queue.append(Planned(ids(), prompt, int(outs[i]),
                                 0.0 if greedy[i] else spec["temperature"],
                                 client=client))
        clients.append(queue)
    return clients

