"""End-to-end statistics over one window, from host-clock timestamps.

Every request carries its scheduled arrival and the host times at which
each of its tokens reached the host (`Timeline`). Nothing here reads the
program.

Open loop: the requests that arrived in the window are followed through
the drain that comes after it. Time to first token counts from the
scheduled arrival; a request with no first token by the end of the drain
is counted at the drain's end (its true TTFT is larger) and misses both
limits. Inter-token latency (ITL) is every gap between consecutive
tokens of those requests. Where `limits` are given (the knee sweep), a
request meets them when it finished without error, its TTFT is within
`ttft_ms` and its mean gap between tokens (time per output token) within
`itl_ms`.

Closed loop: output tokens that reached the host inside the window, over
the window's length, and every gap whose later token lies in the window.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Timeline:
    arrival: float                     # scheduled (open) or sent (closed)
    n_prompt: int
    max_new: int
    tokens: List[float] = dataclasses.field(default_factory=list)
    done: bool = False
    error: Optional[str] = None


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def gaps(t: Timeline) -> np.ndarray:
    return np.diff(np.asarray(t.tokens, np.float64))


def open_loop(tl: Dict[int, Timeline], t0: float, seconds: float,
              t_end: float, limits: Optional[dict] = None) -> dict:
    """Metrics of the requests whose arrival lies in [t0, t0 + seconds)."""
    sent = [t for t in tl.values() if t0 <= t.arrival < t0 + seconds]
    ttft, itl, met, failed = [], [], 0, 0
    for t in sent:
        first = t.tokens[0] if t.tokens else t_end
        ttft.append(first - t.arrival)
        g = gaps(t)
        itl.extend(g.tolist())
        ok = t.done and t.error is None
        failed += not ok
        tpot = float(g.mean()) if len(g) else 0.0
        met += bool(limits and ok and ttft[-1] * 1e3 <= limits["ttft_ms"]
                    and tpot * 1e3 <= limits["itl_ms"])
    out = {"attempted": len(sent), "failed": failed}
    if sent:
        out["ttft_p90_ms"] = percentile(ttft, 90) * 1e3
        if limits:
            out["slo_attainment"] = 100.0 * met / len(sent)
    if itl:
        out["itl_p95_ms"] = percentile(itl, 95) * 1e3
    return out


def closed_loop(tl: Dict[int, Timeline], t0: float, seconds: float) -> dict:
    t1 = t0 + seconds
    tokens, itl = 0, []
    for t in tl.values():
        ts = np.asarray(t.tokens, np.float64)
        tokens += int(((ts >= t0) & (ts < t1)).sum())
        if len(ts) > 1:
            later = ts[1:]
            itl.extend(np.diff(ts)[(later >= t0) & (later < t1)].tolist())
    sent = [t for t in tl.values() if t.arrival < t1]
    out = {"attempted": len(sent),
           "failed": sum(t.error is not None for t in sent),
           "output_tok_per_s": tokens / seconds}
    if itl:
        out["itl_p95_ms"] = percentile(itl, 95) * 1e3
    return out
