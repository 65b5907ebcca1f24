"""Reduction of a profiler trace to device busy time, per-op and
per-program device time, and idle gaps named by the host span they fell
in.

`read(dir)` turns the `.xplane.pb` the JAX profiler wrote into a `Trace`
of plain tuples (name, start_ns, duration_ns): the device's operations
(its "XLA Ops" line), its programs ("XLA Modules") and the harness's own
host spans (`TraceAnnotation`s whose names start with "bench."). All the
arithmetic below works on that structure, so it can be checked on a
synthetic trace.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]        # name, start ns, duration ns

HOST_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    ops: List[Event]
    modules: List[Event]
    host: List[Event]
    planes: List[str] = dataclasses.field(default_factory=list)


def read(trace_dir: str) -> Optional[Trace]:
    """The first accelerator plane of the newest xplane file under
    `trace_dir`, or None where there is none."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return None
    pd = ProfileData.from_file(files[-1])
    ops, modules, host, names = [], [], [], []
    device_seen = False
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        names.append(f"{plane.name}: {sorted(lines)}")
        if plane.name.startswith("/device:") and not device_seen:
            if "XLA Ops" not in lines:
                continue
            device_seen = True
            ops = _events(lines["XLA Ops"])
            if "XLA Modules" in lines:
                modules = _events(lines["XLA Modules"])
        elif plane.name.startswith("/host:"):
            for ln in lines.values():
                host.extend(e for e in _events(ln)
                            if e[0].startswith(HOST_PREFIX))
    if not device_seen:
        return None
    return Trace(ops, modules, host, names)


def _events(line) -> List[Event]:
    """Events of one line. An operation's event name is its HLO text
    ("%fusion.3 = bf16[...] fusion(...)"); it is cut to the name."""
    return [(e.name.split(" = ", 1)[0].lstrip("%"), float(e.start_ns),
             float(e.duration_ns)) for e in line.events]


def union(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals covered by `events`."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    out: List[List[float]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(a: Sequence[Tuple[float, float]],
            b: Sequence[Tuple[float, float]]) -> float:
    """Total length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def busy_ns(tr: Trace) -> float:
    return sum(e - s for s, e in union(tr.ops))


def window(tr: Trace) -> Tuple[float, float]:
    """From the first to the last event of the trace, host or device."""
    ev = tr.ops + tr.host
    return (min(s for _, s, _ in ev), max(s + d for _, s, d in ev))


def host_spans(tr: Trace, name: str) -> List[Tuple[float, float]]:
    return union([e for e in tr.host if e[0] == HOST_PREFIX + name])


def busy_share_of(tr: Trace, spans) -> Optional[float]:
    """Share of the time inside `spans` in which the device was busy."""
    total = sum(e - s for s, e in spans)
    if total <= 0:
        return None
    return overlap(union(tr.ops), spans) / total


def leaves(events: Sequence[Event]) -> List[Event]:
    """Events that hold no other event: a loop's operation (the scan over
    layers is one `while`) spans the operations of its body, which are
    kept instead."""
    ev = sorted(events, key=lambda e: (e[1], -e[2]))
    return [e for i, e in enumerate(ev)
            if not (i + 1 < len(ev) and ev[i + 1][1] < e[1] + e[2]
                    and ev[i + 1][1] + ev[i + 1][2] <= e[1] + e[2])]


def top_ops(tr: Trace, n: int = 10) -> List[list]:
    """The `n` device operations (leaves) with the most total time, in
    seconds."""
    tot = {}
    for name, _, d in leaves(tr.ops):
        tot[name] = tot.get(name, 0.0) + d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def idle_gaps(tr: Trace, n: int = 10) -> List[list]:
    """The `n` longest gaps between device operations inside the traced
    window, each named by the host span that covers most of it
    ("none" where the harness was in no span)."""
    busy = union(tr.ops)
    lo, hi = window(tr)
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    names = sorted({e[0] for e in tr.host})
    spans = {k: union([e for e in tr.host if e[0] == k]) for k in names}
    out = []
    for g in gaps[:n]:
        cover = {k: overlap([g], v) for k, v in spans.items()}
        best = max(cover, key=cover.get) if cover else None
        label = (best[len(HOST_PREFIX):] if best and cover[best] > 0
                 else "none")
        out.append([label, (g[1] - g[0]) * 1e-9])
    return out


def matching(events: Sequence[Event], patterns: Sequence[str]) -> List[Event]:
    """Events whose name contains any of `patterns`."""
    return [e for e in events if any(p in e[0] for p in patterns)]
