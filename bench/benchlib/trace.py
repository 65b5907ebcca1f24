"""Reduction of a profiler trace to device busy time, per-op and
per-program device time, device time by the program's device scope, and
idle gaps named by the host span they fell in.

`read(dir)` turns the `.xplane.pb` the JAX profiler wrote into a `Trace`
of plain tuples (name, start_ns, duration_ns): the device's operations
(its "XLA Ops" line), its programs ("XLA Modules"), the harness's own
host spans (`TraceAnnotation`s whose names start with "bench."), and the
program's spans ("serving.*", with their arguments). Each operation's
device scope (`op_scope`) is the innermost `jax.named_scope` of `scopes`
on its op-name path, which the optimized HLO of its program holds: the
profiler stores that HLO in the trace's "/host:metadata" plane, one
"Hlo Proto" per program, under the program's name as the "XLA Modules"
events give it. All the arithmetic below works on that structure, so it
can be checked on a synthetic trace.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]        # name, start ns, duration ns
Span = Tuple[str, float, float, dict]   # name, start ns, duration ns, args

HOST_PREFIX = "bench."
PROGRAM_PREFIX = "serving."
# The program's device scopes an operation is attributed to; a model
# module's `SCOPES` adds its own (see `spec.model`).
SCOPES = frozenset({"embed", "attention", "serve_matmul", "head", "sample"})
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


@dataclasses.dataclass
class Trace:
    ops: List[Event]
    modules: List[Event]
    host: List[Event]
    planes: List[str] = dataclasses.field(default_factory=list)
    spans: List[Span] = dataclasses.field(default_factory=list)
    # (op name, start ns) of each operation -> its innermost scope or None
    op_scope: Dict[Tuple[str, float], Optional[str]] = dataclasses.field(
        default_factory=dict)


def read(trace_dir: str, scopes: Iterable[str] = SCOPES) -> Optional[Trace]:
    """The first accelerator plane of the newest xplane file under
    `trace_dir`, or None where there is none."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return None
    pd = ProfileData.from_file(files[-1])
    ops, modules, host, spans, names = [], [], [], [], []
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
                for e in ln.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.name, float(e.start_ns),
                                     float(e.duration_ns)))
                    elif e.name.startswith(PROGRAM_PREFIX):
                        spans.append((e.name, float(e.start_ns),
                                      float(e.duration_ns), dict(e.stats)))
    if not device_seen:
        return None
    op_names = hlo_op_names(files[-1], {n for n, _, _ in modules})
    return Trace(ops, modules, host, names, spans,
                 scopes_of(ops, modules, op_names, frozenset(scopes)))


def _events(line) -> List[Event]:
    """Events of one line. An operation's event name is its HLO text
    ("%fusion.3 = bf16[...] fusion(...)"); it is cut to the name."""
    return [(e.name.split(" = ", 1)[0].lstrip("%"), float(e.start_ns),
             float(e.duration_ns)) for e in line.events]


# -- the op-name paths of the programs' HLO --------------------------------
# The xplane file is a protobuf (tsl/profiler/protobuf/xplane.proto) that
# `ProfileData` does not expose below the event level, and the HLO it
# holds is one too (xla/service/hlo.proto). Only these fields are read:
#   XSpace.planes 1; XPlane.name 2, .event_metadata 4 (map entry: key 1,
#   value 2), .stat_metadata 5 (map entry: value 2); XEventMetadata.name
#   2, .stats 5; XStatMetadata.id 1, .name 2; XStat.metadata_id 1,
#   .bytes_value 6; HloProto.hlo_module 1; HloModuleProto.computations 3;
#   HloComputationProto.instructions 2; HloInstructionProto.name 1,
#   .metadata 7; OpMetadata.op_name 2.

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf):
    """(field number, value) of each field of one protobuf message: an int
    for a varint, a memoryview of the bytes otherwise."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield num, v


def _only(buf, num: int) -> list:
    return [v for f, v in _fields(buf) if f == num]


def hlo_op_names(path: str, modules=None) -> Dict[str, Dict[str, str]]:
    """{program: {instruction: op-name path}} of the programs named in
    `modules` (all where None), from the HLO the profiler stored in the
    trace at `path`."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for plane in _only(space, 1):
        if not any(bytes(v).decode() == METADATA_PLANE
                   for v in _only(plane, 2)):
            continue
        stat_ids = set()
        for entry in _only(plane, 5):
            for meta in _only(entry, 2):
                fs = dict(_fields(meta))
                if bytes(fs.get(2, b"")).decode() == HLO_PROTO_STAT:
                    stat_ids.add(fs.get(1))
        for entry in _only(plane, 4):
            for meta in _only(entry, 2):
                name = "".join(bytes(v).decode() for v in _only(meta, 2))
                if modules is not None and name not in modules:
                    continue
                for stat in _only(meta, 5):
                    fs = dict(_fields(stat))
                    if fs.get(1) in stat_ids and 6 in fs:
                        out[name] = _instruction_op_names(fs[6])
    return out


def _instruction_op_names(hlo_proto) -> Dict[str, str]:
    out = {}
    for module in _only(hlo_proto, 1):
        for comp in _only(module, 3):
            for ins in _only(comp, 2):
                name = op = None
                for f, v in _fields(ins):
                    if f == 1:
                        name = bytes(v).decode()
                    elif f == 7:
                        op = "".join(bytes(w).decode() for w in _only(v, 2))
                if name and op:
                    out[name] = op
    return out


def innermost(path: str, scopes) -> Optional[str]:
    """The last component of an op-name path that is one of `scopes`."""
    for part in reversed(path.split("/")):
        if part in scopes:
            return part
    return None


def scopes_of(ops: Sequence[Event], modules: Sequence[Event],
              op_names: Dict[str, Dict[str, str]],
              scopes) -> Dict[Tuple[str, float], Optional[str]]:
    """(name, start) of each operation -> the innermost of `scopes` on
    its op-name path in the program whose event it starts in, or None."""
    mods = sorted(modules, key=lambda e: e[1])
    starts = [s for _, s, _ in mods]
    out = {}
    for name, start, _ in ops:
        i = bisect.bisect_right(starts, start) - 1
        path = None
        if i >= 0 and start <= mods[i][1] + mods[i][2]:
            path = op_names.get(mods[i][0], {}).get(name)
        out[(name, start)] = innermost(path, scopes) if path else None
    return out


# -- interval arithmetic ---------------------------------------------------

def union(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals covered by `events`."""
    spans = sorted((s, s + d) for _, s, d, *_ in events if d > 0)
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


def program_spans(tr: Trace, name: str) -> List[Span]:
    """The program's `serving.<name>` spans, with their arguments."""
    return [s for s in tr.spans if s[0] == PROGRAM_PREFIX + name]


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


def scope_ns(tr: Trace, spans) -> Dict[Optional[str], float]:
    """Device ns of the leaf operations that start inside `spans` (merged
    intervals), by innermost scope (None: in none of them)."""
    starts = [s for s, _ in spans]
    out: Dict[Optional[str], float] = {}
    for name, start, d in leaves(tr.ops):
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < spans[i][1]:
            scope = tr.op_scope.get((name, start))
            out[scope] = out.get(scope, 0.0) + d
    return out


def step_scopes(tr: Trace) -> Optional[dict]:
    """Per `serving.step` span of the trace: the device ms of each scope
    (None: unscoped) and the device's busy ms inside the step spans; None
    where the trace has no step span."""
    spans = program_spans(tr, "step")
    if not spans:
        return None
    steps, n = union(spans), len(spans)
    by_scope = scope_ns(tr, steps)
    return {"steps": n,
            "ms": {k: 1e-6 * v / n for k, v in by_scope.items()},
            "busy_ms": 1e-6 * overlap(union(tr.ops), steps) / n}


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
    window, each named by the span that covers most of it, every instant
    going to the innermost `bench.*` or `serving.*` span over it ("none"
    where the host was in no span). A harness span is named without its
    "bench." prefix, a program span in full."""
    busy = union(tr.ops)
    lo, hi = window(tr)
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [(e[0], e[1], e[1] + e[2]) for e in tr.host + tr.spans
             if e[2] > 0]
    out = []
    for g in gaps[:n]:
        cover = _innermost_cover(g, named)
        best = max(sorted(cover), key=cover.get) if cover else None
        if best is None or cover[best] <= 0:
            label = "none"
        elif best.startswith(HOST_PREFIX):
            label = best[len(HOST_PREFIX):]
        else:
            label = best
        out.append([label, (g[1] - g[0]) * 1e-9])
    return out


def _innermost_cover(gap, named) -> Dict[str, float]:
    """How much of `gap` each span name holds, every piece of it going to
    the shortest span that covers it."""
    lo, hi = gap
    inside = [(n, max(s, lo), min(e, hi), e - s) for n, s, e in named
              if s < hi and e > lo]
    cuts = sorted({lo, hi} | {x for _, s, e, _ in inside for x in (s, e)})
    cover: Dict[str, float] = {}
    for a, b in zip(cuts, cuts[1:]):
        over = [(length, n) for n, s, e, length in inside
                if s <= a and e >= b]
        if over:
            name = min(over)[1]
            cover[name] = cover.get(name, 0.0) + b - a
    return cover


def matching(events: Sequence[Event], patterns: Sequence[str]) -> List[Event]:
    """Events whose name contains any of `patterns`."""
    return [e for e in events if any(p in e[0] for p in patterns)]
