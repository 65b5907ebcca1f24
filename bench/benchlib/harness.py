"""One run of one cell: set-up, warm-up, the measured window, the traced
reduction and the comparison that decides `correct`.

The window drives the program's `ContinuousScheduler` with `submit()` and
`step()` itself. Every timestamp is the host clock read in the harness's
`on_token` callback; a step ends in a host pull of the sampled tokens, so
these follow the device's completion of the step.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from benchlib import correct, serve, spec, stats, traffic
from benchlib import trace as tr

AUX_RID = 1 << 30          # request ids of warm-up and prewarm requests
TRACE_AT = 0.25            # the traced stretch starts this far into the window
TRACE_S = 6.0              # and lasts this long (at most half the window)
FILL_CAP_S = 300.0         # a closed loop must fill its batch within this


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class StepRecord:
    t0: float
    t1: float = 0.0
    decode_keys: List[int] = dataclasses.field(default_factory=list)
    chunks: List[dict] = dataclasses.field(default_factory=list)
    logits_rows: int = 0
    traced: bool = False


@dataclasses.dataclass
class RunData:
    """What a per-layer metric reader gets."""
    m: dict
    peaks: dict
    steps: List[StepRecord]          # the window's steps
    counters: Dict[str, float]       # program counters, window deltas
    trace: Optional[tr.Trace]
    base: Path
    model: object                    # the configuration's model module

    def traced_steps(self) -> List[StepRecord]:
        return [s for s in self.steps if s.traced]

    def roofline(self, kernel: str):
        return spec.roofline(kernel, self.base)


class Recorder:
    """Token timelines and per-step records, fed by `on_token`."""

    def __init__(self):
        self.timelines: Dict[int, stats.Timeline] = {}
        self.requests: Dict[int, object] = {}
        self.steps: List[StepRecord] = []
        self.current: Optional[StepRecord] = None
        self.traced = False

    def on_token(self, req, tok) -> None:
        t = time.perf_counter()
        tl = self.timelines.get(req.rid)
        if tl is None:
            return
        tl.tokens.append(t)
        if self.current is not None:
            self.current.logits_rows += 1
            idx = len(req.out_tokens) - 1
            if idx >= 1:       # a decode step's token: it read n + idx keys
                self.current.decode_keys.append(tl.n_prompt + idx)

    def track(self, req, arrival: float) -> None:
        self.requests[req.rid] = req
        self.timelines[req.rid] = stats.Timeline(
            arrival, len(req.prompt), req.max_new_tokens)

    def step(self, sched, ann) -> list:
        rec = StepRecord(time.perf_counter(), traced=self.traced)
        self.current = rec
        with ann("bench.step"):
            done = sched.step()
        rec.t1 = time.perf_counter()
        self.current = None
        self.steps.append(rec)
        for r in done:
            tl = self.timelines.get(r.rid)
            if tl is not None:
                tl.done, tl.error = True, r.error
        return done


class CompileCounter:
    """Backend compiles (persistent-cache hits included) and their
    seconds, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.count, self.seconds, self.hits = 0, 0.0, 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


class Tracer:
    """Starts the profiler at the first step boundary past `start` and
    stops it at the first one past `stop`, writing under `out`."""

    def __init__(self, rec: Recorder, out: Optional[str]):
        self.rec, self.out = rec, out
        self.start = self.stop = None
        self.state = "idle"

    def arm(self, start: float, stop: float) -> None:
        self.start, self.stop = start, stop

    def tick(self, now: float) -> None:
        if self.out is None or self.start is None:
            return
        import jax

        if self.state == "idle" and now >= self.start:
            jax.profiler.start_trace(self.out)
            self.state, self.rec.traced = "on", True
        elif self.state == "on" and now >= self.stop:
            self.finish()

    def finish(self) -> None:
        if self.state == "on":
            import jax

            jax.profiler.stop_trace()
            self.state, self.rec.traced = "done", False


def request(rid, prompt, max_new, temperature):
    from repro.serving import Request

    return Request(rid=rid, prompt=np.asarray(prompt, np.int32),
                   max_new_tokens=int(max_new), temperature=float(temperature))


def drain(sched, rec, ann, cap_s: float = 600.0) -> None:
    t_end = time.perf_counter() + cap_s
    while sched.num_waiting or sched.num_active:
        if time.perf_counter() > t_end:
            raise RuntimeError("warm-up did not drain")
        rec.step(sched, ann)


def warm_up(sched, rec, ann, m: dict, mix: dict, seed: int) -> None:
    """Compile every program the cell's traffic can reach and no other:
    one request whose prompt is the longest the mix can send walks every
    chunk shape up to it, then decodes; a sampled request beside it."""
    rng = np.random.default_rng(seed)
    n = traffic.max_prompt(mix, m["context_length"])
    for i, (length, temp) in enumerate([(n, 0.0), (17, 0.7)]):
        sched.submit(request(AUX_RID + i, rng.integers(0, m["vocab"], length),
                              3, temp))
    drain(sched, rec, ann)


def drive_open(sched, rec, ann, tracer, mix, prewarm, plan, seconds,
               on_open):
    """Prewarm, lead-in, window, drain; `on_open()` as the window opens.
    Returns (t0, t_end)."""
    for i, toks in enumerate(prewarm):
        sched.submit(request(AUX_RID + 100 + i, toks, 1, 0.0))
    drain(sched, rec, ann)
    t0 = time.perf_counter() + mix["lead_in_s"]
    tracer.arm(t0 + TRACE_AT * seconds,
               t0 + TRACE_AT * seconds + min(TRACE_S, seconds / 2))
    pending = collections.deque(plan)
    cap = t0 + seconds + mix["drain_cap_s"]
    opened = False
    while True:
        now = time.perf_counter()
        if not opened and now >= t0:
            on_open()
            opened = True
        tracer.tick(now)
        if pending and t0 + pending[0].arrival <= now:
            with ann("bench.submit"):
                while pending and t0 + pending[0].arrival <= now:
                    p = pending.popleft()
                    req = request(p.rid, p.prompt, p.max_new, p.temperature)
                    rec.track(req, t0 + p.arrival)
                    sched.submit(req)
        if not (sched.num_waiting or sched.num_active):
            if not pending:
                break
            with ann("bench.wait"):
                time.sleep(max(0.0, min(0.001, t0 + pending[0].arrival
                                        - time.perf_counter())))
            continue
        if now >= cap:
            break
        rec.step(sched, ann)
    tracer.finish()
    return t0, time.perf_counter()


def drive_closed(sched, rec, ann, tracer, clients, seconds, on_open):
    """Fill the batch (every client has its first token), then the
    window; `on_open()` as it opens. Returns (t0, t_end)."""
    queues = [collections.deque(q) for q in clients]
    owner = {}

    def send(c: int) -> None:
        if not queues[c]:
            return              # the client has sent all it planned: it stops
        p = queues[c].popleft()
        req = request(p.rid, p.prompt, p.max_new, p.temperature)
        owner[p.rid] = c
        rec.track(req, time.perf_counter())
        sched.submit(req)

    for c in range(len(queues)):
        send(c)
    firsts = list(owner)
    t0 = t_fill = time.perf_counter()
    end = None
    while True:
        now = time.perf_counter()
        if end is None:
            if all(rec.timelines[r].tokens for r in firsts):
                t0, end = now, now + seconds
                on_open()
                tracer.arm(t0 + TRACE_AT * seconds,
                           t0 + TRACE_AT * seconds + min(TRACE_S, seconds / 2))
            elif now - t_fill > FILL_CAP_S:
                raise RuntimeError("the closed loop did not fill its batch")
        elif now >= end:
            break
        tracer.tick(now)
        if not (sched.num_waiting or sched.num_active):
            time.sleep(0.001)       # every client has stopped
            continue
        for r in rec.step(sched, ann):
            with ann("bench.submit"):
                send(owner[r.rid])
    tracer.finish()
    return t0, time.perf_counter()


def _pool_counters(sched) -> Dict[str, float]:
    """Every number `pool_stats()` gives (flags and nested groups left
    out)."""
    return {k: float(v) for k, v in sched.pool_stats().items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


@dataclasses.dataclass
class Cell:
    """A cell's program, set up and warmed, ready for windows."""
    m: dict
    mix: dict
    peaks: dict
    dev: object
    device_count: int
    model: object
    engine: object
    sched: object
    rec: Recorder
    ann: object
    compiles: CompileCounter
    base: Path
    t_warm: float


def set_up(bench: dict, cell: str, seed: int, trace: bool, *,
           t_process: float, root: Path = spec.ROOT, require_tpu: bool = True,
           peaks_kind: Optional[str] = None) -> Cell:
    """Weights from the seed on the device, the engine, and the warm-up of
    every shape the cell's traffic uses. `require_tpu=False` and
    `peaks_kind` let a test drive a run on the CPU, with the peaks of a
    named device for the arithmetic."""
    import jax

    base = root / "bench"
    w = spec.workload(bench, cell)
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < w["chips"]):
        raise NoChip(f"JAX found {len(devices)} {dev.platform} device(s); "
                     f"the cell needs {w['chips']} TPU chip(s)")
    peaks = spec.peaks(peaks_kind or dev.device_kind, base)
    m = spec.config(bench, w["config"], root)
    mix = spec.traffic(w["traffic"], base)

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = CompileCounter()
    if require_tpu:
        from repro.kernels import get_registry

        backend = get_registry().active.name
        if backend != "mosaic":
            raise NoChip(f"kernel backend is {backend!r}, not 'mosaic'")
    t_init = time.perf_counter()

    model = spec.model(m["model"], base)
    params = model.make_params(m, seed)
    jax.block_until_ready(params)
    t_weights = time.perf_counter()
    rec = Recorder()
    engine = serve.build_engine(model, m, seed, params, rec.on_token)
    del params
    sched = engine.scheduler()
    t_engine = time.perf_counter()

    ann = jax.profiler.TraceAnnotation if trace else (
        lambda name: contextlib.nullcontext())
    _observe_chunks(sched, rec)
    warm_up(sched, rec, ann, m, mix, seed)
    t_warm = time.perf_counter()
    log(f"init {t_init - t_process:.3f} s, weights {t_weights - t_init:.3f}"
        f" s, engine {t_engine - t_weights:.3f} s, warm-up "
        f"{t_warm - t_engine:.3f} s ({compiles.count} compiles, "
        f"{compiles.seconds:.3f} s, {compiles.hits} persistent-cache hits, "
        f"cache {cache_dir})")
    return Cell(m, mix, peaks, dev, len(devices), model, engine, sched, rec,
                ann, compiles, base, t_warm)


def run_window(c: Cell, seed: int, seconds: float, trace_dir=None,
               rate: Optional[float] = None, rid0: int = 0) -> dict:
    """Drive the cell's traffic through one window (and, open loop, its
    drain). Returns t0, t_end, the window's steps and the program's
    counters as the window opened and closed."""
    rec, sched, mix, m = c.rec, c.sched, c.mix, c.m
    tracer = Tracer(rec, trace_dir)
    n_steps = len(rec.steps)
    counters0 = {}

    def on_open():
        counters0.update(_pool_counters(sched))

    ctx, vocab = m["context_length"], m["vocab"]
    if mix["loop"] == "open":
        prewarm, plan = traffic.open_loop(mix, ctx, vocab, seed, seconds,
                                          rate)
        for p in plan:
            p.rid += rid0
        t0, t_end = drive_open(sched, rec, c.ann, tracer, mix, prewarm,
                               plan, seconds, on_open)
    else:
        clients = traffic.closed_loop(mix, ctx, vocab, seed)
        for p in (p for q in clients for p in q):
            p.rid += rid0
        t0, t_end = drive_closed(sched, rec, c.ann, tracer, clients, seconds,
                                 on_open)
    steps = [s for s in rec.steps[n_steps:] if t0 <= s.t0 < t0 + seconds]
    return {"t0": t0, "t_end": t_end, "steps": steps,
            "counters0": counters0, "counters1": _pool_counters(sched)}


def run_cell(bench: dict, cell: str, seed: int, seconds: float, trace: bool,
             *, t_process: float, root: Path = spec.ROOT,
             require_tpu: bool = True, peaks_kind: Optional[str] = None,
             control=None) -> dict:
    """One run of `cell`; returns the result object the benchmark prints.
    `control(engine, m, seqs)`, where given, picks a token at every
    compared position from the live program (a lower-precision path of
    it), one array per compared sequence; those picks are checked in the
    served tokens' place, and that verdict and its checks come back under
    "control"."""
    c = set_up(bench, cell, seed, trace, t_process=t_process, root=root,
               require_tpu=require_tpu, peaks_kind=peaks_kind)
    m, dev, rec = c.m, c.dev, c.rec
    trace_dir = _trace_dir() if trace else None
    n0 = c.compiles.count
    win = run_window(c, seed, seconds, trace_dir)
    t0, t_end = win["t0"], win["t_end"]
    setup_s = t0 - t_process
    log(f"setup {setup_s:.3f} s, of it traffic before the window "
        f"{t0 - c.t_warm:.3f} s; window {seconds} s, {len(win['steps'])} "
        f"steps, drain ended {t_end - t0 - seconds:.3f} s after it; "
        f"compiles after the warm-up: {c.compiles.count - n0}")

    if c.mix["loop"] == "open":
        e2e = stats.open_loop(rec.timelines, t0, seconds, t_end)
    else:
        e2e = stats.closed_loop(rec.timelines, t0, seconds)
    e2e["setup_s"] = setup_s
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": c.device_count, "memory_peak_bytes": int(mem)}

    result = {"correct": False, "attempted": e2e.pop("attempted"),
              "failed": e2e.pop("failed")}
    metrics, breakdown = {}, None
    if trace:
        trc = tr.read(trace_dir,
                      tr.SCOPES | set(getattr(c.model, "SCOPES", ())))
        _remove(trace_dir)
        if trc is not None:
            log("trace planes: " + " | ".join(trc.planes))
            by_step = tr.step_scopes(trc)
            if by_step is not None:
                log(f"device ms a step over {by_step['steps']} serving.step "
                    f"spans, by scope: {by_step['ms']}; sum "
                    f"{sum(by_step['ms'].values())}, busy inside the steps "
                    f"{by_step['busy_ms']}")
            lo, hi = tr.window(trc)
            device["busy_s"] = tr.busy_ns(trc) * 1e-9
            device["window_s"] = (hi - lo) * 1e-9
            breakdown = {"device_ops": tr.top_ops(trc),
                         "idle_gaps": tr.idle_gaps(trc)}
        c0, c1 = win["counters0"], win["counters1"]
        run = RunData(m, c.peaks, win["steps"],
                      {k: c1[k] - c0.get(k, 0.0) for k in c1}, trc, c.base,
                      c.model)
        for entry in spec.cell_metrics(bench, cell, True):
            v = spec.metric_reader(entry["name"], c.base)(run, entry["name"])
            if v is not None:
                metrics[entry["name"]] = {"value": v, "unit": entry["unit"]}
    else:
        for entry in spec.cell_metrics(bench, cell, False):
            if entry["name"] in e2e:
                metrics[entry["name"]] = {"value": e2e[entry["name"]],
                                          "unit": entry["unit"]}

    finished = [r for rid, r in rec.requests.items()
                if rec.timelines[rid].done]
    cc = m["correct"]
    seqs = correct.sequences(correct.sample(
        finished, seed, cc["sample_tokens"], cc["max_requests"]))
    errors = sum(1 for r in finished if r.error)
    picks = control(c.engine, m, seqs) if control is not None else None
    fallbacks = win["counters1"]["kernel_fallbacks"]
    base = c.base
    # The program's state goes before the reference runs.
    del c, rec, finished
    gc.collect()
    verdicts = correct.check(m, seed, seqs, errors, base, picks)
    for checks in verdicts.values():
        checks["kernel_fallbacks"] = {"value": fallbacks, "limit": 0}
    checks = verdicts["served"]
    if control is not None:
        result["control"] = {"correct": correct.passed(verdicts["control"]),
                             "checks": verdicts["control"]}
    result["correct"] = correct.passed(checks)
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def _observe_chunks(sched, rec: Recorder) -> None:
    """Record each prefill chunk's start and length for the roofline and
    model-operation counts. The program has no span or counter that gives
    them, so they are read from the chunk plan just before it runs."""
    run_chunk = sched._run_chunk

    def observed(slot):
        plan = sched._chunk_plans[slot]
        if rec.current is not None:
            start = plan["next"]
            rec.current.chunks.append(
                {"start": start,
                 "len": min(sched.prefill_budget, plan["n"] - start)})
        return run_chunk(slot)

    sched._run_chunk = observed


def _trace_dir() -> str:
    import tempfile

    return tempfile.mkdtemp(prefix="bench-trace-")


def _remove(path) -> None:
    import shutil

    if path:
        shutil.rmtree(path, ignore_errors=True)
