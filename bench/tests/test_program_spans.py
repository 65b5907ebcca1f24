"""The program's own spans, read back from a CPU profiler trace, agree
with what the harness records on the same run: each prefill chunk's start
and length (`_observe_chunks`) and each step's decode rows and keys (the
tokens `on_token` saw). No reader of the benchmark reads these spans yet;
this is the record a reader that re-points those harness pieces at them
would have to reproduce."""
import glob
import os

import jax
import numpy as np
from jax.profiler import ProfileData

from benchlib import harness, serve, spec

SEED = 2**33 + 5


def program_spans(trace_dir) -> list:
    """(name, start ns, duration ns, args) of the program's `serving.*`
    spans in the trace under `trace_dir`."""
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return [(e.name, e.start_ns, e.duration_ns, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name.startswith("serving.")]


def test_spans_match_the_harness_record(tiny, tmp_path):
    root, bench = tiny
    m = spec.config(bench, "stablelm-12b-pp4", root)
    model = spec.model(m["model"], root / "bench")
    rec = harness.Recorder()
    engine = serve.build_engine(model, m, SEED, model.make_params(m, SEED),
                                rec.on_token)
    sched = engine.scheduler()
    harness._observe_chunks(sched, rec)
    rng = np.random.default_rng(SEED)
    # longer than one 32-token chunk, and more requests than slots
    for i, (n, new) in enumerate([(70, 6), (20, 4), (45, 5), (9, 3),
                                  (33, 4), (64, 2)]):
        req = harness.request(i, rng.integers(0, m["vocab"], n), new, 0.0)
        rec.track(req, 0.0)
        sched.submit(req)
    jax.profiler.start_trace(str(tmp_path))
    harness.drain(sched, rec, jax.profiler.TraceAnnotation, cap_s=300.0)
    jax.profiler.stop_trace()

    spans = sorted(program_spans(str(tmp_path)), key=lambda sp: sp[1])
    chunks = [sp[3] for sp in spans if sp[0] == "serving.chunk"]
    seen = [c for s in rec.steps for c in s.chunks]
    assert len(seen) >= 6
    assert [(c["start"], c["len"]) for c in chunks] == [
        (c["start"], c["len"]) for c in seen]
    assert all(c["plans"] >= 1 for c in chunks)

    steps = [sp for sp in spans if sp[0] == "serving.step"]
    assert len(steps) == len(rec.steps)
    decodes = {}
    for sp in spans:
        if sp[0] == "serving.decode":
            owner = [i for i, st in enumerate(steps)
                     if st[1] <= sp[1] <= st[1] + st[2]]
            assert len(owner) == 1
            decodes[owner[0]] = sp[3]
    rows = [decodes[i]["rows"] if i in decodes else 0
            for i in range(len(steps))]
    keys = [decodes[i]["keys"] if i in decodes else 0
            for i in range(len(steps))]
    assert rows == [len(s.decode_keys) for s in rec.steps]
    assert keys == [sum(s.decode_keys) for s in rec.steps]
    assert sum(rows) > 0
    assert [sp[3]["step"] for sp in steps] == list(
        range(steps[0][3]["step"], steps[0][3]["step"] + len(steps)))
