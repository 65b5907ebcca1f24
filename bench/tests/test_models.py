"""The model a configuration runs is the module its file names
(`models/<name>.py`): the dense builder gives the parameters it gave
before it had a module of its own, bit for bit, and a builder that a
later configuration adds as a file alone is the one the harness and
`mfu` use. Every number of `pool_stats()` reaches the readers as its
change over the window."""
import hashlib
import inspect
import json
import time

import jax
import numpy as np
import pytest

from benchlib import harness, spec
from conftest import CHAT_CELL

SEED = 2**33 + 17
CELL = "stablelm12b-pp4.batch-decode"

# sha256 of the params tree (`tree_hash`) that `benchlib/serve.py::
# make_params` gave for SEED on the tiny copy before the dense builder
# moved to `models/dense_transformer.py`.
PARENT_HASHES = {
    "stablelm-12b-pp4":
        "a3ffdee3db0578a2e3b7607d4cc866dc35baf4b6ec8dbcc7c8c11a29ef9026ef",
    "olmo-1b":
        "8b19578d6d0c7e1c178dcdb4160bf9baf9d13f668b8be35c088f0942bdbca2af",
}

# A builder written by a test as a later configuration would add one: it
# serves the dense block, gives the pool one block less, counts twice the
# dense operations, and records each call in calls.jsonl beside it.
PROBE_MODEL = '''
import json
from pathlib import Path

from benchlib import spec

HERE = Path(__file__).resolve().parent
DENSE = spec.model("dense_transformer", HERE.parent)


def _record(name, value):
    with open(HERE / "calls.jsonl", "a") as f:
        f.write(json.dumps([name, value]) + "\\n")
    return value


def model_config(m):
    _record("model_config", m["name"])
    return DENSE.model_config(m)


def make_params(m, seed):
    _record("make_params", seed)
    return DENSE.make_params(m, seed)


def pool_blocks(m, block_size):
    return _record("pool_blocks", DENSE.pool_blocks(m, block_size) - 1)


def flops(m, tokens, keys, logits_rows):
    return _record("flops", 2 * DENSE.flops(m, tokens, keys, logits_rows))
'''

# A reader that hands the test what the harness gives every reader: the
# window's counters, and the chunks its steps ran.
COUNTER_PROBE = '''
import json
from pathlib import Path


def read(run, name):
    out = {"counters": run.counters,
           "chunks": sum(len(s.chunks) for s in run.steps)}
    (Path(run.base) / "counters.json").write_text(json.dumps(out))
    return 1.0
'''


def tree_hash(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}|{a.dtype}|{a.shape}|"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("cell", [CELL, CHAT_CELL])
def test_dense_params_match_the_parent_bit_for_bit(tiny, cell):
    root, bench = tiny
    m = spec.config(bench, spec.workload(bench, cell)["config"], root)
    assert m["model"] == "dense_transformer"
    model = spec.model(m["model"], root / "bench")
    assert tree_hash(model.make_params(m, SEED)) == PARENT_HASHES[m["name"]]


@pytest.fixture(scope="module")
def probe_run(tmp_path_factory):
    """One traced CPU run of the tiny stablelm cell whose configuration
    names the probe builder, with the counter probe among its metrics."""
    import shutil

    from conftest import BENCH, shrink

    root = tmp_path_factory.mktemp("probe")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = shrink(root)
    (root / "bench" / "models" / "probe_model.py").write_text(PROBE_MODEL)
    (root / "bench" / "metrics" / "counter_probe.py").write_text(
        COUNTER_PROBE)
    path = root / "bench" / "configs" / "stablelm-12b-pp4.json"
    m = json.loads(path.read_text())
    m["model"] = "probe_model"
    path.write_text(json.dumps(m))
    bench["per_layer"].append({"name": "counter_probe", "unit": "1",
                               "workloads": [CELL]})
    r = harness.run_cell(bench, CELL, SEED, 6.0, True,
                         t_process=time.perf_counter(), root=root,
                         require_tpu=False, peaks_kind="TPU v5 lite")
    calls = [json.loads(line) for line in
             (root / "bench" / "models" / "calls.jsonl").read_text()
             .splitlines()]
    probe = json.loads((root / "bench" / "counters.json").read_text())
    return r, calls, probe, m


def test_builder_named_by_the_config_is_the_one_used(probe_run):
    r, calls, _, m = probe_run
    assert r["correct"], r["checks"]
    names = [c[0] for c in calls]
    assert names.count("make_params") == 1 and ["make_params", SEED] in calls
    assert "model_config" in names
    from repro.serving import ServingEngine

    block = inspect.signature(ServingEngine).parameters["block_size"].default
    dense = spec.model("dense_transformer")
    assert [v for n, v in calls if n == "pool_blocks"] == [
        dense.pool_blocks(m, block) - 1]
    # mfu is the probe's operations over the steps' time
    assert "flops" in names and r["metrics"]["mfu.throughput"]["value"] > 0


def test_every_pool_counter_arrives_as_its_window_change(probe_run):
    _, _, probe, _ = probe_run
    counters = probe["counters"]
    # flags ("paged", "prefix_cache"), names and nested groups stay out
    assert {"paged", "prefix_cache", "victim_policy", "tiers",
            "chaos"}.isdisjoint(counters)
    assert {"prefix_hit_tokens", "prompt_tokens", "kernel_fallbacks",
            "prefill_chunks_run", "free_blocks", "swap_ins",
            "preemptions"} <= set(counters)
    assert all(isinstance(v, float) for v in counters.values())
    # the warm-up ran chunks before the window: what arrives is the
    # window's own, chunk for chunk with the steps the harness recorded
    # (the last step may start a hair past the window's end)
    assert 0 < probe["chunks"] <= counters["prefill_chunks_run"] <= (
        probe["chunks"] + 1)
    assert counters["kernel_fallbacks"] == 0
