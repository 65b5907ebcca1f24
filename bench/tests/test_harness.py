"""Whole runs of the harness on the CPU at a tiny size: the comparison
that decides `correct` passes on the program as it is, fails when the
timed path is broken underneath it, and its control (the program's w2
path) reads far above what the served tokens read."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from benchlib import harness
from conftest import CHAT_CELL, ROOT, TINY_LIMIT

CELLS = [CHAT_CELL, "stablelm12b-pp4.batch-decode"]
SEED = 2**33 + 17


def run(tiny, cell, trace=False, **kw):
    root, bench = tiny
    return harness.run_cell(bench, cell, SEED, 6.0, trace,
                            t_process=time.perf_counter(), root=root,
                            require_tpu=False, peaks_kind="TPU v5 lite", **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_on_cpu(tiny, cell):
    r = run(tiny, cell)
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    assert r["attempted"] > 0 and r["failed"] == 0
    assert "setup_s" in r["metrics"]
    assert r["checks"]["max_logit_gap"]["value"] < TINY_LIMIT


def test_traced_run_reports_layer_metrics(tiny):
    r = run(tiny, CHAT_CELL, trace=True)
    assert r["correct"]
    # host-clock and counter metrics exist off the chip too; device ones
    # need a device plane in the trace, which the CPU does not give
    assert {"prefix_hit_share", "step_ms.latency", "mfu.latency"} <= set(
        r["metrics"])
    assert "setup_s" not in r["metrics"]


def test_closed_loop_client_stops_when_its_plan_runs_out(tiny):
    """A client that has sent every request it planned stops, and the
    window runs to its end: with one request a client, the batch fills,
    each client's one request finishes, and the run is still judged."""
    root, bench = tiny
    path = root / "bench" / "traffic" / "batch-decode.json"
    mix = json.loads(path.read_text())
    mix["per_client"] = 1
    path.write_text(json.dumps(mix))
    r = run(tiny, "stablelm12b-pp4.batch-decode")
    assert r["correct"], r["checks"]
    assert r["attempted"] <= mix["clients"]


@pytest.mark.parametrize("cell", CELLS)
def test_altered_token_is_not_correct(tiny, monkeypatch, cell):
    """A greedy token altered where it is produced (the sampler) makes
    the run come out not correct."""
    from repro.serving import sampling

    orig = sampling.sample_tokens

    def altered(logits, temperatures, *args):
        tok = np.asarray(orig(logits, temperatures, *args))
        greedy = np.asarray(temperatures) <= 0
        return np.where(greedy, (tok + 1) % logits.shape[-1], tok)

    monkeypatch.setattr(sampling, "sample_tokens", altered)
    r = run(tiny, cell)
    assert not r["correct"]
    assert r["checks"]["max_logit_gap"]["value"] > TINY_LIMIT


@pytest.mark.parametrize("cell", CELLS)
def test_decode_leaving_the_pool_unwritten_is_not_correct(tiny, monkeypatch,
                                                          cell):
    """A decode step that returns the KV pool as it got it (its state
    unchanged) makes the run come out not correct."""
    import dataclasses

    from repro.models import transformer

    orig = transformer._decode_step_paged

    def unwritten(params, cfg, cache, tokens, **kw):
        new, logits = orig(params, cfg, cache, tokens, **kw)
        kv = dataclasses.replace(new.kv, k=cache.kv.k, v=cache.kv.v,
                                 k_scale=cache.kv.k_scale,
                                 v_scale=cache.kv.v_scale)
        return dataclasses.replace(new, kv=kv), logits

    monkeypatch.setattr(transformer, "_decode_step_paged", unwritten)
    r = run(tiny, cell)
    assert not r["correct"]
    assert r["checks"]["max_logit_gap"]["value"] > TINY_LIMIT


@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_far_above_served(tiny, cell):
    sys.path.insert(0, str(ROOT / "bench"))
    import control

    r = run(tiny, cell, control=control.lower_tier_picks)
    assert r["correct"] and not r["control"]["correct"]
    served = r["checks"]["max_logit_gap"]["value"]
    ctl = r["control"]["checks"]["max_logit_gap"]
    assert ctl["value"] > ctl["limit"] == TINY_LIMIT > served
    assert ctl["value"] >= 3 * served


def test_params_match_the_programs_packed_tree(tiny):
    import jax

    from benchlib import spec
    from repro.core.precision import parse_quant_token
    from repro.core.quantized_linear import quantize_params_for_serving
    from repro.models import build_model

    root, bench = tiny
    for c in bench["configs"]:
        m = json.loads((root / c["file"]).read_text())
        model = spec.model(m["model"], root / "bench")
        ours = model.make_params(m, SEED)
        cfg = model.model_config(m)
        theirs = quantize_params_for_serving(
            build_model(cfg).init(jax.random.PRNGKey(0)),
            parse_quant_token(m["serving"]["weights"]), min_size=1024)
        shape = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda a: (a.shape, a.dtype), t)
        assert (jax.tree_util.tree_structure(ours)
                == jax.tree_util.tree_structure(theirs))
        assert shape(ours) == shape(theirs)


def _run_py(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "stablelm12b-pp4.batch-decode", "--seed", "3", "--seconds", "1",
         "--trace", "0", *args], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
