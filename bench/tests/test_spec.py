"""Finding a cell's pieces by name from files alone, and the peaks
table."""
import json

import pytest

from benchlib import spec


def test_peaks_known_and_unknown_device():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.UnknownDevice):
        spec.peaks("TPU v9 imaginary")


def test_new_cell_found_from_files_alone(tmp_path):
    """A new configuration, model module, mix, metric and kernel roofline
    are found by the names BENCHMARK.json gives them, with no code
    edited."""
    b = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics", "roofline", "models"):
        (b / d).mkdir(parents=True)
    (b / "configs" / "toy.json").write_text(
        json.dumps({"name": "toy", "model": "toy_model"}))
    (b / "traffic" / "burst.json").write_text(json.dumps({"loop": "open"}))
    (b / "metrics" / "queue_ms.py").write_text(
        "def read(run, name):\n    return 7.0 if name.endswith('.a') "
        "else 8.0\n")
    (b / "models" / "toy_model.py").write_text(
        "def flops(m, tokens, keys, logits_rows):\n    return 3.0 * tokens\n")
    (b / "roofline" / "my_kernel.py").write_text(
        "NAMES = ('k',)\ndef cost(m, call):\n    return 1.0, 2.0\n")
    bench = {
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy.burst", "config": "toy",
                       "traffic": "burst", "chips": 1}],
        "end_to_end": [{"name": "tok_s"},
                       {"name": "ttft", "workloads": ["other"]}],
        "per_layer": [{"name": "queue_ms.a", "moves": "tok_s"},
                      {"name": "queue_ms.b", "moves": "ttft"}],
    }
    w = spec.workload(bench, "toy.burst")
    assert spec.config(bench, w["config"], tmp_path) == {
        "name": "toy", "model": "toy_model"}
    assert spec.traffic(w["traffic"], b) == {"loop": "open"}
    assert spec.model("toy_model", b).flops({}, 5, 0, 0) == 15.0
    assert spec.metric_reader("queue_ms.a", b)(None, "queue_ms.a") == 7.0
    assert spec.roofline("my_kernel", b).cost({}, {}) == (1.0, 2.0)
    names = [e["name"] for e in spec.cell_metrics(bench, "toy.burst", True)]
    assert names == ["queue_ms.a"]      # queue_ms.b moves a metric not here
    names = [e["name"] for e in spec.cell_metrics(bench, "toy.burst", False)]
    assert names == ["tok_s"]


def test_every_named_piece_of_the_benchmark_exists():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        m = spec.config(bench, w["config"])
        spec.traffic(w["traffic"])
        spec.reference(m["reference"])
        spec.model(m["model"])
        for e in spec.cell_metrics(bench, w["name"], True):
            assert callable(spec.metric_reader(e["name"]))
    for kernel in ("paged_attention", "paged_prefill"):
        spec.roofline(kernel)


def test_config_without_model_fails_at_load(tmp_path):
    (tmp_path / "old.json").write_text(
        json.dumps({"name": "old", "reference": "dense_transformer"}))
    bench = {"configs": [{"name": "old", "file": "old.json"}]}
    with pytest.raises(KeyError, match='"model"'):
        spec.config(bench, "old", tmp_path)
