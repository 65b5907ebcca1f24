"""Finding a cell's files by name.

`BENCHMARK.json` names configurations, traffic mixes and metrics; each
lives in a file of its own under the benchmark's directory, found here by
that name alone:

    configs/<config>.json       (the path the configuration entry gives)
    models/<model>.py           (named by the configuration file's "model")
    traffic/<mix>.json
    metrics/<metric>.py         or metrics/<base>.py, base = the metric's
                                name up to its first "." (one reader for
                                step_ms.latency and step_ms.throughput)
    roofline/<kernel>.py
    references/<reference>.py   (named by the configuration file)
    peaks.json                  keyed by JAX's device_kind
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class UnknownDevice(KeyError):
    pass


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            with open(root / c["file"]) as f:
                m = json.load(f)
            if "model" not in m:
                raise KeyError(f"configuration file {c['file']} has no "
                               "\"model\" key: it names the module under "
                               "models/ that builds the model")
            return m
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, base: Path = BENCH) -> dict:
    with open(base / "traffic" / f"{name}.json") as f:
        return json.load(f)


def _module(path: Path, tag: str):
    spec = importlib.util.spec_from_file_location(f"bench_{tag}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, base: Path = BENCH):
    """The `read(run, name)` function of metric `name`."""
    path = base / "metrics" / f"{name}.py"
    if not path.exists():
        path = base / "metrics" / f"{name.split('.')[0]}.py"
    return _module(path, "metric_" + path.stem.replace(".", "_")).read


def roofline(kernel: str, base: Path = BENCH):
    return _module(base / "roofline" / f"{kernel}.py", "roofline_" + kernel)


def model(name: str, base: Path = BENCH):
    """The model module `name`: `model_config(m)`, `make_params(m, seed)`,
    `pool_blocks(m, block_size)` and `flops(m, tokens, keys, logits_rows)`;
    an optional `SCOPES` adds the device scopes it names to those the
    trace reduction attributes operations to."""
    return _module(base / "models" / f"{name}.py", "model_" + name)


def reference(name: str, base: Path = BENCH):
    return _module(base / "references" / f"{name}.py", "reference_" + name)


def peaks(device_kind: str, base: Path = BENCH) -> dict:
    with open(base / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in "
                            f"{base / 'peaks.json'}")
    return table[device_kind]


def reports(bench: dict, cell: str, metric: dict) -> bool:
    """Whether `cell` reports `metric`: the cells its "workloads" list,
    else every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        return any(e["name"] == metric["moves"] and reports(bench, cell, e)
                   for e in bench["end_to_end"])
    return True


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    """The metric entries this cell reports in a run with(out) trace."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if reports(bench, cell, m)]
