"""Shared set-up of the benchmark's CPU tests: the program and the
benchmark's own modules on the path, no persistent compile cache, and a
copy of the benchmark at a tiny size to drive whole runs on the CPU."""
import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

# An open-loop cell added to the tiny copy by data alone, as a later cell
# would be: olmo-1b (hf allenai/OLMo-1B, uncut) under multi-turn chat.
# It exercises the open-loop generator, the prefix cache and the
# tied-embedding, gain-free LayerNorm block, which no cell of the
# benchmark runs on the chip yet.
OLMO_1B = dict(
    name="olmo-1b", source="https://huggingface.co/allenai/OLMo-1B",
    model="dense_transformer", reference="dense_transformer", num_layers=16,
    d_model=2048, n_heads=16, n_kv_heads=16, head_dim=128, d_ff=8192,
    vocab=50304, context_length=2048, rope_theta=10000.0, ffn="swiglu",
    norm="nonparam_ln", qk_norm=False, tie_embeddings=True,
    serving=dict(weights="w4a8", kv_int8=True, max_batch=32,
                 pool_bytes=3 << 30),
    correct=dict(metric="max_logit_gap", limit=2.0, sample_tokens=400,
                 min_compared=200, max_requests=16))
CHAT_SESSIONS = dict(
    loop="open", rate_per_s=0.4, lead_in_s=8.0, drain_cap_s=60.0,
    shape_seed=0,
    tenants=dict(count=16, zipf=1.1, system_min=256, system_max=1024,
                 prewarm=True),
    turns=dict(min=1, max=4, think_median_s=5.0, think_sigma=0.5),
    user=dict(median=64, sigma=1.0, min=16, max=512),
    output=dict(median=96, sigma=0.8, min=16, max=512),
    greedy_share=0.5, temperature=0.7)
SHORT_UNSHARED = dict(
    loop="open", rate_per_s=0.8, lead_in_s=5.0, drain_cap_s=30.0,
    shape_seed=0, user=dict(median=48, sigma=0.8, min=8, max=256),
    output=dict(median=32, sigma=0.6, min=8, max=128),
    greedy_share=0.5, temperature=0.7)
CHAT_CELL = "olmo1b.chat-sessions"
CHAT_ENTRIES = {
    "configs": [{"name": "olmo-1b", "file": "bench/configs/olmo-1b.json"}],
    "workloads": [{"name": CHAT_CELL, "config": "olmo-1b",
                   "traffic": "chat-sessions", "chips": 1}],
    "end_to_end": [{"name": "ttft_p90_ms", "unit": "ms",
                    "workloads": [CHAT_CELL]}],
    "per_layer": [
        {"name": n, "unit": u, "moves": mv, "workloads": [CHAT_CELL]}
        for n, u, mv in (("prefix_hit_share", "%", "ttft_p90_ms"),
                         ("step_ms.latency", "ms", "itl_p95_ms"),
                         ("mfu.latency", "%", "itl_p95_ms"),
                         ("paged_prefill_roofline", "%", "ttft_p90_ms"))],
}

TINY = dict(num_layers=2, d_model=128, n_heads=4, head_dim=32, d_ff=256,
            vocab=512, context_length=256)
TINY_LIMIT = 0.3     # max_logit_gap at this size: served ~0.04, w2 ~1


@pytest.fixture(autouse=True)
def _no_compile_cache():
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    yield


def shrink(root: Path) -> dict:
    """Add the open-loop cell, then cut every configuration and mix under
    `root/bench` to a size the CPU runs in seconds; returns the
    BENCHMARK.json it should use."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for key, entries in CHAT_ENTRIES.items():
        bench[key] += entries
    (root / "bench" / "configs" / "olmo-1b.json").write_text(
        json.dumps(OLMO_1B))
    (root / "bench" / "traffic" / "chat-sessions.json").write_text(
        json.dumps(CHAT_SESSIONS))
    for c in bench["configs"]:
        path = root / c["file"]
        m = json.loads(path.read_text())
        m.update(TINY, n_kv_heads=m["n_kv_heads"] * 4 // m["n_heads"])
        m["serving"].update(max_batch=4, pool_bytes=1 << 22)
        m["correct"].update(limit=TINY_LIMIT, sample_tokens=60,
                            min_compared=10)
        path.write_text(json.dumps(m))
    for path in (root / "bench" / "traffic").glob("*.json"):
        t = json.loads(path.read_text())
        t["user"].update(max=min(t["user"]["max"], 64),
                         median=min(t["user"]["median"], 24))
        t["output"].update(max=min(t["output"]["max"], 32),
                           min=min(t["output"]["min"], 8),
                           median=min(t["output"]["median"], 16))
        if "tenants" in t:
            t["tenants"].update(count=3, system_min=32, system_max=48)
        if t["loop"] == "open":
            t.update(lead_in_s=1.0, drain_cap_s=30.0, rate_per_s=1.0)
        else:
            t.update(clients=4, per_client=6)
        path.write_text(json.dumps(t))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench


@pytest.fixture
def tiny(tmp_path):
    """(root, bench) of a tiny copy of the benchmark."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return tmp_path, shrink(tmp_path)
