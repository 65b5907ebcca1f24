"""Operation and byte counts against hand counts at one shape."""
import json
from pathlib import Path

import pytest

from benchlib import spec

from conftest import OLMO_1B

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def olmo():
    return dict(OLMO_1B)


def test_paged_attention_hand_count():
    m = olmo()          # 16 heads of 128, MHA, int8 pool
    f, b = spec.roofline("paged_attention").cost(m, {"keys": [100, 28]})
    # 4 flops per (head, dim, key): 4 * 16 * 128 * 128 keys
    assert f == 4 * 16 * 128 * 128
    # per key slot: 16 KV heads x (128 K + 128 V codes + 2 fp32 scales)
    # = 16 * 264 = 4224 bytes; 128 slots; plus q and out, bf16, 2 rows
    assert b == 128 * 4224 + 2 * 2 * 16 * 128 * 2


def test_paged_prefill_hand_count():
    m = olmo()
    f, b = spec.roofline("paged_prefill").cost(m, {"start": 64, "len": 32})
    # query i sees 64 + i + 1 keys: 32 * 64 + 32 * 33 / 2 = 2576 keys
    assert f == 4 * 16 * 128 * 2576
    slot = 16 * (2 * 128 + 8)
    assert b == 96 * slot + 32 * 16 * 2 * 128 * 2 + 32 * 2 * 16 * 128 * 2


def test_gqa_counts_kv_heads_once():
    m = json.loads((CONFIGS / "stablelm-12b-pp4.json").read_text())
    f, b = spec.roofline("paged_attention").cost(m, {"keys": [10]})
    assert f == 4 * 32 * 160 * 10
    assert b == 10 * 8 * (2 * 160 + 8) + 2 * 32 * 160 * 2


def test_model_flops_hand_count():
    model = spec.model("dense_transformer")
    m = olmo()
    p = 16 * (4 * 2048 * 2048 + 3 * 2048 * 8192)
    assert model.layer_params(m) == p
    assert model.flops(m, 3, 50, 2) == pytest.approx(
        2 * p * 3 + 4 * 16 * 16 * 128 * 50 + 2 * 2048 * 50304 * 2)
