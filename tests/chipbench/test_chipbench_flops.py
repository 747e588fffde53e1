"""FLOP count against hand counts, and the table of peaks."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import flops  # noqa: E402
from chipbench.bench import Spec  # noqa: E402


def config(name):
    return json.loads((ROOT / "chipbench" / "configs" / name).read_text())


def test_qwen2_flops_per_token_hand_count():
    # Per layer: q 896x896, k and v 896x128 each, o 896x896, and
    # gate, up, down 896x4864 each; 24 layers; head 151936x896.
    per_layer = 896 * 896 + 2 * 896 * 128 + 896 * 896 + 3 * 896 * 4864
    assert per_layer == 14_909_440
    params = 24 * per_layer + 151936 * 896
    assert params == 493_961_216
    # Causal QK^T and PV: 6 * layers * heads * head_dim * (S + 1).
    attention = 6 * 24 * 14 * 64 * 2049
    want = 6 * params + attention
    cfg = config("qwen2-0.5b.json")
    assert flops.matmul_params(cfg) == params
    assert flops.train_flops_per_token(cfg, 2048) == want
    assert round(want / 1e9, 3) == 3.228


def test_qwen15_flops_per_token_hand_count():
    per_layer = 4 * 1024 * 1024 + 3 * 1024 * 2816
    params = 24 * per_layer + 151936 * 1024
    assert params == 463_863_808
    want = 6 * params + 6 * 24 * 16 * 64 * 2049
    cfg = config("qwen1.5-0.5b.json")
    assert flops.train_flops_per_token(cfg, 2048) == want
    assert round(want / 1e9, 3) == 3.085


def test_peaks_are_keyed_by_device_kind():
    peaks = Spec(ROOT).peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="not in chipbench/peaks.json"):
        Spec(ROOT).peaks("TPU v9 imaginary")
