"""Operations and bytes per step against numbers worked out by hand."""

import json
from pathlib import Path

import pytest

from bench import counts

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_yi_6b_decode_token_is_11_6_gflop():
    yi = cfg("yi_6b")
    # per layer: q and o 4096x4096 each, k and v 4096x512 each, MLP 3x4096x11008
    layer = 2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008
    assert counts.layer_matmul_params(yi) == layer == 173_015_040
    n = 32 * layer + 4096 * 64000
    assert counts.matmul_params(yi) == n == 5_798_625_280
    assert 2 * n / 1e9 == pytest.approx(11.6, abs=0.01)
    # one lane at context 0 attends to itself: 4 * layers * heads * head_dim
    assert counts.decode_flops(yi, [0]) == 2 * n + 4 * 32 * 32 * 128


def test_yi_6b_bytes():
    yi = cfg("yi_6b")
    assert counts.kv_bytes_per_token(yi) == 64 * 1024  # 2 x 32 layers x 4 heads x 128 x 2 B
    w = (5_798_625_280 + (2 * 32 + 1) * 4096) * 2
    assert counts.weight_bytes(yi) == w
    # 32 lanes at 1000 tokens read their 1000 keys/values and write one
    assert counts.decode_bytes(yi, [1000] * 32) == w + 32 * 1001 * 65536


def test_phi3_kv_is_six_times_yi():
    assert counts.kv_bytes_per_token(cfg("phi3_mini")) == 6 * counts.kv_bytes_per_token(cfg("yi_6b"))
    assert counts.kv_bytes_per_token(cfg("phi3_mini")) == 384 * 1024


def test_prefill_attention_is_causal_sum():
    yi = cfg("yi_6b")
    p = 256
    per_query = sum(counts.attention_flops(yi, q) for q in range(p))
    assert counts.prefill_flops(yi, p) == 2 * counts.matmul_params(yi) * p + per_query


def test_least_seconds_takes_the_binding_roof():
    peaks = {"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9}
    assert counts.least_seconds(200e12, 1, peaks) == pytest.approx(1.0)
    assert counts.least_seconds(1, 1600e9, peaks) == pytest.approx(2.0)
