"""Hand counts for qwen3-1.7b against ``counts.py``."""
import os

import pytest

from benchmarks.chip import counts, harness
from benchmarks.chip.peaks import peaks

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
Q = harness.load_json(os.path.join(ROOT, "configs", "qwen3-1.7b-serve.json"))


def test_parameters():
    c = counts.param_counts(Q)
    # per layer: q 2048x2048, k and v 2048x1024, o 2048x2048, MLP 3 x
    # 2048x6144, two RMSNorms of 2048 and the q/k norms of 128
    layer = 3 * 2048 * 2048 + 3 * 2048 * 6144 + 2 * 2048 + 2 * 128
    assert c["layer"] == layer == 50_336_000
    assert c["total"] == 28 * layer + 2048 + 151_936 * 2048 == 1_720_574_976
    assert round(c["total"] / 1e9, 3) == 1.721
    assert counts.active_params(Q) == c["total"]


def test_kv_bytes_per_token():
    assert counts.kv_bytes_per_token(Q) == 28 * 2 * 8 * 128 * 2 == 114_688


def test_flops():
    per_tok = 2 * (1_409_286_144 + 151_936 * 2048)
    assert counts.matmul_flops_per_token(Q) == per_tok
    assert counts.attn_flops(Q, 100) == 4 * 28 * 16 * 128 * 100
    assert counts.train_flops_per_token(Q, 4096) == pytest.approx(
        3 * (per_tok + 4 * 28 * 16 * 128 * 4097 / 2))


def test_decode_tick_least_is_memory_bound():
    p = peaks("TPU v5 lite")
    t, bound = counts.decode_tick_least(Q, p, [500] * 16)
    bytes_ = 1_720_574_976 * 2 + 114_688 * (16 * 500 + 16)
    assert bound == "memory"
    assert t == pytest.approx(bytes_ / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks("cpu")


def test_moe_active_params():
    moe = dict(Q, num_experts=8, num_experts_per_tok=2,
               moe_intermediate_size=1024)
    routed = 28 * 8 * 3 * 2048 * 1024
    assert counts.active_params(moe) == pytest.approx(
        counts.param_counts(moe)["total"] - routed * 0.75)


def test_serve_flops_sums_tokens():
    want = sum(counts.serve_token_flops(Q, p + 1) for p in range(300, 556))
    assert counts.serve_flops(Q, 300, 256) == pytest.approx(want)
