"""FLOP and byte counts of one engine step against hand sums, and the
peaks table."""
import json

import pytest

import chipbench_small as S
from chipbench import counts


def _sizes(name):
    return json.loads((S.ROOT / "chipbench" / "configs" / f"{name}.json").read_text())


def test_qwen2_step_counts_match_hand_sums():
    s = _sizes("qwen2-0.5b")
    attn = 896 * 14 * 64 + 2 * 896 * 2 * 64 + 14 * 64 * 896      # q, k, v, o
    mlp = 3 * 896 * 4864                                           # gate, up, down
    head = 896 * 151936                                            # tied embedding
    per_token = 2 * (24 * (attn + mlp) + head)
    attention = 24 * 4 * 14 * 64 * (500 + 1)                       # QK^T, PV over 501 keys
    assert counts.step_flops(s, 128, 500) == 128 * (per_token + attention)
    weights = 24 * (attn + mlp + 2 * 896 + (14 + 2 * 2) * 64) + head + 896
    assert weights == 494032768                 # the served model's parameter count
    kv = 24 * 128 * 2 * 2 * 64 * (501 + 1)      # layers x slots x (K, V) x heads x dh
    assert counts.step_bytes(s, 128, 500) == 2 * (weights + kv)


def test_olmoe_step_counts_match_hand_sums():
    s = _sizes("olmoe-1b-7b")
    attn = 4 * 2048 * 2048
    expert = 3 * 2048 * 1024
    router = 2048 * 64
    head = 2048 * 50304
    per_token = 2 * (8 * (attn + router + 8 * expert) + head)
    attention = 8 * 4 * 16 * 128 * 101
    assert counts.step_flops(s, 64, 100) == 64 * (per_token + attention)
    touched = 64 * (1 - (1 - 8 / 64) ** 64)     # expected distinct experts of 64 tokens
    assert 63.98 < touched < 63.99
    weights = 8 * (attn + 2 * 2048 + router + touched * expert) + head + 2048
    weights += 64 * 2048                        # untied: the embedding rows looked up
    kv = 8 * 64 * 2 * 16 * 128 * (101 + 1)
    assert counts.step_bytes(s, 64, 100) == pytest.approx(2 * (weights + kv), rel=1e-12)
    # one token touches exactly its 8 experts
    assert counts.experts_touched(s, 1) == pytest.approx(8.0)


def test_peaks_known_and_unknown_device():
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("cpu")
