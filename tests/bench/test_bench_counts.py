"""Operation and byte counts against a hand count at a tiny size, the
peaks table, and the device checks."""

import json
import os

import pytest

from conftest import REPO

# d=8, f=16, 2 layers, 2 query heads and 1 KV head of 4, vocab 16, fp32
C = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
     "num_attention_heads": 2, "num_key_value_heads": 1, "padded_vocab": 16,
     "torch_dtype": "float32"}


def test_parameters_and_bytes_by_hand():
    from bench import counts
    # q 8x8, k and v 8x4 each, o 8x8, gate/up/down 3 x 8x16
    assert counts.layer_params(C) == 64 + 32 + 32 + 64 + 384
    assert counts.head_params(C) == 128
    # two layers with two norm scales each, embedding, final norm
    assert counts.weight_bytes(C) == 4 * (2 * (576 + 16) + 128 + 8)
    # K and V of one head of 4, two layers, 4 bytes
    assert counts.kv_bytes_per_token(C) == 2 * 2 * 4 * 4


def test_prefill_and_decode_costs_by_hand():
    from bench import counts
    per_tok = 2 * 2 * 576                       # 2 flops x layers x params
    attn = lambda keys: 2 * 4 * 2 * 4 * keys   # layers x 4 x heads x hd
    # one row: tokens 3 and 4 computed, 3 served from the cache
    pre = counts.prefill_cost(C, [(3, 5)], calls=2)
    assert pre["flops"] == 2 * per_tok + attn(4) + attn(5) + 2 * 128
    assert pre["bytes"] == 2 * counts.weight_bytes(C) + 5 * 64
    # prompt of 5, 3 tokens served: decode makes tokens 1 and 2, over 6
    # and 7 keys, with logits
    dec = counts.decode_cost(C, [(5, 3)], steps=2)
    assert dec["flops"] == 2 * (per_tok + 2 * 128) + attn(6) + attn(7)
    assert dec["bytes"] == 2 * counts.weight_bytes(C) + (6 + 7) * 64


def test_roofline_names_its_bound():
    from bench import counts
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.roofline_seconds({"flops": 1000, "bytes": 10},
                                   peaks) == (10.0, "flops")
    assert counts.roofline_seconds({"flops": 10, "bytes": 1000},
                                   peaks) == (100.0, "bytes")


def test_peaks_table_has_v5e_and_refuses_other_devices():
    from bench import harness
    with open(os.path.join(REPO, "bench", "peaks.json")) as f:
        table = json.load(f)
    assert "TPU v5e" in table["source"]
    v5e = harness.peaks_for(REPO, "TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit, match="not in bench/peaks.json"):
        harness.peaks_for(REPO, "TPU v9 imaginary")


def test_a_cpu_is_refused():
    from bench import harness
    with pytest.raises(SystemExit, match="no TPU"):
        harness.require_device(1)
