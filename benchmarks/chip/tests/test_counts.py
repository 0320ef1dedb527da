"""The FLOP and byte counters against hand counts, and their independence
from anything but the live lengths and the model's shapes."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

from chipbench import counts  # noqa: E402

# granite-8b widths, two layers
C = {"hidden_size": 4096, "num_attention_heads": 32, "num_key_value_heads": 8,
     "head_dim": 128, "intermediate_size": 14336, "vocab_size": 49152,
     "num_hidden_layers": 2, "mlp_gated": True}


def test_layer_params_by_hand():
    attn = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096
    mlp = 3 * 4096 * 14336
    assert counts.layer_matmul_params(C) == attn + mlp == 218103808


def test_causal_keys_by_hand():
    # rows at positions 5, 6, 7 read 6 + 7 + 8 keys
    assert counts.causal_keys(5, 3) == 21
    assert counts.causal_keys(0, 1) == 1
    assert counts.causal_keys(0, 2048) == 2048 * 2049 // 2


def test_decode_attention_by_hand():
    # one decode row at position 99 (100 keys) of one layer
    f, b = counts.attn_call(C, [(99, 1)])
    assert f == 4 * 32 * 128 * 100
    # K and V of 100 positions x 8 heads x 128 + q and o of 32 heads, bf16
    assert b == 2 * (2 * 8 * 128 * 100 + 2 * 32 * 128)


def test_counts_follow_live_lengths_only():
    """Two requests cost their own context: the count does not depend on
    anything like a horizon, a page size or a slot count."""
    short = counts.serve_call(C, [], [[10, 20]])
    longer = counts.serve_call(C, [], [[10, 2000]])
    assert longer["attn"][0] > short["attn"][0]
    f1, _ = counts.attn_call(C, [(10, 1)])
    f2, _ = counts.attn_call(C, [(20, 1)])
    assert short["attn"][0] == pytest.approx(2 * (f1 + f2))   # 2 layers


def test_serve_call_by_hand():
    """One prefill chunk of 256 rows from position 0 that ends the prompt,
    and one decode step of one request at position 300."""
    L = 2
    w = counts.serve_call(C, [(0, 256)], [[300]], prefill_sampled=1)
    n_tok = 257
    attn_f = L * 4 * 32 * 128 * (256 * 257 // 2 + 301)
    step_f = 2 * n_tok * L * 218103808 + attn_f + 2 * 2 * 4096 * 49152
    assert w["attn"][0] == attn_f
    assert w["step"][0] == step_f
    weights = 2 * (L * 218103808 + 4096 * 49152)
    attn_b = L * 2 * (2 * 8 * 128 * (256 + 301) + 2 * 32 * 128 * 257)
    kv_w = 2 * 2 * 8 * 128 * L * n_tok
    assert w["step"][1] == weights + attn_b + kv_w


def test_train_flops_by_hand():
    B, S = 4, 2048
    mm = 6 * B * S * (2 * 218103808 + 4096 * 49152)
    att = 12 * 32 * 128 * 2 * B * (S * (S + 1) // 2)
    assert counts.flash_attn_flops(C, B, S) == att
    assert counts.train_step_flops(C, B, S) == mm + att


def test_bound_takes_the_larger():
    peaks = {"bf16_flops": 100.0, "hbm_bw": 10.0}
    assert counts.bound_s(1000.0, 50.0, peaks) == 10.0
    assert counts.bound_s(100.0, 50.0, peaks) == 5.0
