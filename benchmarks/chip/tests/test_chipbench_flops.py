"""Operation and byte counts against values worked by hand: the dense
family's, from its reference file."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import cells, flops, peaks  # noqa: E402

dense = cells.reference_module("dense_transformer")

# a small model: 2 layers, 4 query heads on 2 key/value heads of width 8
SMALL = {"n_layers": 2, "d_model": 32, "n_heads": 4, "n_kv_heads": 2, "head_dim": 8,
         "d_ff": 64, "vocab_size": 100, "activation": "silu"}


def test_layer_params_at_published_widths():
    ds = {"d_model": 768, "n_heads": 12, "n_kv_heads": 12, "d_ff": 3072, "activation": "silu"}
    # attention 4 x 768^2, gated FFN 3 x 768 x 3072
    assert dense.layer_matmul_params(ds) == 2_359_296 + 7_077_888
    granite = {"d_model": 6144, "n_heads": 48, "n_kv_heads": 1, "head_dim": 128,
               "d_ff": 24576, "activation": "gelu"}
    # q and o 6144 x 6144 each, k and v 6144 x 128 each, ungated FFN 2 x 6144 x 24576
    assert dense.layer_matmul_params(granite) == 2 * 37_748_736 + 2 * 786_432 + 301_989_888


def test_extend_work_by_hand():
    # row 0 writes 3 tokens from position 0 (they attend 1, 2, 3 positions);
    # row 1 is padding; row 2 writes 2 tokens from position 16 (17, 18)
    w = dense.extend_work(SMALL, "float32", [0, 5, 16], [3, 0, 2], [True, False, False])
    ctx = 1 + 2 + 3 + 17 + 18
    assert w.tokens == 5
    assert w.attn_flops == 2 * 4 * 4 * 8 * ctx  # layers x (QK + PV) x heads x width x positions
    # K and V of the attended positions (3 and 18 per row, 2 heads x 8 x 4 B each),
    # plus queries read and outputs written (4 heads x 8 x 4 B per token)
    kv = (3 + 18) * 2 * 8 * 4 * 2
    qo = 2 * 5 * 4 * 8 * 4
    assert w.attn_bytes == 2 * (kv + qo)
    per_token = dense.layer_matmul_params(SMALL)
    assert per_token == 32 * 32 + 2 * 32 * 16 + 32 * 32 + 3 * 32 * 64
    # one row completes its prompt: one row of the output head
    assert w.model_flops == 2 * 2 * per_token * 5 + w.attn_flops + 2 * 32 * 100


def test_decode_work_by_hand():
    w = dense.decode_work(SMALL, "bfloat16", [5, 0])
    assert w.tokens == 2
    assert w.attn_flops == 2 * 4 * 4 * 8 * (6 + 1)
    assert w.attn_bytes == 2 * ((6 + 1) * 2 * 8 * 2 * 2 + 2 * 2 * 4 * 8 * 2)
    per_row = 2 * (2 * dense.layer_matmul_params(SMALL) + 32 * 100)
    assert w.model_flops == 2 * per_row + w.attn_flops


def test_roofline_bound_picks_the_larger():
    w = flops.DispatchWork(tokens=1, model_flops=0, attn_flops=197e12, attn_bytes=819e9 * 2)
    p = peaks.peak_for("TPU v5 lite")
    assert w.attn_least_s(p.matmul_flops_per_s, p.hbm_bytes_per_s) == pytest.approx(2.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peak_for("TPU v9 imaginary")
