"""FLOP and byte counts against hand-worked values, and the peaks table."""

import json
import os

import pytest

from benchmarks import common, counts, peaks


def _model(name):
    with open(os.path.join(common.HERE, "configs", name + ".json")) as f:
        return json.load(f)["model_config"]


def test_bert_large_seq512_flops_by_hand():
    cfg = _model("bert_large")
    b, s, n_pred = 32, 512, 80
    tokens = b * s
    # per layer and token: QKV 2*1024*3072, out 2*1024*1024, FFN 2*2*1024*4096
    layer = 2 * 1024 * 3072 + 2 * 1024 * 1024 + 4 * 1024 * 4096
    assert layer == 25_165_824
    attn = 4 * b * 16 * s * s * 64            # QK^T and PV, all heads
    head = b * n_pred * (2 * 1024 * 1024 + 2 * 1024 * 30528)
    pooler = b * 2 * 1024 * 1024
    by_hand = 3 * (24 * (tokens * layer + attn) + head + pooler)
    assert counts.bert_train_flops_per_step(cfg, b, s, n_pred) == by_hand
    assert by_hand == pytest.approx(32.657e12, rel=1e-3)


def test_bert_large_seq128_flops_by_hand():
    cfg = _model("bert_large")
    got = counts.bert_train_flops_per_step(cfg, 112, 128, 20)
    tokens = 112 * 128
    by_hand = 3 * (24 * (tokens * 25_165_824 + 4 * 112 * 16 * 128 * 128 * 64)
                   + 112 * 20 * (2 * 1024 * 1024 + 2 * 1024 * 30528)
                   + 112 * 2 * 1024 * 1024)
    assert got == by_hand


def test_gpt2_xl_flops_and_parameters_by_hand():
    cfg = _model("gpt2_xl")
    h = 1600
    per_layer = 12 * h * h + 13 * h          # 4 matrices + biases + 2 LNs
    params = 48 * per_layer + 50304 * h + 1024 * h + 2 * h
    assert counts.gpt2_param_count(cfg) == params
    assert params == pytest.approx(1.5577e9, rel=1e-3)
    b, s = 16, 1024
    layer = 2 * 12 * h * h
    attn = 4 * b * 25 * s * s * 64 // 2      # causal: half
    by_hand = 3 * (48 * (b * s * layer + attn) + b * s * 2 * h * 50304)
    assert counts.gpt2_train_flops_per_step(cfg, b, s) == by_hand


def test_attention_kernel_counts_by_hand():
    # BERT-large seq 512, batch 32: 7 matmuls of 2*b*h*s*s*d a layer
    one = 2 * 32 * 16 * 512 * 512 * 64
    assert counts.attention_kernel_flops_per_layer(
        32, 16, 512, 64, causal=False) == 7 * one
    assert counts.attention_kernel_flops_per_layer(
        32, 16, 512, 64, causal=True) == 7 * (one // 2)
    assert counts.attention_kernel_bytes_per_layer(
        32, 16, 512, 64) == 12 * 32 * 16 * 512 * 64 * 2


def test_gpt2_large_decode_bytes_by_hand():
    cfg = _model("gpt2_large")
    h = 1280
    weights = 36 * (12 * h * h + 13 * h) + 50304 * h + 2 * h
    live = 16 * 300
    assert counts.gpt2_decode_bytes_per_step(cfg, live) == (
        2 * weights + 2 * 36 * live * h * 2)
    assert 2 * weights == pytest.approx(1.545e9, rel=1e-2)


def test_peaks_known_kind_and_unknown_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite") == {
        "bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
        "ici_link_bytes_per_s": 45e9, "hbm_bytes": 16e9}
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v7x")
    with pytest.raises(KeyError):
        peaks.mfu_percent(1.0, 1.0, "cpu")


def test_mfu_and_roofline_arithmetic():
    # 55,829 tokens/s of BERT-large seq 512 (ledger, PR 22) by this count
    cfg = _model("bert_large")
    per_token = counts.bert_train_flops_per_step(cfg, 32, 512, 80) / (32 * 512)
    assert peaks.mfu_percent(55829.0, per_token, "TPU v5 lite") == \
        pytest.approx(56.49, abs=0.05)
    share, bound = peaks.roofline_percent(197e12, 1.0, 2.0, "TPU v5 lite")
    assert (share, bound) == (pytest.approx(50.0), "flops")
    share, bound = peaks.roofline_percent(1.0, 819e9, 4.0, "TPU v5 lite")
    assert (share, bound) == (pytest.approx(25.0), "bytes")
