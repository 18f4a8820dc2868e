"""The yardstick's counts against hand counts, and the peaks table."""
import os

import pytest

import _chipbench
import flops
import peaks

QWEN3_CUT = dict(hidden_size=2048, intermediate_size=6144,
                 num_attention_heads=16, num_key_value_heads=8, head_dim=128,
                 num_hidden_layers=4, vocab_size=18992)


def test_qwen3_cut_matmul_params():
    # per layer: q 2048x2048, k and v 2048x1024, o 2048x2048, MLP 3x2048x6144
    per_layer = 2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 6144
    assert per_layer == 50_331_648
    assert flops.llm_matmul_params(QWEN3_CUT) == 4 * per_layer + 2048 * 18992
    assert flops.llm_matmul_params(QWEN3_CUT) == 240_222_208   # 240.2 M


def test_qwen3_train_flops_per_token():
    s = 1024
    attn = 3 * 4 * 2 * 2 * 16 * 128 * (s + 1) / 2
    assert flops.llm_train_flops_per_token(QWEN3_CUT, s) == pytest.approx(
        6 * 240_222_208 + attn, rel=1e-12)


def test_mnist_cnn_forward_macs():
    # conv1 24x24x10x25, conv2 8x8x20x250, fc 320x50, fc 50x10
    assert flops.mnist_cnn_forward_macs(28) == 144_000 + 320_000 + 16_000 + 500
    assert flops.mnist_cnn_forward_macs(28) == 480_500
    assert flops.cnn_train_flops_per_sample(480_500) == 6 * 480_500


def test_peaks_v5e_and_unknown_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9 and p["ici_bits_per_s"] == 1600e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_mfu_llm_reads_the_qwen3_cut_count():
    """``mfu.llm`` on the ``qwen3-1.7b-cut`` configuration file: the dense
    decoder's count of 240,222,208 matmul parameters and causal
    attention, at 1024 positions, over the v5e's bf16 peak."""
    import spec
    c = _chipbench.load(os.path.join(_chipbench.HERE, "configs",
                                     "qwen3-1.7b-cut.json"))
    ctx = {"rates": {"train_tokens_per_s": 31012.0}, "config": c,
           "traffic": {"seq_len": 1024}, "peaks": peaks.peaks("TPU v5 lite")}
    per_token = 6 * 240_222_208 + 3 * 4 * 2 * 2 * 16 * 128 * 1025 / 2
    assert spec.load_reader("mfu.llm")(ctx) == pytest.approx(
        100.0 * per_token * 31012.0 / 197e12, rel=1e-12)
