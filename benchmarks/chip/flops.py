"""Operations and bytes the algorithms need, computed from shapes.

These are the yardstick's counts, kept with the benchmark so that a change
to the program cannot change them. Recomputed work (remat) and work on
masked-away epochs is never counted: only what the result requires.
"""
from __future__ import annotations


def llm_matmul_params(c: dict) -> int:
    """Parameters that enter a matmul once per token in a decoder step:
    the attention projections, the SwiGLU MLP and the output head. The
    input embedding is a gather and is not counted."""
    d, f = c["hidden_size"], c["intermediate_size"]
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return c["num_hidden_layers"] * per_layer + d * c["vocab_size"]


def llm_train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward plus backward FLOPs per trained token: 6 per matmul
    parameter, plus causal attention. A query at position i attends to
    i + 1 keys, (S + 1) / 2 on average; QK^T and PV take 2 FLOPs per
    key, head dimension and head each, forward, and the backward pass
    twice that."""
    attn_fwd = (2 * 2 * c["num_attention_heads"] * c["head_dim"]
                * (seq_len + 1) / 2)
    return 6.0 * llm_matmul_params(c) + 3.0 * attn_fwd * c["num_hidden_layers"]


def mnist_cnn_forward_macs(hw: int = 28) -> int:
    """Multiply-adds of one forward pass of the paper's MNIST CNN:
    conv(1->10, 5x5), pool 2, conv(10->20, 5x5), pool 2, fc(320->50),
    fc(50->10); valid convolutions, stride 1."""
    o1 = hw - 4
    p1 = o1 // 2
    o2 = p1 - 4
    p2 = o2 // 2
    conv1 = o1 * o1 * 10 * 5 * 5 * 1
    conv2 = o2 * o2 * 20 * 5 * 5 * 10
    flat = p2 * p2 * 20
    return conv1 + conv2 + flat * 50 + 50 * 10


def cnn_train_flops_per_sample(forward_macs: int) -> float:
    """2 FLOPs per multiply-add, and training is three forward passes'
    worth (forward, gradient of the activations, gradient of the
    weights)."""
    return 3.0 * 2.0 * forward_macs

