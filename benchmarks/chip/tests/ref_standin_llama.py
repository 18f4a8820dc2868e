"""Stand-in reference module of a second LLM architecture, for the CPU
tests: a Llama-style decoder, which is Qwen3's block without the RMSNorm
on each query and key head (``ref_qwen3``'s initializer, rotary
embedding and norms otherwise). It gives what every LLM reference
module gives: ``init``, ``loss`` and ``train_flops_per_token``."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import flops
import ref_qwen3

HIGHEST = jax.lax.Precision.HIGHEST


def init(key, c: dict) -> dict:
    """``ref_qwen3``'s weights from ``key`` without the per-head norms."""
    params = ref_qwen3.init(key, c)
    for name in ("q_norm", "k_norm"):
        del params["layers"]["attn"][name]
    return params


def loss(params, tokens, labels, c: dict, dtype=jnp.float32):
    """Mean next-token cross-entropy of one batch (B, S)."""
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    b, s = tokens.shape
    x = p["embed"][tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def mm(a, w):
        return jnp.matmul(a, w, precision=HIGHEST)

    def block(x, lp):
        a = lp["attn"]
        y = ref_qwen3._rms(x, lp["ln1"], eps)
        q = ref_qwen3._rope(mm(y, a["wq"]).reshape(b, s, h, hd), theta)
        k = ref_qwen3._rope(mm(y, a["wk"]).reshape(b, s, kv, hd), theta)
        v = mm(y, a["wv"]).reshape(b, s, kv, hd)
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST)
        sc = sc.astype(jnp.float32) / math.sqrt(hd)
        sc = jnp.where(causal, sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1).astype(dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=HIGHEST)
        x = x + mm(o.reshape(b, s, h * hd), a["wo"])
        y = ref_qwen3._rms(x, lp["ln2"], eps)
        m = lp["mlp"]
        x = x + mm(jax.nn.silu(mm(y, m["w_gate"])) * mm(y, m["w_up"]),
                   m["w_down"])
        return x, None

    x, _ = jax.lax.scan(block, x, p["layers"])
    x = ref_qwen3._rms(x, p["final_norm"], eps)
    head = p["embed"].T if c["tie_word_embeddings"] else p["unembed"]
    logits = mm(x, head).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - gold)


def train_flops_per_token(c: dict, seq_len: int) -> float:
    return flops.llm_train_flops_per_token(c, seq_len)
