"""Plain reference of a Qwen3 decoder: the reference module of the
``qwen3-1.7b-cut`` configuration (``"reference": "ref_qwen3"``).

Straightforward ``jax.numpy`` in float32 with every matmul at
``Precision.HIGHEST``: no kernels, no remat, no chunking, no replica
axes. It imports nothing of the program under test and builds its own
weights from the seed by the initializer the configuration names
(truncated normal at 1/sqrt(fan_in) for projections, normal(0.02) for
the embedding and truncated normal(0.02) for an untied output head;
norm weights one).

The decoder follows the published Qwen3 block: pre-RMSNorm, grouped
query attention with RMSNorm on each query and key head, rotary
positions, causal softmax, SwiGLU MLP, final RMSNorm, the output head
(the transposed input embedding where ``tie_word_embeddings`` is set,
as in the published configuration), and next-token cross-entropy
averaged over tokens. One departure, to match
the program: the rotary embedding rotates adjacent pairs of a head's
dimensions (2i, 2i + 1); the published one rotates (i, i + hd/2). The
two are the same model up to a fixed permutation of each head's query
and key columns.

``dtype`` selects the precision the whole computation is held in:
float32 for the reference, bfloat16 for the check's control. Like every
LLM reference module it gives ``init``, ``loss`` and
``train_flops_per_token``; the HFL round around ``loss`` is
``ref_hfl``'s.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import flops

HIGHEST = jax.lax.Precision.HIGHEST


def _tn(key, shape, std):
    return jax.random.truncated_normal(key, -3, 3, shape, jnp.float32) * std


def init(key, c: dict) -> dict:
    """The configuration's weights from ``key``, float32."""
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    keys = jax.random.split(key, 8)

    def block(k):
        k1, k2 = jax.random.split(k)
        ka = jax.random.split(k1, 4)
        km = jax.random.split(k2, 3)
        return {
            "ln1": jnp.ones((d,), jnp.float32),
            "attn": {
                "wq": _tn(ka[0], (d, h * hd), 1.0 / math.sqrt(d)),
                "wk": _tn(ka[1], (d, kv * hd), 1.0 / math.sqrt(d)),
                "wv": _tn(ka[2], (d, kv * hd), 1.0 / math.sqrt(d)),
                "wo": _tn(ka[3], (h * hd, d), 1.0 / math.sqrt(h * hd)),
                "q_norm": jnp.ones((hd,), jnp.float32),
                "k_norm": jnp.ones((hd,), jnp.float32),
            },
            "ln2": jnp.ones((d,), jnp.float32),
            "mlp": {
                "w_gate": _tn(km[0], (d, f), 1.0 / math.sqrt(d)),
                "w_up": _tn(km[1], (d, f), 1.0 / math.sqrt(d)),
                "w_down": _tn(km[2], (f, d), 1.0 / math.sqrt(f)),
            },
        }

    layers = jax.vmap(block)(jax.random.split(keys[2],
                                              c["num_hidden_layers"]))
    params = {
        "embed": jax.random.normal(keys[0], (v, d), jnp.float32) * 0.02,
        "final_norm": jnp.ones((d,), jnp.float32),
        "layers": layers,
    }
    if not c["tie_word_embeddings"]:
        params["unembed"] = _tn(keys[1], (d, v), 0.02)
    return params


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x: (B, S, H, hd); rotates adjacent pairs by position."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs   # (S, hd/2)
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


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
        y = _rms(x, lp["ln1"], eps)
        q = mm(y, a["wq"]).reshape(b, s, h, hd)
        k = mm(y, a["wk"]).reshape(b, s, kv, hd)
        v = mm(y, a["wv"]).reshape(b, s, kv, hd)
        q = _rope(_rms(q, a["q_norm"], eps), theta)
        k = _rope(_rms(k, a["k_norm"], eps), theta)
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST)
        sc = sc.astype(jnp.float32) / math.sqrt(hd)
        sc = jnp.where(causal, sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1).astype(dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=HIGHEST)
        x = x + mm(o.reshape(b, s, h * hd), a["wo"])
        y = _rms(x, lp["ln2"], eps)
        m = lp["mlp"]
        x = x + mm(jax.nn.silu(mm(y, m["w_gate"])) * mm(y, m["w_up"]),
                   m["w_down"])
        return x, None

    x, _ = jax.lax.scan(block, x, p["layers"])
    x = _rms(x, p["final_norm"], eps)
    head = p["embed"].T if c["tie_word_embeddings"] else p["unembed"]
    logits = mm(x, head).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - gold)


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward plus backward FLOPs one trained token requires (the
    dense decoder's count, ``flops.llm_train_flops_per_token``)."""
    return flops.llm_train_flops_per_token(c, seq_len)
