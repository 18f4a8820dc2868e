"""Kernel micro-benchmarks: pure-jnp oracle vs Pallas kernel wall time
on CPU (interpret mode — the portable reference; the container cannot
time Mosaic), plus the analytic HBM traffic ratio each kernel achieves
vs the naive formulation (the TPU-relevant number).

Rows are persisted to ``BENCH_kernels.json`` by ``benchmarks.run`` (the
``ARTIFACT`` hook) so the perf trajectory of the hot path is recorded
per commit."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import flatbank
from repro.kernels import ops, ref

ARTIFACT = "BENCH_kernels.json"


def _time(fn, *args, iters=5):
    """Median of ``iters`` individually-synced calls (first call compiles
    and is discarded) — the median keeps the bench-regression gate
    (scripts/bench_gate.py) stable against scheduler noise on shared CI
    runners."""
    fn(*args)  # compile
    samples = []
    for _ in range(iters):
        t0 = time.time()
        out = fn(*args)
        jax.tree.leaves(out)[0].block_until_ready()
        samples.append(time.time() - t0)
    samples.sort()
    return samples[len(samples) // 2] * 1e6


def run(quick: bool = True):
    rows = []
    rng = np.random.default_rng(0)
    # flash attention: naive materializes S*S scores; flash keeps
    # (BQ x BK) in VMEM -> HBM traffic ratio = S/BK per q block
    b, h, s, d = 1, 4, 1024, 64
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    us = _time(jax.jit(lambda *a: ref.flash_attention_ref(*a)), q, k, v)
    naive_hbm = b * h * s * s * 4          # f32 score matrix
    flash_hbm = b * h * s * d * 2 * 3      # q,k,v streamed once (bf16)
    rows.append({"setting": "flash_attn_1k",
                 "oracle_us_per_call": round(us, 1),
                 "hbm_bytes_naive": naive_hbm,
                 "hbm_bytes_kernel": flash_hbm,
                 "traffic_ratio": round(naive_hbm / flash_hbm, 1)})
    # wkv6: sequential scan round-trips state every step; chunked kernel
    # keeps it in VMEM for `chunk` steps
    b, s, nh, hd = 2, 512, 4, 64
    r = jnp.asarray(rng.normal(size=(b, s, nh, hd)), jnp.float32)
    kk = jnp.asarray(rng.normal(size=(b, s, nh, hd)), jnp.float32)
    vv = jnp.asarray(rng.normal(size=(b, s, nh, hd)), jnp.float32)
    ww = jnp.asarray(rng.uniform(0.5, 0.999, size=(b, s, nh, hd)),
                     jnp.float32)
    u = jnp.asarray(rng.normal(size=(nh, hd)), jnp.float32)
    us = _time(jax.jit(lambda *a: ref.wkv6_ref(*a)[0]), r, kk, vv, ww, u)
    state_bytes = b * nh * hd * hd * 4
    chunk = 64
    rows.append({"setting": "wkv6_512",
                 "oracle_us_per_call": round(us, 1),
                 "hbm_bytes_scan": state_bytes * 2 * s,
                 "hbm_bytes_kernel": state_bytes * 2 * (s // chunk),
                 "traffic_ratio": float(chunk)})
    # ------------------------------------------------------------------
    # hier_agg (legacy single-segment): oracle vs kernel path
    nrep, p1 = 8, 500_000
    bank = jnp.asarray(rng.normal(size=(nrep, p1)), jnp.float32)
    w = jnp.ones((nrep,), jnp.float32)
    us = _time(jax.jit(ref.hier_agg_ref), bank, w)
    us_k = _time(lambda b_, w_: ops.hier_agg(b_, w_), bank, w)
    rows.append({"setting": "hier_agg_8x500k",
                 "oracle_us_per_call": round(us, 1),
                 "kernel_us_per_call": round(us_k, 1),
                 "hbm_bytes_naive": int(bank.size * 4 * 2),
                 "hbm_bytes_kernel": int(bank.size * 4 + bank.size // 8 * 4),
                 "traffic_ratio": 2.0})
    # ------------------------------------------------------------------
    # segment_agg (flat-bank hot path): 64 devices x 8 edges x 500k
    # params. Naive per-leaf tree path round-trips HBM 3x: weight-scale
    # f32 temp (write+read N*P), segment scatter-add (write E*P, read
    # E*P), normalize (write E*P). Fused kernel: read N*P once, write
    # E*P once, normalization in-kernel.
    n_dev, n_edge, p2 = 64, 8, 500_000
    mat = jnp.asarray(rng.normal(size=(n_dev, p2)), jnp.float32)
    wd = jnp.asarray(rng.uniform(0.5, 2.0, size=(n_dev,)), jnp.float32)
    seg = jnp.asarray(rng.integers(0, n_edge, size=(n_dev,)), jnp.int32)
    us = _time(jax.jit(lambda *a: ref.segment_agg_ref(*a, n_edge)),
               mat, wd, seg)
    us_k = _time(lambda *a: ops.segment_agg(*a, n_edge), mat, wd, seg)
    naive_hbm = 4 * (3 * n_dev * p2 + 3 * n_edge * p2)
    kern_hbm = 4 * (n_dev * p2 + n_edge * p2)
    rows.append({"setting": "segment_agg_64x8x500k",
                 "oracle_us_per_call": round(us, 1),
                 "kernel_us_per_call": round(us_k, 1),
                 "hbm_bytes_naive": naive_hbm,
                 "hbm_bytes_kernel": kern_hbm,
                 "traffic_ratio": round(naive_hbm / kern_hbm, 2)})
    # ------------------------------------------------------------------
    # sharded segment_agg (shard_map + psum path) on a 1-shard mesh of
    # the local device, driven through the public mesh API
    # (hfl.weighted_aggregate). Multi-shard *parity* lives in
    # tests/test_sharded_bank.py — wall time under forced host devices
    # is not meaningful; what matters here is the overhead of the
    # sharded launch (overhead_vs_plain) staying near 1.
    from repro.core import hfl
    from repro.launch import mesh as mesh_lib
    ctx1 = hfl.AggContext.for_mesh(mesh_lib.make_bank_mesh(1))
    us_s = _time(lambda b_, w_, s_: hfl.weighted_aggregate(
        {"w": b_}, w_, s_, n_edge, ctx=ctx1)["w"], mat, wd, seg)
    # per-shard HBM totals are unchanged (each shard reads its N/K rows
    # once, writes E*P once); both comparators are recorded — the gated
    # oracle ratio and the shard_map overhead vs the plain kernel
    rows.append({"setting": "segment_agg_sharded_1shard_64x8x500k",
                 "oracle_us_per_call": round(us, 1),
                 "kernel_us_per_call": round(us_s, 1),
                 "plain_kernel_us_per_call": round(us_k, 1),
                 "overhead_vs_plain": round(us_s / max(us_k, 1e-9), 2),
                 "hbm_bytes_naive": naive_hbm,
                 "hbm_bytes_kernel": kern_hbm,
                 "traffic_ratio": round(naive_hbm / kern_hbm, 2)})
    # ------------------------------------------------------------------
    # sharded async edge round's masked aggregation: edge-style weights
    # (one active edge, the rest masked to zero) through the AggContext
    # sharded launch (shard_map + psum on the 1-shard mesh) vs the jnp
    # oracle. This is the per-upload hot launch of the mesh-aware
    # AsyncHFLEnv (hfl.make_edge_round under a sharded AggContext); the
    # masking folds into the weight vector so the HBM totals match the
    # unmasked row above.
    w_mask = jnp.asarray(np.asarray(wd) * (np.asarray(seg) == 0), jnp.float32)
    us = _time(jax.jit(lambda *a: ref.segment_agg_ref(*a, n_edge)),
               mat, w_mask, seg)
    us_e = _time(lambda b_, w_, s_: hfl.weighted_aggregate(
        {"w": b_}, w_, s_, n_edge, ctx=ctx1)["w"], mat, w_mask, seg)
    rows.append({"setting": "segment_agg_edge_sharded_64x8x500k",
                 "oracle_us_per_call": round(us, 1),
                 "kernel_us_per_call": round(us_e, 1),
                 "hbm_bytes_naive": naive_hbm,
                 "hbm_bytes_kernel": kern_hbm,
                 "traffic_ratio": round(naive_hbm / kern_hbm, 2)})
    # ------------------------------------------------------------------
    # staleness-weighted segment_agg (async runtime flush): the decay
    # folds into the weight vector, so the fused kernel serves the
    # FedBuff-style buffered aggregation with zero extra HBM traffic —
    # the (N,) reweight is noise next to the N*P bank read. Oracle:
    # the numpy/jnp staleness mean (ref.staleness_aggregate_ref
    # semantics on one segment).
    from repro.runtime import staleness_scale
    tau = rng.integers(0, 5, size=(n_dev,))
    ws = jnp.asarray(np.asarray(wd) * staleness_scale(tau, "poly", 0.5))

    def stale_oracle(mat_, w_):
        return (w_[:, None] * mat_).sum(0) / jnp.maximum(w_.sum(), 1e-9)

    us = _time(jax.jit(stale_oracle), mat, ws)
    us_k = _time(lambda m_, w_: ops.segment_agg(
        m_, w_, jnp.zeros((n_dev,), jnp.int32), 1), mat, ws)
    naive_hbm = 4 * (3 * n_dev * p2 + 3 * p2)
    kern_hbm = 4 * (n_dev * p2 + p2)
    rows.append({"setting": "segment_agg_stale_64x500k",
                 "oracle_us_per_call": round(us, 1),
                 "kernel_us_per_call": round(us_k, 1),
                 "hbm_bytes_naive": naive_hbm,
                 "hbm_bytes_kernel": kern_hbm,
                 "traffic_ratio": round(naive_hbm / kern_hbm, 2)})
    # ------------------------------------------------------------------
    # end-to-end aggregation: per-leaf tree-path oracle vs flat-bank
    # engine (flatten -> segment_agg -> unflatten) on a nested pytree
    leaf = p2 // 4
    tree_bank = {"a": mat[:, :leaf].reshape(n_dev, 500, 250),
                 "b": {"w": mat[:, leaf:3 * leaf],
                       "v": mat[:, 3 * leaf:]}}
    us_tree = _time(jax.jit(
        lambda b_, w_, s_: ref.weighted_aggregate_ref(b_, w_, s_, n_edge)),
        tree_bank, wd, seg)

    def flat_path(b_, w_, s_):
        spec = flatbank.bank_spec(b_)
        return spec.unflatten(
            ops.segment_agg(spec.flatten(b_), w_, s_, n_edge))

    us_flat = _time(jax.jit(flat_path), tree_bank, wd, seg)
    rows.append({"setting": "flatbank_agg_64x8x500k",
                 "tree_path_us_per_call": round(us_tree, 1),
                 "flat_path_us_per_call": round(us_flat, 1),
                 "speedup": round(us_tree / max(us_flat, 1e-9), 2)})
    return rows
