"""Segment-weighted model aggregation + bank resync (TPU Pallas).

Arena's hot spot (Eqs. 1/2): dataset-size-weighted means over the flat
model bank. The flat-bank engine (``repro.core.flatbank``) presents the
whole device bank as one ``(N, P)`` matrix; the kernels here do the two
hot-path operations in one launch each:

``segment_agg``
    ``(N, P) bank × (N,) weights × (N,) segment_ids -> (E, P)`` — the
    per-edge (or cloud, E=1) weighted mean. The grid tiles P; one grid
    step owns an ``(N, BN)`` column block resident in VMEM, builds the
    ``(E, N)`` weighted one-hot assignment from an iota/segment-id
    compare, and reduces over N on the MXU. Normalization is fused: the
    per-segment inverse weight sum enters as an ``(E, 1)`` input (it
    depends on traced weights, so it is an array, not a static) and the
    multiply happens before the tile is written — no post-hoc pass over
    the ``(E, P)`` output and no ``(N, P)`` f32 weighted temporary in
    HBM. HBM traffic is the optimal ``N·P`` read + ``E·P`` write versus
    the per-leaf tree path's 3 round trips (weight-scale temp, segment
    sum, normalize).

``segment_broadcast``
    ``(E, P) edge models × (N,) segment_ids -> (N, P)`` — resyncs every
    device row from its edge's model (the Eq. 5 "devices resume from
    their edge" step). The gather is a one-hot matmul per column tile
    and the output is written directly in the bank's storage dtype, so
    no ``(N, P)`` f32 intermediate is materialized when the bank is
    stored in bf16.

``hier_agg`` (legacy API) is the single-segment special case,
``segment_agg(..., num_segments=1)[0]``.

Sharded (multi-host) variants — used under ``jax.shard_map`` when the
bank's device axis N is partitioned across a mesh (see
``repro.core.flatbank.ShardedBankSpec``):

``segment_sum_partial``
    The per-shard kernel: same launch as ``segment_agg`` but with the
    in-kernel normalization disabled (unit inverse), returning the
    *unnormalized* ``(E, P)`` weighted sums plus the local ``(E,)``
    weight sums. Each shard reduces only its local rows.

``combine_partials``
    Call **inside** ``shard_map`` on the shard-local
    ``segment_sum_partial``: combines the partial edge sums and weight
    sums with an axis-scoped ``jax.lax.psum`` over the mesh axes, and
    normalizes (``ops.segment_agg_sharded`` composes the two). The
    result is replicated across shards and matches the single-chip
    ``segment_agg`` up to f32 reduction-order error.

``segment_broadcast`` needs no sharded twin: under ``shard_map`` each
shard calls it with its local segment ids and the (replicated) edge
matrix, resyncing only its own rows — the full-bank broadcast never
materializes on one device.

Tile sizing: ``bn=None`` picks the widest column tile that keeps the
resident blocks within a VMEM budget (8 MiB compiled; effectively
"all columns" in interpret mode, where each grid step pays a full
input copy and a 1-step grid is fastest). Explicit ``bn`` must be a
multiple of 128 (the TPU lane width); P is padded up internally.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANE = 128
# f32 contraction on the MXU: the weighted mean and the one-hot gather
# must not round the f32 bank through a single bf16 pass
_HIGHEST = jax.lax.Precision.HIGHEST


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _auto_bn(p: int, rows_in: int, rows_out: int, interpret: bool) -> int:
    """Widest 128-multiple column tile whose resident blocks fit the
    budget: interpret mode copies full inputs per grid step, so it gets
    a large budget (few grid steps); compiled mode respects VMEM."""
    budget = (256 if interpret else 8) * 1024 * 1024
    bytes_per_col = 4 * (rows_in + rows_out)
    cap = max(_LANE, budget // bytes_per_col // _LANE * _LANE)
    return min(_round_up(p, _LANE), cap)


def _segment_agg_kernel(seg_ref, w_ref, inv_ref, x_ref, o_ref):
    """One (N, BN) column tile -> (E, BN) weighted segment means."""
    e, n = o_ref.shape[0], x_ref.shape[0]
    ids = jax.lax.broadcasted_iota(jnp.int32, (e, n), 0)
    # (E, N) weighted one-hot assignment, built in VMEM
    a = jnp.where(ids == seg_ref[...], w_ref[...].astype(jnp.float32), 0.0)
    acc = jnp.dot(a, x_ref[...].astype(jnp.float32),
                  precision=_HIGHEST, preferred_element_type=jnp.float32)
    o_ref[...] = (acc * inv_ref[...]).astype(o_ref.dtype)


def _segment_agg_call(bank, w32, inv, segment_ids, num_segments: int,
                      bn: int | None, interpret: bool):
    """Shared launch: (N, P) bank x (N,) f32 weights x (E, 1) scale ->
    (E, P) f32 ``scale * segment-weighted sums``."""
    n, p = bank.shape
    e = int(num_segments)
    if bn is None:
        bn = _auto_bn(p, n, e, interpret)
    p_pad = _round_up(p, bn)
    if p_pad != p:
        bank = jnp.pad(bank, ((0, 0), (0, p_pad - p)))
    out = pl.pallas_call(
        _segment_agg_kernel,
        grid=(p_pad // bn,),
        in_specs=[
            pl.BlockSpec((1, n), lambda i: (0, 0)),      # segment ids
            pl.BlockSpec((1, n), lambda i: (0, 0)),      # weights
            pl.BlockSpec((e, 1), lambda i: (0, 0)),      # per-segment scale
            pl.BlockSpec((n, bn), lambda i: (0, i)),     # bank tile
        ],
        out_specs=pl.BlockSpec((e, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((e, p_pad), jnp.float32),
        interpret=interpret,
        name="segment_agg",
    )(segment_ids.reshape(1, n).astype(jnp.int32),
      w32.reshape(1, n), inv, bank)
    return out[:, :p]


def segment_agg(bank, weights, segment_ids, num_segments: int, *,
                bn: int | None = None, interpret: bool):
    """bank: (N, P); weights: (N,); segment_ids: (N,) int. Returns the
    per-segment weighted means (num_segments, P) f32:

        out[j] = sum_{i: seg_i=j} w_i bank[i] / max(sum w_i, 1e-9)

    Empty segments return zeros (the weight-sum clamp), matching the
    tree-path oracle. Weights may be traced; the inverse weight sums are
    computed outside and enter the kernel as an (E, 1) input so the
    normalization still happens in-kernel.
    """
    e = int(num_segments)
    w32 = weights.astype(jnp.float32)
    wsum = jnp.maximum(jax.ops.segment_sum(w32, segment_ids, e), 1e-9)
    inv = (1.0 / wsum).reshape(e, 1)
    return _segment_agg_call(bank, w32, inv, segment_ids, e, bn, interpret)


def segment_sum_partial(bank, weights, segment_ids, num_segments: int, *,
                        bn: int | None = None, interpret: bool):
    """Per-shard half of the sharded aggregation: the same fused launch
    as ``segment_agg`` but *unnormalized* (unit scale). Returns

        sums:  (num_segments, P) f32  — sum_{i: seg_i=j} w_i bank[i]
        wsum:  (num_segments,)   f32  — sum_{i: seg_i=j} w_i

    over the rows this shard holds. Combine across shards with ``psum``
    and normalize (``combine_partials`` does both).
    """
    e = int(num_segments)
    w32 = weights.astype(jnp.float32)
    wsum = jax.ops.segment_sum(w32, segment_ids, e)
    ones = jnp.ones((e, 1), jnp.float32)
    sums = _segment_agg_call(bank, w32, ones, segment_ids, e, bn, interpret)
    return sums, wsum


def combine_partials(sums, wsum, axis_names):
    """Second half of the sharded ``segment_agg`` — call inside
    ``shard_map`` with the bank rows partitioned over ``axis_names``,
    on each shard's ``segment_sum_partial`` of its local
    ``(N_local, P)`` rows. The (E, P) partial edge sums and (E,) weight
    sums are combined with an axis-scoped ``psum`` and normalized with
    the same multiply-by-reciprocal the single-chip kernel fuses in, so
    the returned (E, P) means are replicated on every shard and equal
    the single-chip result up to f32 reduction-order error — and
    **bitwise** when every segment's nonzero-weight rows live within a
    single shard (the ``ShardedBankSpec`` layout contract): zero-weight
    rows and zero psum partials are reduction-neutral
    (``fma(0, x, acc) == acc``), so the owner shard reproduces the
    single-chip accumulation chain exactly. A segment spanning shards
    splits that chain at a psum and the result differs in the last
    ulp. Empty segments (globally) return zeros."""
    sums = jax.lax.psum(sums, axis_names)
    wsum = jax.lax.psum(wsum, axis_names)
    # normalize exactly like the single-chip kernel: multiply by the
    # reciprocal (``acc * inv``), not divide — division rounds
    # differently, and the async edge round's bitwise-parity contract
    # (core.hfl.AggContext) needs the two paths to agree to the bit
    # whenever the summation itself is (shard-alignment) exact.
    inv = 1.0 / jnp.maximum(wsum, 1e-9)
    return sums * inv[:, None]


def _segment_bcast_kernel(seg_ref, m_ref, o_ref):
    """One (E, BN) model tile -> (N, BN) gathered bank tile."""
    n, e = o_ref.shape[0], m_ref.shape[0]
    ids = jax.lax.broadcasted_iota(jnp.int32, (n, e), 1)
    a = (ids == seg_ref[...]).astype(jnp.float32)        # (N, E) one-hot
    o_ref[...] = jnp.dot(a, m_ref[...].astype(jnp.float32),
                         precision=_HIGHEST,
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


def segment_broadcast(models, segment_ids, *, out_dtype=None,
                      bn: int | None = None, interpret: bool):
    """models: (E, P); segment_ids: (N,) int. Returns (N, P) with
    ``out[i] = models[segment_ids[i]]`` cast to ``out_dtype`` (default:
    models.dtype) as it is written — the fused bank resync."""
    e, p = models.shape
    n = segment_ids.shape[0]
    out_dtype = jnp.dtype(out_dtype or models.dtype)
    if bn is None:
        bn = _auto_bn(p, e, n, interpret)
    p_pad = _round_up(p, bn)
    if p_pad != p:
        models = jnp.pad(models, ((0, 0), (0, p_pad - p)))
    out = pl.pallas_call(
        _segment_bcast_kernel,
        grid=(p_pad // bn,),
        in_specs=[
            pl.BlockSpec((n, 1), lambda i: (0, 0)),      # segment ids
            pl.BlockSpec((e, bn), lambda i: (0, i)),     # model tile
        ],
        out_specs=pl.BlockSpec((n, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, p_pad), out_dtype),
        interpret=interpret,
        name="segment_broadcast",
    )(segment_ids.reshape(n, 1).astype(jnp.int32), models)
    return out[:, :p]


def hier_agg(bank, weights, *, bn: int | None = None,
             interpret: bool):
    """Legacy single-segment API. bank: (R, N); weights: (R,). Returns
    the weighted mean (N,) f32 — ``segment_agg`` with one segment."""
    r = bank.shape[0]
    return segment_agg(bank, weights, jnp.zeros((r,), jnp.int32), 1,
                       bn=bn, interpret=interpret)[0]
