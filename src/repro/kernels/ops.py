"""jit'd public wrappers around the Pallas kernels.

The platform picks the kernel mode: a call lowered for a TPU compiles
the kernel with Mosaic, a call lowered for any other backend (the CPU
test suite) runs the kernel body in the Pallas interpreter. The choice
is made by ``jax.lax.platform_dependent`` when the call is lowered, so
importing this module reads no device state, and a TPU program never
contains an interpreted kernel.

``segment_agg`` / ``segment_broadcast`` are the flat-bank hot path
(``repro.core.flatbank`` + ``repro.core.hfl``); ``hier_agg`` is the
legacy single-segment API kept for its callers and tests.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import hier_agg as _ha
from repro.kernels import wkv6 as _wkv


def _by_platform(kernel, *args, **static):
    """``kernel(*args, **static)`` compiled on a TPU and interpreted on
    every other platform (``interpret`` follows the lowering platform;
    the other branch is pruned before compilation)."""
    return jax.lax.platform_dependent(
        *args,
        tpu=functools.partial(kernel, interpret=False, **static),
        default=functools.partial(kernel, interpret=True, **static))


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "q_offset", "bq", "bk"))
def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    bq=128, bk=128):
    return _by_platform(_fa.flash_attention, q, k, v, causal=causal,
                        window=window, q_offset=q_offset, bq=bq, bk=bk)


@functools.partial(jax.jit, static_argnames=("bn",))
def hier_agg(bank, weights, *, bn=None):
    return _by_platform(_ha.hier_agg, bank, weights, bn=bn)


@functools.partial(jax.jit, static_argnames=("num_segments", "bn"))
def segment_agg(bank, weights, segment_ids, num_segments, *, bn=None):
    """(N, P) x (N,) weights x (N,) segment ids -> (E, P) f32 means."""
    return _by_platform(_ha.segment_agg, bank, weights, segment_ids,
                        num_segments=num_segments, bn=bn)


@functools.partial(jax.jit, static_argnames=("num_segments", "bn"))
def segment_sum_partial(bank, weights, segment_ids, num_segments, *,
                        bn=None):
    """Per-shard unnormalized (E, P) sums + (E,) weight sums."""
    return _by_platform(_ha.segment_sum_partial, bank, weights,
                        segment_ids, num_segments=num_segments, bn=bn)


def segment_agg_sharded(bank, weights, segment_ids, num_segments,
                        axis_names, *, bn=None):
    """Sharded segment_agg: per-shard kernel + psum over ``axis_names``.
    Must run inside ``shard_map`` (no standalone jit wrapper — the psum
    needs the bound mesh axes). Only the kernel launch sits under the
    platform switch; the psum stays outside it."""
    sums, wsum = segment_sum_partial(bank, weights, segment_ids,
                                     num_segments, bn=bn)
    return _ha.combine_partials(sums, wsum, axis_names)


@functools.partial(jax.jit, static_argnames=("out_dtype", "bn"))
def segment_broadcast(models, segment_ids, *, out_dtype=None, bn=None):
    """(E, P) x (N,) segment ids -> (N, P) bank resync (fused gather)."""
    return _by_platform(_ha.segment_broadcast, models, segment_ids,
                        out_dtype=out_dtype, bn=bn)


@functools.partial(jax.jit, static_argnames=("chunk",))
def wkv6(r, k, v, w, u, *, chunk=64):
    return _by_platform(_wkv.wkv6_chunked, r, k, v, w, u, chunk=chunk)
