"""Segmented reductions with selection masks.

The TPU-native replacement for the reference's hash-aggregation inner loops
(executor/aggregate.go partial workers; mocktikv row-at-a-time aggregation):
group codes are dense ints, so partial aggregation is a segment reduction —
an operation XLA compiles to efficient scatter/one-hot-matmul kernels on the
MXU instead of a hash table.  Reference pattern: "partial aggregates"
two-phase split (planner/core/task.go agg pushdown; DrJAX mapreduce
primitives, PAPERS.md).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


# Group-count threshold below which segment reductions unroll into one
# masked full reduction per group instead of a scatter (a TPU scatter over
# millions of colliding updates serializes).  The unrolled reductions are
# NOT one traversal: the v5e compiler keeps each `where + reduce` a fusion
# of its own, a full pass over its operands (30 `select_reduce_fusion` for
# the five int64 sums of TPC-H Q1 at G=6; ISSUE 35, PERF.md section 5).  So
# the counts and integer sums of a dense aggregate do not come here any
# more: copr/fusion.py::_BlockSums emits them all as one variadic reduce.
# What still unrolls here is min, max, first_row and float sums (whose
# order of additions must not change), one pass each.  High-NDV
# aggregations take the sort-based mesh path instead.
UNROLL_G = 32


def masked_segment_sum(data, gidx, mask, num_segments: int):
    """sum of data[i] into segment gidx[i] where mask[i]."""
    zero = jnp.zeros((), dtype=data.dtype)
    if num_segments <= UNROLL_G:
        return jnp.stack([
            jnp.sum(jnp.where(mask & (gidx == g), data, zero))
            for g in range(num_segments)
        ])
    contrib = jnp.where(mask, data, zero)
    return jax.ops.segment_sum(contrib, gidx, num_segments=num_segments)


def masked_segment_count(gidx, mask, num_segments: int):
    if num_segments <= UNROLL_G:
        return jnp.stack([
            jnp.sum((mask & (gidx == g)).astype(jnp.int64))
            for g in range(num_segments)
        ])
    return jax.ops.segment_sum(
        mask.astype(jnp.int64), gidx, num_segments=num_segments
    )


def masked_segment_min(data, gidx, mask, num_segments: int):
    big = _extreme(data.dtype, True)
    if num_segments <= UNROLL_G:
        return jnp.stack([
            jnp.min(jnp.where(mask & (gidx == g), data, big))
            for g in range(num_segments)
        ])
    contrib = jnp.where(mask, data, big)
    return jax.ops.segment_min(contrib, gidx, num_segments=num_segments)


def masked_segment_max(data, gidx, mask, num_segments: int):
    small = _extreme(data.dtype, False)
    if num_segments <= UNROLL_G:
        return jnp.stack([
            jnp.max(jnp.where(mask & (gidx == g), data, small))
            for g in range(num_segments)
        ])
    contrib = jnp.where(mask, data, small)
    return jax.ops.segment_max(contrib, gidx, num_segments=num_segments)


def masked_segment_argfirst(gidx, mask, num_segments: int):
    """Index of the first masked row per segment (for FIRST_ROW);
    num_rows (= len(gidx)) where the segment is empty."""
    n = gidx.shape[0]
    idx = jnp.arange(n, dtype=jnp.int64)
    if num_segments <= UNROLL_G:
        return jnp.stack([
            jnp.min(jnp.where(mask & (gidx == g), idx, n))
            for g in range(num_segments)
        ])
    contrib = jnp.where(mask, idx, n)
    return jax.ops.segment_min(contrib, gidx, num_segments=num_segments)


def _extreme(dtype, want_max: bool):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf if want_max else -jnp.inf, dtype=dtype)
    info = jnp.iinfo(dtype)
    return jnp.array(info.max if want_max else info.min, dtype=dtype)


def segment_min(data, gidx, num_segments: int):
    """Plain segment min with the same small-G unrolling as the masked ops."""
    if num_segments <= UNROLL_G:
        big = _extreme(data.dtype, True)
        return jnp.stack([
            jnp.min(jnp.where(gidx == g, data, big))
            for g in range(num_segments)
        ])
    return jax.ops.segment_min(data, gidx, num_segments=num_segments)


def _two_level(x, scan, combine):
    """An inclusive scan of a vector in two levels (along blocks of up
    to 1,024, then over the blocks' last values): the chip's compiler
    takes seconds over a one-level scan of millions of rows and a
    fraction of one over this."""
    n = x.shape[0]
    b = math.gcd(n, 1024)
    inner = scan(x.reshape(n // b, b), 1)
    ends = scan(inner[:, -1], 0)
    return combine(inner[:, 1:], inner[:, :1], ends[:-1]).reshape(n)


def prefix_sums(x):
    """Inclusive running sum of a vector, in its own dtype."""
    def combine(rest, first, before):
        before = jnp.concatenate([jnp.zeros(1, x.dtype), before])[:, None]
        return jnp.concatenate([first + before, rest + before], axis=1)

    return _two_level(x, jnp.cumsum, combine)


def prefix_max(x):
    """Inclusive running maximum of a vector."""
    def combine(rest, first, before):
        low = jnp.full(1, _extreme(x.dtype, want_max=False), x.dtype)
        before = jnp.concatenate([low, before])[:, None]
        return jnp.concatenate([jnp.maximum(first, before),
                                jnp.maximum(rest, before)], axis=1)

    return _two_level(x, lambda a, axis: jax.lax.cummax(a, axis=axis),
                      combine)


def prefix_counts(mask):
    """Inclusive running count of a boolean vector, int32."""
    return prefix_sums(mask.astype(jnp.int32))


def first_marked(mask, size: int, fill: int, counts=None):
    """Row numbers of the first `size` rows `mask` marks, in order, int32;
    slots past their number hold `fill` (`jnp.nonzero(mask, size=size,
    fill_value=fill)[0]`, by one prefix count and one scatter of the row
    numbers instead of its prefix sum, bincount and second prefix sum).
    `counts` is `prefix_counts(mask)` where the caller has it."""
    n = mask.shape[0]
    if counts is None:
        counts = prefix_counts(mask)
    return jnp.full(size, fill, dtype=jnp.int32) \
        .at[jnp.where(mask, counts - 1, size)] \
        .set(jnp.arange(n, dtype=jnp.int32), mode="drop")
