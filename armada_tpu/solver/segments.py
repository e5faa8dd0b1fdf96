"""Scatter-free reductions over the round's padded job and slot axes.

XLA:TPU applies a scatter's updates one at a time: on a v5e a float64
`segment_sum` over the flagship's 2^21 padded jobs costs ~190 ms (91 ns
a row), an int32 `segment_min` ~18 ms, however few rows are live. The
solve's setup, window gather and finish programs are full of such
reductions over masks that are almost all false, so every one of them
goes through these helpers instead. Each gives the same bits as the
scatter it replaces, and each picks its form from static shapes alone:

- a small static segment axis (queues, queue x class) becomes a dense
  masked reduce over `[rows, segments]`, which XLA fuses into one pass
  (`segment_sum_dense`, `segment_min_dense`);
- a large target axis (nodes, jobs) scatters only the rows that
  contribute: a gather-only stream compaction (cumsum, then
  `searchsorted`, no scatter) packs them into a static bucket
  (`compact`), and the full scatter stays as the `lax.cond` branch for
  a count above the bucket (`scatter_rows`).

`scatter_rows` reports which branch ran as an int32[2] (bounded,
fallback) count, which the solve totals onto its `solve` span.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# A compaction over fewer rows than this is not worth its cumsum: the
# axis is scattered whole, as before.
MIN_BUCKET = 1024


def compaction_bucket(n: int) -> int:
    """Static capacity of the bounded path over `n` rows: 1/512 of the
    axis, at least MIN_BUCKET (4,096 rows at the flagship's 2^21). An
    axis no larger than its bucket is never compacted."""
    return min(int(n), max(MIN_BUCKET, int(n) >> 9))


def no_compaction():
    """The path count of a site that scattered its axis whole."""
    return jnp.zeros(2, jnp.int32)


def segment_sum_dense(data, seg, num_segments: int):
    """`jax.ops.segment_sum(data, seg, num_segments)` as one masked
    reduce over [rows, segments]; rows whose `seg` lies outside
    [0, num_segments) drop, as the scatter drops them. Equal bit for bit
    wherever the sum is order-free: integers, or integer-valued floats
    whose partial sums stay below 2**53 (device units in float64)."""
    hit = seg[:, None] == jnp.arange(num_segments, dtype=seg.dtype)[None, :]
    hit = hit.reshape(hit.shape + (1,) * (data.ndim - 1))
    zero = jnp.zeros((), data.dtype)
    return jnp.sum(jnp.where(hit, data[:, None], zero), axis=0, dtype=data.dtype)


def segment_min_dense(data, seg, num_segments: int):
    """`jax.ops.segment_min(data, seg, num_segments)` for 1-D integer
    `data` as one masked reduce; an empty segment reads the dtype's
    maximum, as the scatter's identity does."""
    top = jnp.asarray(jnp.iinfo(data.dtype).max, data.dtype)
    hit = seg[:, None] == jnp.arange(num_segments, dtype=seg.dtype)[None, :]
    return jnp.min(jnp.where(hit, data[:, None], top), axis=0)


def compact(mask, size: int):
    """(idx, count): the positions of the first `size` true entries of
    the 1-D `mask` in ascending order, -1 past the count, and the number
    of true entries. `idx` equals `jnp.nonzero(mask, size=size,
    fill_value=-1)[0]`, with no scatter: the k-th true entry is where the
    running count first reaches k."""
    n = mask.shape[0]
    running = jnp.cumsum(mask.astype(jnp.int32))
    want = jnp.arange(1, size + 1, dtype=jnp.int32)
    idx = jnp.searchsorted(running, want, side="left").astype(jnp.int32)
    return jnp.where(idx < n, idx, -1), running[-1]


def scatter_rows(mask, bounded, full):
    """Run a scatter over the rows of `mask` that contribute.

    `full()` scatters every row. `bounded(idx)` scatters only the rows
    `idx` (-1 = none), which must hold every true row of `mask`; rows
    outside the mask must not change the result. The bounded branch runs
    when the true rows fit `compaction_bucket(len(mask))`, the full one
    otherwise; an axis no larger than its bucket runs `full` with no
    compaction. Returns (result, int32[2] path count (bounded, fallback))."""
    n = mask.shape[0]
    size = compaction_bucket(n)
    if size >= n:
        return full(), no_compaction()
    idx, count = compact(mask, size)
    fits = count <= size
    out = jax.lax.cond(fits, lambda: bounded(idx), full)
    return out, jnp.stack([fits, ~fits]).astype(jnp.int32)
