"""Masked lexicographic argmin: the vectorized candidate selection.

The reference iterates nodes from least to most allocatable at the target
priority over the indexed resources, tie-broken by node id
(nodeiteration.go:170-185), and takes the first feasible one. Dense form:
among feasible nodes, take the lexicographic argmin of
(key_0, key_1, ..., id_rank) — computed by iterative mask refinement,
one masked-min reduction per key level. O(K * N), fully parallel, and
reduces cleanly across device shards (each shard returns its local winner;
a tiny cross-shard argmin picks the global one).

The same primitive picks the next queue in the candidate-gang loop (float
cost keys) — any total order expressible as a lexicographic key works.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _sentinel(dtype):
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf, dtype)
    return jnp.asarray(jnp.iinfo(dtype).max, dtype)


def masked_min(values, mask):
    """Min of values where mask, else the dtype's max sentinel."""
    return jnp.min(jnp.where(mask, values, _sentinel(values.dtype)))


def masked_keys(keys, mask):
    """Substitute each key's masked-out entries with its dtype's max
    sentinel, so masked entries sort last under any lexicographic order.

    The one shared helper for every sort/argmin call site that needs
    sentinel keys (dist._fill_sort, the fair-preemption walk order):
    callers used to each re-derive and re-broadcast their own sentinel
    per key per call, which both duplicated the pattern and let the
    sentinels drift (BIG vs iinfo.max) between sites."""
    return [jnp.where(mask, k, _sentinel(k.dtype)) for k in keys]


def _f32_word(x):
    """float32 -> int32 of the same order, the way `lax.sort` compares
    floats (-0.0 == 0.0; NaNs equal and last)."""
    x = jnp.where(x == 0, jnp.zeros_like(x), x)
    x = jnp.where(jnp.isnan(x), jnp.full_like(x, np.nan), x)
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ np.int32(2**31 - 1), bits)


def _words32(key):
    """Order-preserving 32-bit integer words of one sort key, most
    significant first. A 64-bit integer becomes (signed high word,
    unsigned low word). A float64 becomes three float32s (h, m, l) with
    h + m + l == x exactly — each a round-to-nearest of the remainder, so
    their lexicographic order is x's own wherever float32's exponent
    range holds x (XLA:TPU's emulated float64 has no wider range, and
    cannot be bitcast); non-finite x keeps its h and zero remainders.
    Floats end as int32 words, so the sort compares integers only."""
    if key.dtype == jnp.bool_:
        return [key.astype(jnp.int32)]
    if key.dtype == jnp.float32:
        return [_f32_word(key)]
    if key.dtype == jnp.float64:
        finite = jnp.isfinite(key)
        words = []
        for _ in range(3):
            w = key.astype(jnp.float32)
            words.append(_f32_word(w))
            key = jnp.where(finite, key - w.astype(jnp.float64), 0.0)
        return words
    if jnp.dtype(key.dtype).itemsize < 8:
        return [key]
    if key.dtype == jnp.uint64:
        return [
            (key >> np.uint64(32)).astype(jnp.uint32),
            (key & np.uint64(2**32 - 1)).astype(jnp.uint32),
        ]
    return [
        (key >> np.int64(32)).astype(jnp.int32),
        (key & np.int64(2**32 - 1)).astype(jnp.uint32),
    ]


def lexsort(keys):
    """Indices of a stable lexicographic sort (first key most
    significant) — `jnp.lexsort(tuple(reversed(keys)))` index for index.

    Every key is split into 32-bit words, and the words are sorted one
    at a time, least significant first, each pass stable: XLA:TPU's
    compile time grows steeply with a sort's key count and key width (a
    3-key int64 sort of 20k entries compiled in 519 s; a 1-key int32
    sort of 1M entries compiles in under 20 s)."""
    words = [w for k in keys for w in _words32(jnp.asarray(k))]
    perm = None
    for w in reversed(words):
        if perm is None:
            perm = jax.lax.broadcasted_iota(jnp.int32, w.shape, 0)
        else:
            w = w[perm]
        _, perm = jax.lax.sort((w, perm), num_keys=1, is_stable=True)
    return perm


def masked_lexsort(keys, mask):
    """Indices sorting masked entries by lexicographic key (first key
    most significant); masked-out entries sort last."""
    return lexsort(masked_keys(keys, mask))


def lex_argmin(keys, mask):
    """Index of the lexicographically smallest entry among masked entries.

    keys: list of [N] arrays (int or float), most-significant first; the last
    key must be unique among masked entries (e.g. an id rank).
    Returns (index int32, found bool); index is 0 when nothing matches.
    """
    m = mask
    for k in keys:
        best = masked_min(k, m)
        m = m & (k == best)
    found = jnp.any(mask)
    idx = jnp.argmax(m)  # final key unique -> at most one bit set
    return jnp.where(found, idx, 0).astype(jnp.int32), found
