"""Scatter-free full-axis reductions (solver/segments.py).

Each form must give the bits of the scatter it replaces: the dense
segment reductions against `jax.ops.segment_sum` / `segment_min`, the
stream compaction against `jnp.nonzero(size=...)`, the compacted node
and gang scatters against the whole-axis ones, on masks that are empty,
full, random, live only in the unpadded prefix, and above the bucket
(the fallback branch). The solve's outputs must not move when its
compactions are forced through either branch, and the `solve` span's
`compact_bounded` / `compact_fallback` attributes must say which ran,
with `host_syncs` untouched.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from armada_tpu.solver import segments
from armada_tpu.solver.dist import LOCAL
from armada_tpu.solver.segments import (
    compact,
    compaction_bucket,
    scatter_rows,
    segment_min_dense,
    segment_sum_dense,
)

N = 4096  # above MIN_BUCKET, so the compaction engages unpatched
Q, C = 16, 2


def _mask(kind, n=N, seed=0):
    rng = np.random.default_rng(seed)
    if kind == "empty":
        return np.zeros(n, bool)
    if kind == "full":
        return np.ones(n, bool)
    if kind == "random":
        return rng.random(n) < 0.1
    if kind == "sparse":
        m = np.zeros(n, bool)
        m[rng.choice(n, 37, replace=False)] = True
        return m
    if kind == "pad_rows":  # live rows in the prefix, padded tail all false
        m = rng.random(n) < 0.5
        m[n * 3 // 4:] = False
        return m
    if kind == "over_bucket":
        m = np.zeros(n, bool)
        m[rng.choice(n, compaction_bucket(n) + 1, replace=False)] = True
        return m
    raise ValueError(kind)


MASKS = ["empty", "full", "random", "sparse", "pad_rows", "over_bucket"]


@pytest.mark.parametrize("kind", MASKS)
@pytest.mark.parametrize("size", [1, 64, 1024, N + 3])
def test_compact_equals_nonzero(kind, size):
    m = jnp.asarray(_mask(kind))
    idx, count = jax.jit(compact, static_argnums=1)(m, size)
    ref = jnp.nonzero(m, size=size, fill_value=-1)[0]
    assert idx.dtype == jnp.int32
    assert np.array_equal(np.asarray(idx), np.asarray(ref))
    assert int(count) == int(np.asarray(m).sum())


@pytest.mark.parametrize("kind", MASKS)
@pytest.mark.parametrize("segs", [1, Q, Q * C])
@pytest.mark.parametrize("dtype", ["float64", "int32"])
def test_segment_sum_dense_equals_segment_sum(kind, segs, dtype):
    rng = np.random.default_rng(segs)
    m = jnp.asarray(_mask(kind))
    # Device units: integers, float64 sums of them exact below 2**53.
    data = jnp.asarray(rng.integers(0, 2**20, (N, 4)), dtype)
    data = jnp.where(m[:, None], data, jnp.zeros((), data.dtype))
    seg = jnp.asarray(rng.integers(0, segs, N), jnp.int32)
    got = jax.jit(segment_sum_dense, static_argnums=2)(data, seg, segs)
    ref = jax.ops.segment_sum(data, seg, num_segments=segs)
    assert got.dtype == ref.dtype
    assert np.array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("kind", MASKS)
@pytest.mark.parametrize("segs", [1, Q, Q * C])
def test_segment_min_dense_equals_segment_min(kind, segs):
    rng = np.random.default_rng(segs + 1)
    m = jnp.asarray(_mask(kind))
    pos = jnp.where(m, jnp.arange(N, dtype=jnp.int32), jnp.int32(2**30))
    # Sorted like the slot table's queue column, with empty segments.
    seg = jnp.asarray(np.sort(rng.integers(0, segs, N)) // 2 * 2 % segs, jnp.int32)
    got = jax.jit(segment_min_dense, static_argnums=2)(pos, seg, segs)
    ref = jax.ops.segment_min(pos, seg, num_segments=segs)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("kind", MASKS)
def test_sparse_segment_to_nodes_equals_segment_to_nodes(kind):
    """The compacted node scatter against the whole-axis one, and its
    path count: bounded at or under the bucket, fallback above it."""
    rng = np.random.default_rng(7)
    ln = 300
    m = jnp.asarray(_mask(kind))
    nodes = jnp.asarray(rng.integers(-1, ln, N), jnp.int32)
    contrib = jnp.asarray(rng.integers(1, 1000, (N, 3)), jnp.int32)
    contrib = jnp.where((m & (nodes >= 0))[:, None], contrib, 0)
    got, path = jax.jit(LOCAL.sparse_segment_to_nodes, static_argnums=2)(
        contrib, nodes, ln
    )
    ref = LOCAL.segment_to_nodes(contrib, nodes, ln)
    assert np.array_equal(np.asarray(got), np.asarray(ref))
    live = int(np.asarray(jnp.any(contrib != 0, axis=1)).sum())
    fell = live > compaction_bucket(N)
    assert np.asarray(path).tolist() == [int(not fell), int(fell)]


@pytest.mark.parametrize("kind", MASKS)
def test_scatter_rows_bool_max_equals_at_max(kind):
    """The gang completion's form: `.at[flat].max(sel)` over a flat
    member table against setting only the compacted members."""
    rng = np.random.default_rng(3)
    J = 1500
    sel = jnp.asarray(_mask(kind))
    flat = jnp.asarray(rng.integers(0, J, N), jnp.int32)

    def full():
        return jnp.zeros(J, bool).at[flat].max(sel)

    def bounded(idx):
        jobs = jnp.where(idx >= 0, flat[jnp.clip(idx, 0, N - 1)], J)
        return jnp.zeros(J, bool).at[jobs].set(True, mode="drop")

    got, path = jax.jit(lambda: scatter_rows(sel, bounded, full))()
    assert np.array_equal(np.asarray(got), np.asarray(full()))
    fell = int(np.asarray(sel).sum()) > compaction_bucket(N)
    assert np.asarray(path).tolist() == [int(not fell), int(fell)]


def test_small_axis_scatters_whole():
    """An axis no larger than its bucket is never compacted: the full
    branch runs and the path count stays zero."""
    n = segments.MIN_BUCKET
    sel = jnp.ones(n, bool)
    out, path = scatter_rows(sel, lambda idx: None, lambda: jnp.sum(sel))
    assert int(out) == n and np.asarray(path).tolist() == [0, 0]


# ---------------------------------------------------------------------------
# The solve, with its compactions forced through each branch


BUCKETS = {
    "off": None,  # the shapes' own bucket: these rounds are below it
    "bounded": lambda n: n - 1,
    "fallback": lambda n: 1,
}


@pytest.fixture(scope="module")
def forced_solves():
    """One hot-window test round (evictions, fair preemption, gangs)
    solved fused and host-driven under each bucket rule."""
    from tests.test_hotwindow import _dev

    from armada_tpu.solver.kernel import solve_round
    from armada_tpu.utils.tracing import TRACER

    dev = _dev(fast_fill=True)
    real = segments.compaction_bucket
    out = {}
    try:
        for name, rule in BUCKETS.items():
            segments.compaction_bucket = rule or real
            jax.clear_caches()
            fused = solve_round(dev)
            fused_span = TRACER.spans("solve")[-1]
            windowed = solve_round(dev, window=4, window_min_slots=0)
            out[name] = (fused, fused_span, windowed, TRACER.spans("solve")[-1])
    finally:
        segments.compaction_bucket = real
        jax.clear_caches()
    return out


@pytest.mark.parametrize("branch", ["bounded", "fallback"])
@pytest.mark.parametrize("path", ["fused", "windowed"])
def test_forced_compaction_solve_bit_exact(forced_solves, branch, path):
    from tests.test_hotwindow import _assert_bit_exact

    pick = 0 if path == "fused" else 2
    base, got = forced_solves["off"][pick], forced_solves[branch][pick]
    _assert_bit_exact(base, got, f"{path} {branch}")
    assert int(base["num_loops"]) == int(got["num_loops"])
    assert "compactions" not in got


def test_solve_span_counts_compaction_paths(forced_solves):
    """Six full-axis scatters a round here (setup and finish: the gang
    completion and the two priority rows' node scatters). Below the
    shapes' bucket none is compacted; forced, every one counts on the
    branch it took, on both paths; host_syncs is the same throughout."""
    counts = {}
    for name, (_, fused_span, windowed, span) in forced_solves.items():
        assert windowed["profile"]["compacted"]
        for s in (fused_span, span):
            counts.setdefault(name, []).append(
                (s.attrs["compact_bounded"], s.attrs["compact_fallback"])
            )
    assert counts["off"] == [(0, 0), (0, 0)]
    assert counts["bounded"] == [(6, 0), (6, 0)]
    for bounded, fallback in counts["fallback"]:
        assert bounded + fallback == 6 and fallback >= 1
    syncs = {name: v[3].attrs["host_syncs"] for name, v in forced_solves.items()}
    assert len(set(syncs.values())) == 1, syncs
    assert all(v[1].attrs["host_syncs"] == 1 for v in forced_solves.values())
