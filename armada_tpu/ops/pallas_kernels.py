"""Pallas solve kernels: fused pass-1 scoring blocks + winner reduction.

The kernel seam (ROADMAP item 2): the fill loop's candidate-chain math —
feasibility masking, best-fit bin-pack caps, and the fused int64 K-key
packing — runs as one pass over VMEM-sized node blocks instead of a
chain of materialized [N] intermediates, and the hierarchical winner
exchange reduces gathered per-host tuples with a kernel instead of
`all_gather`+argmin.

Three executable paths share ONE scoring body (`_score_values`):

- ``blocked``: `_score_values` applied to the whole node axis as a single
  XLA block, plus the radix-threshold top-B selection (`fill_take`) that
  replaces the per-fill-loop `jnp.lexsort` — the measurable CPU win
  (the threshold walk is O(bits * N) sweeps + one B-sized sort, ~4x the
  65k-node single-key sort on this host).
- ``pallas``: the same body wrapped in `pl.pallas_call` over
  `BLOCK_NODES`-sized node blocks, compiled by Mosaic on a TPU and
  interpreted (``interpret=True``) on any other backend, so CPU tier-1
  asserts bit-exactness against the lax path block-for-block.
- ``native``: the pallas path plus the ICI ring winner exchange
  (`make_async_remote_copy`) on multi-host meshes. TPU only: asked for
  anywhere else, `resolve_kernel_path` raises.

Bit-exactness is structural, not numerical luck: every op here is
integer/bool (masking, `//`, clips, shifts), the per-node math has no
cross-block reduction, and the packed key is carried as a (hi, lo)
int32 pair — 31 payload bits each — whose recombination
``(hi << 31) | lo`` equals `kernel._pack_fill_keys`'s mixed-radix int64
exactly whenever the pack plan's bit widths sum to <= 62 (each width
<= 31, so no int32 shift overflows). TPU lanes never need an int64.

`CollectiveStats` booking: every pallas call site notes its block count
and VMEM-resident bytes, and the winner exchange notes
its step count and DMA bytes, at trace time — the fabric cost model is
asserted on CPU even where the hardware isn't (tests/test_pallas_parity.py).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .select import lexsort, masked_keys

KERNEL_PATHS = ("lax", "blocked", "pallas", "native")
PATH_ENV = "ARMADA_TPU_KERNEL_PATH"

# Node-axis block width for the pallas scoring kernel. Padded node counts
# are powers of two >= 8 (kernel_prep._pow2), so BLOCK_NODES always
# divides N or exceeds it; lane-width (128) aligned for the native path.
BLOCK_NODES = 1024

_HI_SHIFT = 31
_LO_MASK = (1 << 31) - 1
_I64_SENTINEL = (1 << 63) - 1


def native_available() -> bool:
    """Pallas kernels compile natively only on a TPU backend; anywhere
    else they run in interpret mode."""
    return jax.default_backend() == "tpu"


def resolve_kernel_path(configured: str = "lax") -> str:
    """The effective solve kernel path for this process.

    ``ARMADA_TPU_KERNEL_PATH`` overrides config (the bench A/B lever).
    A path that cannot run here raises: an unknown name, or ``native``
    off-TPU."""
    path = os.environ.get(PATH_ENV, "").strip() or str(configured or "lax")
    if path not in KERNEL_PATHS:
        raise ValueError(
            f"unknown solve kernel path {path!r} (one of {KERNEL_PATHS})"
        )
    if path == "native" and not native_available():
        raise RuntimeError(
            f"kernel path 'native' needs a TPU; JAX runs on "
            f"{jax.default_backend()!r}"
        )
    return path


def pack_plan(dev, n_shards: int):
    """Static bit widths of the fused fill key, or None when the fused
    path is ineligible (x64 off, or widths overflow the 62-bit budget).
    Mirrors `kernel._pack_fill_keys`'s gate exactly: same widths, same
    fallback — the blocked/pallas paths only engage where the lax path
    would have packed to one int64 too, so their keys are comparable
    bit-for-bit."""
    if not jax.config.jax_enable_x64:
        return None
    n_local = int(dev.node_id_rank.shape[0])
    rank_bits = max(1, (n_local * n_shards - 1).bit_length())
    bits = tuple(
        [max(1, int(b)) for b in dev.order_key_bits] + [rank_bits]
    )
    if sum(bits) > 62 or max(bits) > 31:
        return None
    return bits


def combine_hi_lo(hi, lo):
    """(hi, lo) int32 pair -> the packed int64 fill key."""
    return (hi.astype(jnp.int64) << _HI_SHIFT) | lo.astype(jnp.int64)


def kernel_info(path: str, n_nodes: int | None = None) -> dict:
    """Static kernel-selection facts for bench `extra.kernels` and the
    `scheduler_solve_kernel_info` gauge: the resolved path and the block
    geometry the pallas path would run with."""
    info = {
        "path": path,
        "block_nodes": BLOCK_NODES,
        "interpret": not native_available(),
    }
    if n_nodes:
        nb = min(int(n_nodes), BLOCK_NODES)
        info["blocks"] = max(1, int(n_nodes) // nb)
        info["block_shape"] = [nb]
    return info


# ---------------------------------------------------------------------------
# Fused pass-1 scoring
# ---------------------------------------------------------------------------


def _score_values(
    alloc_rows,
    total_rows,
    taint_rows,
    label_rows,
    node_rank,
    node_gid,
    unsched,
    aff_ok,
    tolerated,
    selector,
    req_fit,
    excl,
    job_ok,
    order_res_idx,
    order_res_resolution,
    bits,
    batch_window,
):
    """One node block's candidate-chain values: (fit0, caps, hi, lo).

    The single scoring body shared VERBATIM by the blocked path (whole
    node axis as one block) and the pallas kernel (per-VMEM-block), so
    the two can never drift; all ops are int/bool and elementwise over
    nodes, so block decomposition is exact. Node-major inputs come as
    lists of same-shape per-column node vectors (`*_rows`); the per-job
    inputs (`tolerated` .. `order_res_resolution`) are read only at
    static positions, so they may be arrays or SMEM refs. Every constant
    is an explicit int32: a weak Python int under x64 becomes an int64
    conversion that Mosaic cannot lower."""
    i32 = np.int32
    ok = (unsched == 0) & (aff_ok != 0) & (job_ok[0] != 0)
    for w, col in enumerate(taint_rows):
        ok = ok & ((col & ~tolerated[w]) == 0)
    for w, col in enumerate(label_rows):
        ok = ok & ((selector[w] & ~col) == 0)
    for k in range(excl.shape[0]):
        ok = ok & (node_gid != excl[k])
    fit0 = ok
    caps = jnp.full(node_rank.shape, BIG_I32, jnp.int32)
    for r, (a, t) in enumerate(zip(alloc_rows, total_rows)):
        req = req_fit[r]
        fit0 = fit0 & (req <= t) & (req <= a)
        caps = jnp.where(
            req > 0, jnp.minimum(caps, a // jnp.maximum(req, i32(1))), caps
        )
    caps = jnp.minimum(jnp.maximum(caps, i32(0)), i32(batch_window))
    hi = jnp.zeros(node_rank.shape, jnp.int32)
    lo = jnp.zeros(node_rank.shape, jnp.int32)
    keys = []
    for k in range(len(bits) - 1):
        ri = order_res_idx[k]
        col = alloc_rows[0]
        for r in range(1, len(alloc_rows)):
            col = jnp.where(ri == i32(r), alloc_rows[r], col)
        keys.append(col // order_res_resolution[k])
    keys.append(node_rank)
    for key, b in zip(keys, bits):
        kc = jnp.minimum(jnp.maximum(key, i32(0)), i32((1 << b) - 1))
        hi = (hi << i32(b)) | (lo >> i32(_HI_SHIFT - b))
        lo = ((lo << i32(b)) & i32(_LO_MASK)) | kc
    return jnp.where(fit0, i32(1), i32(0)), caps, hi, lo


# Plain numpy scalar, not a jnp constant: the pallas kernel body closes
# over it, and traced-array captures are rejected under shard_map.
BIG_I32 = np.int32(2**30)

# Node-major inputs, transposed to [columns, N] so nodes run along lanes.
_NODE_MAJOR = 4  # alloc0, node_total, node_taints, node_labels
_NODE_VECS = 4  # node_rank, node_gid, unsched, aff_ok


def _score_kernel(*refs, bits, batch_window):
    node2d = refs[:_NODE_MAJOR]
    node1d = refs[_NODE_MAJOR:_NODE_MAJOR + _NODE_VECS]
    per_job = refs[_NODE_MAJOR + _NODE_VECS:-4]
    fit_ref, caps_ref, hi_ref, lo_ref = refs[-4:]
    rows = [[ref[c:c + 1, :] for c in range(ref.shape[0])] for ref in node2d]
    outs = _score_values(
        *rows, *(ref[...] for ref in node1d), *per_job, bits, batch_window
    )
    for ref, val in zip((fit_ref, caps_ref, hi_ref, lo_ref), outs):
        ref[...] = val


def _score_inputs(dev, alloc0, j, extra_sel):
    """Host/trace-side gathers shared by the blocked and pallas paths:
    the per-job scalars plus the one [N] gather (affinity words) that is
    cheaper outside the block grid than as an in-kernel word lookup."""
    n_idx = dev.node_gid
    a = dev.job_affinity_group[j]
    safe_a = jnp.clip(a, 0, dev.affinity_allowed.shape[0] - 1)
    aff_bits = dev.affinity_allowed[safe_a]
    aff_ok = (a < 0) | (
        (aff_bits[n_idx // 32] >> (n_idx % 32).astype(jnp.uint32)) & 1
    ).astype(bool)
    selector = dev.job_selector[j]
    if extra_sel is not None:
        selector = selector | extra_sel
    return (
        alloc0,
        dev.node_total,
        dev.node_taints,
        dev.node_labels,
        dev.node_id_rank,
        dev.node_gid,
        dev.node_unschedulable.astype(jnp.int32),
        aff_ok.astype(jnp.int32),
        dev.job_tolerated[j],
        selector,
        dev.job_req_fit[j],
        dev.job_excluded_nodes[j],
        dev.job_possible[j].astype(jnp.int32).reshape(1),
        dev.order_res_idx,
        dev.order_res_resolution,
    )


def fill_score(dev, dist, alloc0, j, path, bits, extra_sel=None):
    """The f0 candidate chain — (fit0 mask, per-node caps, [packed key])
    — computed by the blocked or pallas scoring body. Returns exactly
    what `kernel._pass_segment.f0_chain` returns on the lax path for the
    same inputs; `bits` is the (non-None) `pack_plan`. Books the call's
    block/VMEM footprint into `dist.stats` at trace time."""
    args = _score_inputs(dev, alloc0, j, extra_sel)
    B = int(dev.batch_window)
    if path == "blocked":
        rows = [
            [arr[:, c] for c in range(arr.shape[1])]
            for arr in args[:_NODE_MAJOR]
        ]
        fit0, caps, hi, lo = _score_values(
            *rows, *args[_NODE_MAJOR:], bits, B
        )
    else:
        fit0, caps, hi, lo = _pallas_score(args, bits, B)
        _book_pallas(dist, args)
    return fit0.astype(bool), caps, [combine_hi_lo(hi, lo)]


def _pallas_score(args, bits, batch_window):
    """`_score_values` over BLOCK_NODES-wide node blocks: node-major
    inputs transposed to [columns, N] and node vectors as [1, N] (nodes
    on lanes), per-job inputs in SMEM. Interpreted off-TPU."""
    n = int(args[0].shape[0])
    nb = min(n, BLOCK_NODES)
    node2d = [a.T for a in args[:_NODE_MAJOR]]
    node1d = [a.reshape(1, n) for a in args[_NODE_MAJOR:_NODE_MAJOR + _NODE_VECS]]
    per_job = list(args[_NODE_MAJOR + _NODE_VECS:])

    def lanes(rows):
        # int32 block indices: a Python 0 is an int64 under x64.
        return pl.BlockSpec((rows, nb), lambda i: (np.int32(0), i))

    def smem(a):
        return pl.BlockSpec(
            a.shape, lambda i: (np.int32(0),), memory_space=pltpu.SMEM
        )

    in_specs = [lanes(a.shape[0]) for a in node2d + node1d] + [
        smem(a) for a in per_job
    ]
    kern = functools.partial(
        _score_kernel, bits=bits, batch_window=batch_window
    )
    outs = pl.pallas_call(
        kern,
        grid=(n // nb,),
        in_specs=in_specs,
        out_specs=[lanes(1)] * 4,
        out_shape=[jax.ShapeDtypeStruct((1, n), jnp.int32)] * 4,
        interpret=not native_available(),
    )(*node2d, *node1d, *per_job)
    return [o.reshape(n) for o in outs]


def _book_pallas(dist, args, outs_bytes=0):
    stats = getattr(dist, "stats", None)
    if stats is None or not hasattr(stats, "pallas_calls"):
        return
    n = int(args[0].shape[0])
    nb = min(n, BLOCK_NODES)
    blocks = n // nb
    per_block = 0
    for a in args:
        sz = int(np.prod(a.shape)) if a.ndim else 1
        if a.shape and a.shape[0] == n:
            sz = sz // blocks
        per_block += sz * jnp.dtype(a.dtype).itemsize
    per_block += 4 * nb * 4  # the four int32 output blocks
    stats.pallas_calls += 1
    stats.pallas_blocks += blocks
    stats.pallas_vmem_bytes += per_block + outs_bytes


# ---------------------------------------------------------------------------
# Blocked top-B selection (the fill sort replacement)
# ---------------------------------------------------------------------------


def fill_take(key, B, nbits=63):
    """Indices of the B smallest entries of a packed int64 key, in sort
    order — `jnp.lexsort((key,))[:B]` exactly, including the masked
    (sentinel) tail, via radix threshold selection: a bitwise binary
    search for the B-th smallest value (`nbits` O(N) sweeps), a cumsum
    compaction of the flagged entries (first-index tie order = stable
    sort order, keys below the threshold are unique), and one stable
    sort of the B survivors. ~4x the 65k-node lexsort on CPU, and every
    sweep is a block-decomposable elementwise pass — the same walk the
    native kernel tiles over VMEM. Returns (take, key[take])."""
    n = key.shape[0]
    want = min(int(B), n)
    wanti = jnp.int32(want)

    def bit_step(i, lo):
        mid = lo + (jnp.int64(1) << (nbits - 1 - i))
        cnt = jnp.sum((key < mid).astype(jnp.int32))
        return jnp.where(cnt >= wanti, lo, mid)

    lo = jax.lax.fori_loop(0, nbits, bit_step, jnp.int64(0))
    # The nbits-bit search space misses the sentinel; when fewer than
    # `want` keys are real the threshold must swallow the masked tail.
    cnt = jnp.sum((key <= lo).astype(jnp.int32))
    lo = jnp.where(cnt >= wanti, lo, jnp.int64(_I64_SENTINEL))
    flag = key <= lo
    rank = jnp.cumsum(flag.astype(jnp.int32)) - 1
    keep = flag & (rank < wanti)
    pos = jnp.where(keep, rank, want)
    take0 = jnp.zeros(want, jnp.int32).at[pos].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop"
    )
    kv = key[take0]
    o = lexsort([kv])
    return take0[o], kv[o]


def fill_sort_path(keys, mask, B, path, nbits):
    """`dist._fill_sort` with a kernel-path escape hatch: the blocked
    selection engages only for the fused single-int64 key (where it is
    provably lexsort-exact); everything else — multi-key fallback,
    x64-off — keeps the lax sort. Returns (take, masked_keys_list)."""
    mk = masked_keys(keys, mask)
    if (
        path in ("blocked", "pallas", "native")
        and nbits is not None
        and len(mk) == 1
        and mk[0].dtype == jnp.int64
    ):
        take, _ = fill_take(mk[0], B, nbits)
        return take, mk
    return lexsort(mk)[:B], mk


# ---------------------------------------------------------------------------
# Winner reduction (the hierarchical select exchange)
# ---------------------------------------------------------------------------


def _winner_kernel(rows_ref, out_ref, *, n_rows, width):
    """Fold gathered winner tuples to one lexicographic minimum.

    rows: int32[n_rows * width] in SMEM, row-major (notfound, keys...,
    gid). Scalar compares, first row kept on ties (only possible between
    notfound rows) — first-index order, matching `lex_argmin`."""
    best = [rows_ref[c] for c in range(width)]
    for i in range(1, n_rows):
        cand = [rows_ref[i * width + c] for c in range(width)]
        less = cand[width - 2] < best[width - 2]
        for c in range(width - 3, -1, -1):  # gid column excluded
            less = (cand[c] < best[c]) | ((cand[c] == best[c]) & less)
        best = [jnp.where(less, x, y) for x, y in zip(cand, best)]
    for c in range(width):
        out_ref[c] = best[c]


def winner_reduce(keys, found, gids, dist=None):
    """The host-level winner argmin as a pallas kernel.

    keys: list of int32[H] gathered per-host winner keys; found: bool[H];
    gids: int32[H]. Returns (gid, found) — exactly
    `lex_argmin(keys, found)` + gid pick: the last key is the globally
    unique node rank, so the found-row minimum is unique whatever order
    the reduction takes. Interpreted off-TPU, compiled on TPU."""
    h = int(found.shape[0])
    nf = jnp.where(found, jnp.int32(0), jnp.int32(1))
    sent = jnp.int32(np.iinfo(np.int32).max)
    cols = [nf]
    for k in keys:
        cols.append(jnp.where(found, k.astype(jnp.int32), sent))
    cols.append(gids.astype(jnp.int32))
    width = len(keys) + 2
    rows = jnp.stack(cols, axis=1).reshape(h * width)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kern = functools.partial(_winner_kernel, n_rows=h, width=width)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((width,), jnp.int32),
        in_specs=[smem],
        out_specs=smem,
        interpret=not native_available(),
    )(rows)
    _book_winner(dist, 1 << max(0, (h - 1).bit_length()), len(keys))
    return out[-1], out[0] == 0


def _book_winner(dist, p, n_keys):
    stats = getattr(dist, "stats", None)
    if stats is None or not hasattr(stats, "ring_steps"):
        return
    steps = max(1, int(np.log2(max(p, 2))))
    stats.pallas_calls += 1
    stats.ring_steps += steps
    # Each tree/ring step moves one (notfound, keys, gid) tuple per
    # participating host pair; booked as the DMA payload of the exchange.
    stats.ring_bytes += steps * (n_keys + 2) * 4
    stats.pallas_vmem_bytes += p * (n_keys + 2) * 4


# ---------------------------------------------------------------------------
# Native ICI ring exchange (TPU only)
# ---------------------------------------------------------------------------


def _lex_pick(cand, best, width):
    """Row-wise lexicographic min of two [8, 128] tiles whose lanes
    0..width-1 hold (notfound, keys..., gid); the gid lane is excluded
    from the compare and ties keep `best`."""
    lane = jax.lax.broadcasted_iota(jnp.int32, cand.shape, 1)
    neq = (cand != best) & (lane < width - 1)
    first = jnp.min(
        jnp.where(neq, lane, np.int32(cand.shape[1])), axis=1, keepdims=True
    )
    less = jnp.max(
        jnp.where((lane == first) & (cand < best), np.int32(1), np.int32(0)),
        axis=1,
        keepdims=True,
    )
    return jnp.where(less > 0, cand, best)


def ring_winner_exchange(rows, axis_name, n_devices):
    """One winner tuple per device, reduced around the ICI ring with
    `make_async_remote_copy`: each of the n-1 steps DMAs the running
    minimum to the right neighbour along `axis_name` (other mesh axes
    fixed) — SNIPPETS.md's ring-permute shape applied to a lexicographic
    min instead of a gather. Every step has its own buffer and
    semaphores, so no hop can overwrite one not yet read. Runs on the
    TPU only (`native` kernel path); `winner_reduce` gives the same
    answer everywhere. rows: int32[width] -> the minimum row, on every
    device of the ring."""
    width = int(rows.shape[0])
    steps = n_devices - 1
    tile = jnp.zeros((8, 128), jnp.int32).at[0, :width].set(rows)
    mesh = pltpu.DeviceIdType.MESH

    def kern(in_ref, out_ref, send_ref, recv_ref, send_sem, recv_sem):
        me = jax.lax.axis_index(axis_name)
        n = np.int32(n_devices)
        right = jax.lax.rem(me + np.int32(1), n)
        left = jax.lax.rem(me + n - np.int32(1), n)
        # Both neighbours must be inside the kernel (scratch allocated)
        # before the first DMA lands there.
        barrier = pltpu.get_barrier_semaphore()
        for peer in (left, right):
            pltpu.semaphore_signal(
                barrier, 1, device_id={axis_name: peer}, device_id_type=mesh
            )
        pltpu.semaphore_wait(barrier, 2)
        best = in_ref[...]
        for k in map(np.int32, range(steps)):
            send_ref[k] = best
            copy = pltpu.make_async_remote_copy(
                src_ref=send_ref.at[k],
                dst_ref=recv_ref.at[k],
                send_sem=send_sem.at[k],
                recv_sem=recv_sem.at[k],
                device_id={axis_name: right},
                device_id_type=mesh,
            )
            copy.start()
            copy.wait()
            best = _lex_pick(recv_ref[k], best, width)
        out_ref[...] = best

    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((steps, 8, 128), jnp.int32),
            pltpu.VMEM((steps, 8, 128), jnp.int32),
            pltpu.SemaphoreType.DMA((steps,)),
            pltpu.SemaphoreType.DMA((steps,)),
        ],
        compiler_params=pltpu.CompilerParams(collective_id=0),
    )(tile)
    return out[0, :width]
