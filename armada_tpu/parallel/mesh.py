"""Multi-chip execution: the node axis sharded over a device mesh.

The reference scales by partitioning nodes across Kubernetes clusters, one
executor each, with the scheduler seeing the union
(/root/reference/internal/scheduler/scheduling/scheduling_algo.go:135-147).
The TPU-native analogue: one mesh axis ("nodes") over which every per-node
tensor (allocatable[P, N, R], taint/label bitsets, totals) is sharded, so
each chip owns one cluster's worth of nodes.

Execution model: **shard_map, not whole-program GSPMD.** Every chip runs the
same sequential solve in lockstep on replicated job/queue/slot state; per-node
scans (feasibility, best-fit argmin) cover only the local shard, and the
shard-crossing points are explicit collectives provided by
solver.dist.ShardDist:

  - candidate selection: local lexicographic argmin, then an all_gather of
    the per-shard winners and a mesh-size-wide argmin (O(K) scalars on ICI);
  - single-node column reads: masked local gather + psum;
  - binds/evictions: applied by the owning shard only (no collective).

Letting XLA's sharding partitioner propagate through the jitted while_loop
program instead (the round-1 design) made the sharded compile explode;
shard_map compiles the per-shard program once, like the single-device path.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..solver.dist import CollectiveStats, ShardDist
from ..solver.kernel import solve_impl
from ..solver.kernel_prep import DeviceRound

# Node-axis position per sharded field; the axis entry is filled in with
# the mesh axis name(s) — "nodes" for the 1D mesh, ("hosts", "chips") for
# the two-level mesh (parallel/multihost.py).
_NODE_AXIS_POS = {
    "alloc0": 1,
    "node_total": 0,
    "node_taints": 0,
    "node_labels": 0,
    "node_id_rank": 0,
    "node_unschedulable": 0,
    "node_gid": 0,
}


def node_specs(axis) -> dict:
    """Per-field PartitionSpecs sharding the node axis over `axis` (an
    axis name or tuple of axis names)."""
    ndim = {"alloc0": 3, "node_total": 2, "node_taints": 2, "node_labels": 2}
    out = {}
    for name, pos in _NODE_AXIS_POS.items():
        dims = [None] * ndim.get(name, 1)
        dims[pos] = axis
        out[name] = P(*dims)
    return out


_NODE_SHARDED = node_specs("nodes")


def make_node_mesh(devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.asarray(devices), ("nodes",))


def pad_nodes(dev: DeviceRound, multiple: int) -> DeviceRound:
    """Pad the node axis so it divides the mesh. Padded nodes are inert:
    unschedulable, zero resources, worst id-rank."""
    n = dev.node_total.shape[0]
    pad = (-n) % multiple
    if pad == 0:
        return dev
    total = n + pad

    def pad_axis(arr, axis, fill=0):
        widths = [(0, 0)] * arr.ndim
        widths[axis] = (0, pad)
        return np.pad(np.asarray(arr), widths, constant_values=fill)

    return dataclasses.replace(
        dev,
        alloc0=pad_axis(dev.alloc0, 1),
        node_total=pad_axis(dev.node_total, 0),
        node_taints=pad_axis(dev.node_taints, 0),
        node_labels=pad_axis(dev.node_labels, 0),
        node_id_rank=np.concatenate(
            [np.asarray(dev.node_id_rank), np.arange(n, total, dtype=np.int32)]
        ),
        node_unschedulable=np.concatenate(
            [np.asarray(dev.node_unschedulable), np.ones(pad, dtype=bool)]
        ),
        node_gid=np.arange(total, dtype=np.int32),
        affinity_allowed=_pad_words(dev.affinity_allowed, total),
    )


def _pad_words(aw: np.ndarray, n_nodes: int) -> np.ndarray:
    """Grow the node-bitset word axis to cover n_nodes global ids."""
    aw = np.asarray(aw)
    need = (n_nodes + 31) // 32
    if aw.shape[1] >= need:
        return aw
    return np.pad(aw, [(0, 0), (0, need - aw.shape[1])])


def spec_tree(dev: DeviceRound, specs: dict):
    """A DeviceRound-shaped pytree of PartitionSpecs (meta fields kept).

    Every data leaf (including scalar leaves like global_tokens) gets a
    spec; only the node-major arrays are actually sharded."""
    from ..solver.kernel_prep import _META_FIELDS

    full = {
        f.name: specs.get(f.name, P())
        for f in dataclasses.fields(DeviceRound)
        if f.name not in _META_FIELDS
    }
    return dataclasses.replace(dev, **full)


def place_round(dev: DeviceRound, mesh: Mesh, specs: dict) -> DeviceRound:
    """Place a DeviceRound's arrays onto the mesh so jit does not
    re-layout on every call. make_array_from_callback assembles each
    global array from per-device slices of the host copy, which also
    works when the mesh spans multiple processes (each process holds the
    full host copy and contributes its addressable shards)."""
    from ..observe import ledger as _tledger

    placed = {}
    multiproc = jax.process_count() > 1
    for f in dataclasses.fields(DeviceRound):
        v = getattr(dev, f.name)
        if isinstance(v, (np.ndarray, jax.Array)):
            sharding = NamedSharding(mesh, specs.get(f.name, P()))
            # Transfer ledger (observe/ledger.py): every host array
            # placed onto the mesh is an upload the device-resident
            # round refactor would amortize away.
            _tledger.note_up(v, site="mesh.place")
            if multiproc:
                arr = np.asarray(v)
                placed[f.name] = jax.make_array_from_callback(
                    arr.shape, sharding, lambda idx, a=arr: a[idx]
                )
            else:
                placed[f.name] = jax.device_put(v, sharding)
    return dataclasses.replace(dev, **placed)


def sharded_solve(mesh: Mesh, dist, specs: dict):
    """Jitted round solve with node-sharded inputs over `mesh` through the
    given dist seam. Returns a callable dev -> outputs with `.stats` (the
    dist's trace-time CollectiveStats) and `.mesh_shape` attached. Inputs
    must have the node axis padded to a multiple of the shard count
    (pad_nodes). Outputs are replicated and identical to the
    single-device solve on the same snapshot (tests/test_multichip.py,
    tests/test_multihost.py assert this)."""

    def inner(dev):
        # Trace-time side effect: inner's body runs once per (re)trace,
        # so the stats describe THIS compiled program only.
        if dist.stats is not None:
            dist.stats.begin_trace()
        out = solve_impl(dev, dist=dist)
        # The compaction path count (solver/segments.py) is a span
        # attribute of the single-device driver, not a round output.
        del out["compactions"]
        return out

    def build(dev: DeviceRound):
        return jax.jit(
            jax.shard_map(
                inner, mesh=mesh, in_specs=(spec_tree(dev, specs),),
                out_specs=P(), check_vma=False,
            )
        )

    cache = {}

    def _cache_key(dev):
        # Tree structure alone is not enough: the cache holds
        # AOT-compiled executables, which are shape-specialized (unlike
        # a jit wrapper, which re-specializes internally).
        leaves, treedef = jax.tree_util.tree_flatten(dev)
        return treedef, tuple(
            (getattr(v, "shape", ()), str(getattr(v, "dtype", type(v))))
            for v in leaves
        )

    # The prepare(dev) -> run(dev) pattern (parallel/launcher.py) hands
    # the SAME DeviceRound to both calls; re-placing it would double the
    # host->device work (make_array_from_callback rebuilds every array
    # from the full host copy on multi-process meshes). One-entry memo,
    # keyed by identity WITH a strong ref so the id cannot be reused.
    last_placed = []

    # dist.stats is trace-time state: it describes the most recently
    # COMPILED program, which with >1 cached executable (shape buckets,
    # several pools) is not necessarily the one a given run() executes.
    # Snapshot per cache key at compile time; run.last_stats always
    # names the program that just ran.
    stats_by_key = {}

    def _compiled(dev):
        if last_placed and last_placed[0] is dev:
            placed = last_placed[1]
        else:
            placed = place_round(dev, mesh, specs)
            last_placed[:] = [dev, placed]
        key = _cache_key(dev)
        if key not in cache:
            # AOT (lower + compile, no execution): on a multi-process
            # mesh every EXECUTABLE gets its own gloo communicator whose
            # cross-process rendezvous has a hard ~30s window at first
            # execution — compiling AOT lets callers (parallel/launcher)
            # barrier between compile and execute so all processes enter
            # that window together, however far their multi-minute
            # compile wall clocks drifted apart.
            cache[key] = build(dev).lower(placed).compile()
            if dist.stats is not None:
                stats_by_key[key] = dataclasses.replace(dist.stats)
        run.last_stats = stats_by_key.get(key)
        return cache[key], placed

    def run(dev: DeviceRound):
        fn, placed = _compiled(dev)
        try:
            return fn(placed)
        finally:
            # Keep the placed tree only across a prepare(dev) -> run(dev)
            # pair; retaining it between service cycles would pin a full
            # round's host+device arrays that the caller has dropped.
            last_placed.clear()

    def prepare(dev: DeviceRound):
        """Compile this round's program without executing it (see
        _compiled); the next run(dev) dispatches the cached executable
        immediately."""
        _compiled(dev)

    run.prepare = prepare
    run.stats = dist.stats
    run.last_stats = None
    run.n_shards = dist.n_shards
    run.mesh_shape = tuple(mesh.devices.shape)
    return run


def node_sharded_solve(mesh: Mesh):
    """The 1D path: every device is a standalone shard, all collectives
    are mesh-wide (single-host ICI). See parallel/multihost.py for the
    two-level (hosts, chips) variant."""
    dist = ShardDist("nodes", mesh.devices.size, stats=CollectiveStats())
    return sharded_solve(mesh, dist, _NODE_SHARDED)
