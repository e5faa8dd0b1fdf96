"""Two-level multi-host mesh: ICI within a host, DCN across hosts.

The reference's production scale story is one scheduler seeing the UNION
of nodes partitioned across many clusters
(/root/reference/internal/scheduler/scheduling/scheduling_algo.go:135-147).
The 1D mesh (parallel/mesh.py) reproduces that on one host: every chip is
a cluster, all collectives ride a single fabric. Real v5e pods — and any
multi-slice training stack — have TWO fabrics: fast ICI inside a slice,
slow DCN between hosts. This module makes that structure explicit:

  - a 2D `(hosts, chips)` mesh with the node axis sharded over the
    product (host-major blocks), so each host owns a contiguous band of
    clusters and each chip one cluster;
  - the solve runs through `solver.dist.HierarchicalDist`: per-select
    winner reduction is local lex-argmin per shard, an ICI
    all_gather+argmin within the host, then a DCN-minimal exchange of
    ONE winner tuple per host — O(hosts x num_keys) scalars per select
    over DCN instead of the flat O(hosts x chips x num_keys);
  - binds/evictions stay collective-free at both levels (node ownership
    is a local predicate), so the per-fill-loop DCN bill is exactly the
    select/fill reductions, counted by CollectiveStats and documented in
    docs/architecture.md's DCN cost model.

The same code path serves three deployments, asserted bit-identical to
the single-device solve: a single-process virtual mesh (tests), a
multi-process CPU mesh via jax.distributed (parallel/launcher.py — the
dryrun harness), and a real multi-host TPU pod (the axes map 1:1).
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh

from ..solver.dist import CollectiveStats, HierarchicalDist
from .mesh import make_node_mesh, node_sharded_solve, node_specs, sharded_solve

HOST_AXIS = "hosts"
CHIP_AXIS = "chips"

_NODE_SHARDED_2D = node_specs((HOST_AXIS, CHIP_AXIS))


def make_host_mesh(n_hosts: int, n_chips: int, devices=None) -> Mesh:
    """A 2D (hosts, chips) mesh over the first n_hosts*n_chips devices.

    Device order follows jax.devices(), which on multi-process meshes
    groups each process's local devices together — so the host axis
    coincides with process boundaries and the chip axis stays
    process-local, exactly the fabric the hierarchy assumes."""
    devices = list(devices if devices is not None else jax.devices())
    need = n_hosts * n_chips
    if len(devices) < need:
        raise ValueError(
            f"mesh {n_hosts}x{n_chips} needs {need} devices, "
            f"have {len(devices)}"
        )
    grid = np.asarray(devices[:need]).reshape(n_hosts, n_chips)
    return Mesh(grid, (HOST_AXIS, CHIP_AXIS))


def hierarchical_sharded_solve(mesh: Mesh, kernel_path: str = "lax"):
    """Jitted round solve over a 2D (hosts, chips) mesh through the
    two-level HierarchicalDist seam. Same contract as
    mesh.node_sharded_solve: pad the node axis to a multiple of
    hosts*chips first; outputs are replicated and bit-identical to the
    single-device solve.

    kernel_path "pallas"/"native" swaps in PallasHierarchicalDist
    (solver/dist_pallas.py): the host-level winner exchange runs as the
    pallas kernel ("native": the ICI ring kernel) — bit-exact by
    construction, so the runner stays interchangeable with the lax one
    rung-for-rung."""
    if mesh.devices.ndim != 2 or mesh.axis_names != (HOST_AXIS, CHIP_AXIS):
        raise ValueError(
            f"expected a ({HOST_AXIS}, {CHIP_AXIS}) mesh, got "
            f"{mesh.axis_names} with shape {mesh.devices.shape}"
        )
    n_hosts, n_chips = mesh.devices.shape
    if kernel_path in ("pallas", "native"):
        from ..solver.dist_pallas import PallasHierarchicalDist

        dist = PallasHierarchicalDist(
            HOST_AXIS, CHIP_AXIS, n_hosts, n_chips, stats=CollectiveStats(),
            ring=kernel_path == "native",
        )
    else:
        dist = HierarchicalDist(
            HOST_AXIS, CHIP_AXIS, n_hosts, n_chips, stats=CollectiveStats()
        )
    return sharded_solve(mesh, dist, _NODE_SHARDED_2D)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Parsed mesh request: hosts x chips. hosts == 1 selects the 1D
    single-fabric path (no host axis, no DCN stage)."""

    hosts: int
    chips: int

    def __post_init__(self):
        # Every spelling ("0x4", (2, -1), 0) funnels through here, so
        # a non-positive axis fails with a clear error instead of a
        # confusing empty-mesh failure deep in shard_map construction.
        if self.hosts <= 0 or self.chips <= 0:
            raise ValueError(
                f"mesh spec must be positive, got {self.hosts}x{self.chips}"
            )

    @property
    def n_shards(self) -> int:
        return self.hosts * self.chips


def parse_mesh_spec(spec) -> MeshSpec:
    """Accept the mesh spellings used across the stack: an int (1D chip
    count), an "HxC" string ("2x4"), a (hosts, chips) tuple, a MeshSpec,
    or a jax Mesh (1D or 2D)."""
    if isinstance(spec, MeshSpec):
        return spec
    if isinstance(spec, Mesh):
        if spec.devices.ndim == 1:
            return MeshSpec(1, spec.devices.size)
        if spec.devices.ndim == 2:
            return MeshSpec(*spec.devices.shape)
        raise ValueError(f"unsupported mesh rank {spec.devices.ndim}")
    if isinstance(spec, (tuple, list)) and len(spec) == 2:
        return MeshSpec(int(spec[0]), int(spec[1]))
    if isinstance(spec, str) and "x" in spec.lower():
        hosts, chips = spec.lower().split("x", 1)
        return MeshSpec(int(hosts), int(chips))
    return MeshSpec(1, int(spec))


def resolve_solver(spec, kernel_path: str = "lax"):
    """Mesh spec -> solve runner, end to end: the seam
    services/scheduler.py, sim/simulator.py and bench.py share.

    A jax Mesh passes through as-is; anything else builds a mesh over
    the first hosts*chips jax devices. hosts == 1 uses the 1D
    single-fabric path; hosts > 1 the two-level hierarchy. The returned
    callable carries `.stats`, `.n_shards` and `.mesh_shape`.
    `kernel_path` selects the winner-exchange dist on 2D meshes (see
    hierarchical_sharded_solve); 1D meshes have no host level to swap."""
    if isinstance(spec, Mesh):
        parse_mesh_spec(spec)  # reject rank != 1, 2 at the seam
        if spec.devices.ndim == 2:
            return hierarchical_sharded_solve(spec, kernel_path)
        if spec.axis_names != ("nodes",):
            # ShardDist hard-codes the "nodes" axis; fail here, not as
            # an unbound-axis-name error at first solve.
            raise ValueError(
                f'a 1D solve mesh must name its axis "nodes", got '
                f"{spec.axis_names}"
            )
        return node_sharded_solve(spec)
    ms = parse_mesh_spec(spec)
    devices = jax.devices()
    if len(devices) < ms.n_shards:
        raise RuntimeError(
            f"mesh {ms.hosts}x{ms.chips} requested but only "
            f"{len(devices)} devices"
        )
    if ms.hosts == 1:
        return node_sharded_solve(make_node_mesh(devices[: ms.n_shards]))
    return hierarchical_sharded_solve(
        make_host_mesh(ms.hosts, ms.chips, devices), kernel_path
    )
