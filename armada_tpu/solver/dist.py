"""Distribution seam for the round kernel: local vs node-sharded execution.

The reference scales by partitioning nodes across Kubernetes clusters with
the scheduler seeing the union (scheduling_algo.go:135-147). The TPU-native
analogue shards the node axis of every per-node tensor over a mesh axis and
runs the *same* sequential solve on every chip in lockstep: each chip scans
only its node shard, and the few points where the solve touches nodes
globally become explicit tiny collectives:

  - candidate selection: per-shard lexicographic argmin, then an all_gather
    of the K per-shard winners (K = mesh size) and a K-wide argmin — the
    cross-chip traffic per select is O(K * num_keys) scalars over ICI;
  - reads of one node's allocatable column: masked local gather + psum;
  - binds/evictions: scatter-updates applied only by the owning shard
    (no collective at all — ownership is a local predicate).

This is deliberately NOT whole-program GSPMD: annotating the inputs of the
jitted while_loop program and letting the partitioner propagate makes the
compile blow up (the round-1 failure). shard_map pins the partitioning
manually, so the per-shard program compiles like the single-device one.

Every kernel entry point takes a `dist` object; `LOCAL` makes all of these
identities, so the single-device program is untouched, and the sharded and
local paths share one code body — parity by construction.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..ops.select import lex_argmin, lexsort, masked_keys
from .segments import no_compaction, scatter_rows


@dataclasses.dataclass
class CollectiveStats:
    """Trace-time accounting of the kernel's cross-shard traffic.

    Every collective in the dist seam notes itself here while the kernel
    BODY is being traced, so the numbers describe the compiled program:
    how many collective call sites it contains and how many scalars each
    one moves per execution, split by fabric level (ICI within a host,
    DCN across hosts). while_loop bodies trace once, so a site inside the
    fill loop executes `num_loops` times at runtime — multiply to get
    totals. A 1D (single-host) mesh books everything as ICI.

    The headline number for the DCN cost model (docs/architecture.md) is
    `per_select_dcn_scalars`: the cross-host traffic of ONE candidate
    selection — one winner tuple per host, O(hosts x num_keys), however
    many chips each host holds.
    """

    n_hosts: int = 1
    n_chips: int = 1
    selects: int = 0  # lex_argmin_nodes sites (candidate selection)
    fills: int = 0  # fill_candidates sites (batched best-fit merge)
    point_ops: int = 0  # take/take_col/take_rows psum-class sites
    ici_scalars: int = 0  # scalars received per shard, all sites, one exec
    dcn_scalars: int = 0
    ici_bytes: int = 0
    dcn_bytes: int = 0
    per_select_dcn_scalars: int = 0
    per_select_ici_scalars: int = 0
    # Pallas kernel accounting (ops/pallas_kernels.py): call sites, node
    # blocks and VMEM-resident bytes of the fused scoring kernel, plus
    # the winner exchange's tree/ring step count and DMA payload bytes —
    # booked at trace time like every other counter here, so the fabric
    # cost model is asserted on CPU interpret runs too.
    pallas_calls: int = 0
    pallas_blocks: int = 0
    pallas_vmem_bytes: int = 0
    ring_steps: int = 0
    ring_bytes: int = 0

    def begin_trace(self) -> None:
        """Zero the per-program accounting. Called at the START of each
        kernel trace (sharded_solve's inner body runs once per trace),
        so after any solve the numbers describe the most recently
        compiled program — not an accumulation over every retrace and
        shape bucket the runner ever compiled."""
        self.selects = self.fills = self.point_ops = 0
        self.ici_scalars = self.dcn_scalars = 0
        self.ici_bytes = self.dcn_bytes = 0
        self.per_select_dcn_scalars = self.per_select_ici_scalars = 0
        self.pallas_calls = self.pallas_blocks = self.pallas_vmem_bytes = 0
        self.ring_steps = self.ring_bytes = 0

    def note(self, level: str, arrays) -> None:
        fanin = self.n_chips if level == "ici" else self.n_hosts
        scalars = bytes_ = 0
        for a in arrays:
            n = fanin * int(getattr(a, "size", 1))
            scalars += n
            bytes_ += n * jnp.dtype(a.dtype).itemsize
        if level == "ici":
            self.ici_scalars += scalars
            self.ici_bytes += bytes_
        else:
            self.dcn_scalars += scalars
            self.dcn_bytes += bytes_

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def _fill_sort(keys, mask, B, path="lax", nbits=None):
    """Indices of the B lexicographically-smallest masked entries (sorted).
    Masked-out entries sort last (shared sentinel keys, ops/select.py).
    A non-lax `path` routes the fused single-int64 key through the
    blocked top-B selection (ops/pallas_kernels.fill_sort_path), which is
    lexsort-exact index-for-index; everything else keeps the lax sort."""
    if path != "lax":
        from ..ops.pallas_kernels import fill_sort_path

        return fill_sort_path(keys, mask, B, path, nbits)
    mk = masked_keys(keys, mask)
    return lexsort(mk)[:B], mk


class LocalDist:
    """Single-device execution: all ops are plain indexing."""

    n_shards = 1
    stats = None

    def num_nodes(self, alloc):
        """Global node count, given the (locally visible) alloc[P, n, R]."""
        return alloc.shape[1] * self.n_shards

    def lex_argmin_nodes(self, keys, mask, gids):
        """Global node id of the lexicographically smallest masked entry.
        The last key must be globally unique among masked entries."""
        idx, found = lex_argmin(keys, mask)
        return jnp.where(found, gids[idx], 0).astype(jnp.int32), found

    def take(self, x, n):
        """x[n] for a global node index n (scalar); x is node-major."""
        return x[n]

    def take_col(self, alloc, n):
        """alloc[:, n] -> [P, R] for a global node index n."""
        return alloc[:, n]

    def take_rows(self, x, nodes):
        """x[nodes] for global node indices [J]; x is node-major.
        Out-of-range indices (e.g. -1) yield zeros/False."""
        ln = x.shape[0]
        ok = (nodes >= 0) & (nodes < ln)
        v = x[jnp.clip(nodes, 0, ln - 1)]
        okb = ok.reshape(ok.shape + (1,) * (v.ndim - 1))
        return jnp.where(okb, v, jnp.zeros_like(v))

    def add_col(self, alloc, n, delta):
        """alloc[:, n] += delta ([P, R]) at a global node index."""
        return alloc.at[:, n].add(delta)

    def add_row_at(self, alloc, row, n, delta):
        """alloc[row, n] += delta ([R]) at a global node index."""
        return alloc.at[row, n].add(delta)

    def segment_to_nodes(self, contrib, nodes, ln):
        """Sum [J, ...] contributions into their (global) nodes -> local
        node-major array. Rows with out-of-range nodes must be zero."""
        return jax.ops.segment_sum(
            contrib, jnp.clip(nodes, 0, ln - 1), num_segments=ln
        )

    def sparse_segment_to_nodes(self, contrib, nodes, ln):
        """segment_to_nodes over a whole job axis of which few rows are
        nonzero: only those rows are scattered (solver/segments.py).
        Returns (sums, int32[2] compaction path count)."""
        live = jnp.any(contrib != 0, axis=tuple(range(1, contrib.ndim)))

        def bounded(idx):
            ok = idx >= 0
            rows = jnp.clip(idx, 0, contrib.shape[0] - 1)
            okb = ok.reshape(ok.shape + (1,) * (contrib.ndim - 1))
            return self.segment_to_nodes(
                jnp.where(okb, contrib[rows], 0), nodes[rows], ln
            )

        return scatter_rows(
            live, bounded, lambda: self.segment_to_nodes(contrib, nodes, ln)
        )

    def fill_candidates(self, keys, mask, caps, gids, B, path="lax", nbits=None):
        """The globally best (lex-smallest-key) <=B candidate nodes, in fill
        order: (caps[B'], gids[B']) with caps 0 for masked-out entries. A
        batch of <=B jobs needs at most B nodes, so B candidates suffice."""
        take, _ = _fill_sort(keys, mask, B, path, nbits)
        return jnp.where(mask[take], caps[take], 0), gids[take]


LOCAL = LocalDist()


class ShardDist:
    """Node-sharded execution inside shard_map over `axis`.

    All per-node arrays seen by the kernel are the local shard; job, queue
    and slot arrays are replicated and every shard computes identical values
    for them (the collectives below are the only cross-shard data flow, and
    they produce shard-invariant results)."""

    def __init__(self, axis: str, n_shards: int, stats: CollectiveStats | None = None):
        self.axis = axis
        self.n_shards = n_shards
        # Trace-time traffic accounting; a 1D mesh is a single host, so
        # every collective books as ICI. note() fan-in follows n_chips.
        self.stats = stats
        if stats is not None:
            stats.n_hosts = 1
            stats.n_chips = n_shards

    def num_nodes(self, alloc):
        return alloc.shape[1] * self.n_shards

    def _offset(self, ln):
        return (jax.lax.axis_index(self.axis) * ln).astype(jnp.int32)

    def _psum(self, v):
        if self.stats is not None:
            self.stats.point_ops += 1
            self.stats.note("ici", [v])
        if v.dtype == jnp.bool_:
            return jax.lax.psum(v.astype(jnp.int32), self.axis) > 0
        return jax.lax.psum(v, self.axis)

    def lex_argmin_nodes(self, keys, mask, gids):
        lidx, lfound = lex_argmin(keys, mask)
        gkeys = [jax.lax.all_gather(k[lidx], self.axis) for k in keys]
        gfound = jax.lax.all_gather(lfound, self.axis)
        ggid = jax.lax.all_gather(gids[lidx], self.axis)
        if self.stats is not None:
            self.stats.selects += 1
            self.stats.note("ici", [k[lidx] for k in keys] + [lfound, lidx])
            if not self.stats.per_select_ici_scalars:
                self.stats.per_select_ici_scalars = self.n_shards * (
                    len(keys) + 2
                )
        widx, wfound = lex_argmin(gkeys, gfound)
        return jnp.where(wfound, ggid[widx], 0).astype(jnp.int32), wfound

    def _owned(self, n, ln):
        local = n - self._offset(ln)
        ok = (local >= 0) & (local < ln)
        return jnp.clip(local, 0, ln - 1), ok

    def take(self, x, n):
        local, ok = self._owned(n, x.shape[0])
        v = jnp.where(ok, x[local], jnp.zeros_like(x[local]))
        return self._psum(v)

    def take_col(self, alloc, n):
        local, ok = self._owned(n, alloc.shape[1])
        v = jnp.where(ok, alloc[:, local], 0)
        return self._psum(v)

    def take_rows(self, x, nodes):
        local, ok = self._owned(nodes, x.shape[0])
        v = x[local]
        okb = ok.reshape(ok.shape + (1,) * (v.ndim - 1))
        return self._psum(jnp.where(okb, v, jnp.zeros_like(v)))

    def add_col(self, alloc, n, delta):
        local, ok = self._owned(n, alloc.shape[1])
        return alloc.at[:, local].add(jnp.where(ok, delta, 0))

    def add_row_at(self, alloc, row, n, delta):
        local, ok = self._owned(n, alloc.shape[1])
        return alloc.at[row, local].add(jnp.where(ok, delta, 0))

    def segment_to_nodes(self, contrib, nodes, ln):
        local, ok = self._owned(nodes, ln)
        okb = ok.reshape(ok.shape + (1,) * (contrib.ndim - 1))
        return jax.ops.segment_sum(
            jnp.where(okb, contrib, jnp.zeros_like(contrib)),
            local,
            num_segments=ln,
        )

    def sparse_segment_to_nodes(self, contrib, nodes, ln):
        """The shard's own segment_to_nodes over the whole job axis."""
        return self.segment_to_nodes(contrib, nodes, ln), no_compaction()

    def fill_candidates(self, keys, mask, caps, gids, B, path="lax", nbits=None):
        """Per-shard top-B by local sort, then an all_gather of the K*B
        shard winners and a small merge sort — the fill analogue of the
        per-select argmin reduction. Results are shard-invariant."""
        take, mk = _fill_sort(keys, mask, B, path, nbits)
        lkeys = [k[take] for k in mk]
        lcaps = jnp.where(mask[take], caps[take], 0)
        lgids = gids[take]
        if self.stats is not None:
            self.stats.fills += 1
            self.stats.note("ici", lkeys + [lcaps, lgids])
        gkeys = [
            jax.lax.all_gather(k, self.axis).reshape(-1) for k in lkeys
        ]
        gcaps = jax.lax.all_gather(lcaps, self.axis).reshape(-1)
        ggids = jax.lax.all_gather(lgids, self.axis).reshape(-1)
        order = lexsort(gkeys)[:B]
        return gcaps[order], ggids[order]


class HierarchicalDist(ShardDist):
    """Two-level node sharding for a 2D `(hosts, chips)` mesh.

    Same seam as ShardDist — every kernel entry point is oblivious to
    which one it got — but each shard-crossing collective is decomposed
    to match the physical fabric of a multi-host TPU pod (or a
    multi-process CPU mesh standing in for one):

      1. local per-shard reduction (no traffic);
      2. all_gather over the **chip** axis + reduction — ICI, stays
         inside one host/slice;
      3. all_gather over the **host** axis of ONE winner tuple per host
         + final reduction — the only DCN traffic, O(hosts x num_keys)
         scalars per select instead of the flat mesh's
         O(hosts x chips x num_keys).

    Bit-exactness: the last key of every lexicographic reduction is
    globally unique among masked entries (node_id_rank / node gid), so
    the reduction has a single well-defined winner no matter how it is
    associated — the two-level argmin and top-B merges produce exactly
    the flat ShardDist's (and therefore LOCAL's) results. psum-class
    point reads combine one owning shard's values with zeros, exact in
    any association. tests/test_multihost.py asserts all of this.

    Binds/evictions stay collective-free at both levels: ownership of a
    global node id is a local predicate (ShardDist._owned), so scatter
    updates never cross ICI or DCN.
    """

    def __init__(
        self,
        host_axis: str,
        chip_axis: str,
        n_hosts: int,
        n_chips: int,
        stats: CollectiveStats | None = None,
    ):
        self.host_axis = host_axis
        self.chip_axis = chip_axis
        self.n_hosts = n_hosts
        self.n_chips = n_chips
        self.n_shards = n_hosts * n_chips
        self.stats = stats
        if stats is not None:
            stats.n_hosts = n_hosts
            stats.n_chips = n_chips

    def _offset(self, ln):
        # Node blocks are host-major: PartitionSpec (hosts, chips) splits
        # the global node axis into hosts*chips blocks with block index
        # host*chips + chip.
        shard = jax.lax.axis_index(self.host_axis) * self.n_chips + (
            jax.lax.axis_index(self.chip_axis)
        )
        return (shard * ln).astype(jnp.int32)

    def _psum(self, v):
        # ICI partial sums first, then one partial per host over DCN.
        # Exact for the kernel's point reads: only the owning shard
        # contributes non-zeros.
        if self.stats is not None:
            self.stats.point_ops += 1
            self.stats.note("ici", [v])
            self.stats.note("dcn", [v])
        as_bool = v.dtype == jnp.bool_
        if as_bool:
            v = v.astype(jnp.int32)
        per_host = jax.lax.psum(v, self.chip_axis)
        total = jax.lax.psum(per_host, self.host_axis)
        return total > 0 if as_bool else total

    def lex_argmin_nodes(self, keys, mask, gids):
        lidx, lfound = lex_argmin(keys, mask)
        if self.stats is not None:
            self.stats.selects += 1
            self.stats.note("ici", [k[lidx] for k in keys] + [lfound, lidx])
            self.stats.note("dcn", [k[lidx] for k in keys] + [lfound, lidx])
            if not self.stats.per_select_dcn_scalars:
                self.stats.per_select_dcn_scalars = self.n_hosts * (
                    len(keys) + 2
                )
                self.stats.per_select_ici_scalars = self.n_chips * (
                    len(keys) + 2
                )
        # ICI: the chips' winners, reduced to one winner per host.
        ckeys = [jax.lax.all_gather(k[lidx], self.chip_axis) for k in keys]
        cfound = jax.lax.all_gather(lfound, self.chip_axis)
        cgid = jax.lax.all_gather(gids[lidx], self.chip_axis)
        hidx, hfound = lex_argmin(ckeys, cfound)
        # DCN: one winner tuple per host.
        gkeys = [jax.lax.all_gather(k[hidx], self.host_axis) for k in ckeys]
        gfound = jax.lax.all_gather(hfound, self.host_axis)
        ggid = jax.lax.all_gather(cgid[hidx], self.host_axis)
        widx, wfound = lex_argmin(gkeys, gfound)
        return jnp.where(wfound, ggid[widx], 0).astype(jnp.int32), wfound

    def fill_candidates(self, keys, mask, caps, gids, B, path="lax", nbits=None):
        """Hierarchical top-B merge: chips' top-Bs -> host top-B over ICI,
        hosts' top-Bs -> global top-B over DCN. The global top-B is a
        subset of the union of per-host top-Bs, so the two-level merge is
        exact; entry keys end in the globally-unique node id rank, so the
        merged ORDER matches the flat sort too."""
        take, mk = _fill_sort(keys, mask, B, path, nbits)
        lkeys = [k[take] for k in mk]
        lcaps = jnp.where(mask[take], caps[take], 0)
        lgids = gids[take]
        if self.stats is not None:
            self.stats.fills += 1
            self.stats.note("ici", lkeys + [lcaps, lgids])
            self.stats.note("dcn", lkeys + [lcaps, lgids])
        ckeys = [
            jax.lax.all_gather(k, self.chip_axis).reshape(-1) for k in lkeys
        ]
        ccaps = jax.lax.all_gather(lcaps, self.chip_axis).reshape(-1)
        cgids = jax.lax.all_gather(lgids, self.chip_axis).reshape(-1)
        horder = lexsort(ckeys)[:B]
        hkeys = [k[horder] for k in ckeys]
        gkeys = [
            jax.lax.all_gather(k, self.host_axis).reshape(-1) for k in hkeys
        ]
        gcaps = jax.lax.all_gather(ccaps[horder], self.host_axis).reshape(-1)
        ggids = jax.lax.all_gather(cgids[horder], self.host_axis).reshape(-1)
        order = lexsort(gkeys)[:B]
        return gcaps[order], ggids[order]
