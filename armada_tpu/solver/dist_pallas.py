"""Pallas-backed hierarchical dist: the winner exchange as a kernel.

`HierarchicalDist` (dist.py) closes every candidate selection with an
`all_gather` of one winner tuple per host followed by a lex-argmin over
the gathered [hosts, keys] block. This subclass keeps the chip-level ICI
stage verbatim — the per-host winner is still produced by gather+argmin
inside a host, where XLA already fuses it — and replaces the host-level
finish with `ops/pallas_kernels.winner_reduce`: a pallas reduction over
the gathered tuples that runs interpreted (bit-exact, CPU tier-1)
everywhere a TPU isn't attached, and compiles natively on one. On the
`native` path (TPU only) the host-level exchange is instead an ICI ring
of `make_async_remote_copy` steps (`pallas_kernels.ring_winner_exchange`)
with no all_gather at all; `CollectiveStats.ring_steps`/`ring_bytes`
book the exchange's fabric cost either way.

Selection semantics are unchanged by construction: the reduction's last
compare key is the globally unique node id rank, so the found-row
minimum is unique however the reduce associates (tree, ring, or flat
argmin), and not-found rows carry sentinel keys that lose to any real
winner. tests/test_pallas_parity.py pins 2x4 rounds bit-exact against
the single-device solve through this dist.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..ops import pallas_kernels as pk
from ..ops.select import lex_argmin
from .dist import HierarchicalDist


class PallasHierarchicalDist(HierarchicalDist):
    """HierarchicalDist with the host-level winner exchange reduced by
    the pallas kernel, or by the ICI ring kernel when `ring`."""

    def __init__(self, *args, ring: bool = False, **kwargs):
        super().__init__(*args, **kwargs)
        self.ring = ring

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
        import jax

        ckeys = [jax.lax.all_gather(k[lidx], self.chip_axis) for k in keys]
        cfound = jax.lax.all_gather(lfound, self.chip_axis)
        cgid = jax.lax.all_gather(gids[lidx], self.chip_axis)
        hidx, hfound = lex_argmin(ckeys, cfound)
        if self.ring:
            # DCN: this host's winner tuple travels the ring of hosts.
            sent = jnp.int32(2**31 - 1)
            row = jnp.stack(
                [jnp.where(hfound, 0, 1).astype(jnp.int32)]
                + [jnp.where(hfound, k[hidx], sent).astype(jnp.int32)
                   for k in ckeys]
                + [cgid[hidx].astype(jnp.int32)]
            )
            best = pk.ring_winner_exchange(row, self.host_axis, self.n_hosts)
            pk._book_winner(self, self.n_hosts, len(keys))
            wgid, wfound = best[-1], best[0] == 0
            return jnp.where(wfound, wgid, 0).astype(jnp.int32), wfound
        # DCN: one winner tuple per host, reduced by the pallas kernel
        # instead of argmin over the gathered block.
        gkeys = [jax.lax.all_gather(k[hidx], self.host_axis) for k in ckeys]
        gfound = jax.lax.all_gather(hfound, self.host_axis)
        ggid = jax.lax.all_gather(cgid[hidx], self.host_axis)
        wgid, wfound = pk.winner_reduce(gkeys, gfound, ggid, dist=self)
        return jnp.where(wfound, wgid, 0).astype(jnp.int32), wfound
