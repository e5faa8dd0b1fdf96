"""Hot-window compaction for the pass-1 solve.

BENCH_r05 showed the round solve-bound: every pass-1 while-loop
iteration carried the full padded J-job / S-slot axes through its
functional transactions (the gang-attempt rollback, the merged-fill
commit, the per-queue apply conds), so a 50k-job burst paid O(J_padded)
array traffic per loop even though only the per-queue head windows were
ever candidates. The fix is the classic active-frontier move of
round-based schedulers (Gavel, arXiv:2008.09213; packing-constrained
parallel scheduling, arXiv:2004.00518): shrink the per-round decision
set to the live window.

`gather_window` compacts, per queue, the next `Ws` slots at the current
head pointer — plus the members of those slots and every still-active
evicted job (the fair-preemption candidate set) — into a dense window
`DeviceRound` whose job/slot axes are O(Q*Ws) instead of O(J)/O(S).
The UNCHANGED pass-1 machinery (`kernel._pass_segment`: serial gang
attempts, batched fill, merged fill) then runs entirely over the window
axes; `scatter_back` writes the window rows into the full carry at
chunk boundaries (with the full carry's buffers donated, so the
scatter is in place).

Bit-exactness vs the uncompacted kernel, by construction:

  - The kernel's lookahead from a queue's head is bounded: 1 slot in
    serial mode, `batch_window` slots in the fill modes. The window
    chunk stops (the REWINDOW handshake) as soon as any truncated
    queue's in-window remainder drops below that lookahead, so every
    executed iteration sees exactly the slots the full kernel would.
  - Evicted jobs are candidates for fair preemption regardless of
    window membership, so ALL evict_rank >= 0 jobs ride along (deduped
    against window-slot members via `job_slot`); the walk's selection
    is rank-keyed with unique ranks, so extra inert rows cannot change
    the winner.
  - Everything else the pass touches is either queue-/node-/group-axis
    state passed through whole (qalloc, alloc, unfeasible, the
    uniformity and affinity tables) or gathered slot/job rows whose
    values are bitwise those of the full tables. Masked-out window
    lanes (pads, dead rows) never reach a committed value: every
    kernel predicate that admits a lane re-derives validity from the
    gathered fields.

The node axis is untouched — compaction composes with the node-sharded
dist seam (solver/dist.py) exactly because the job/slot axes were never
sharded. (The host-driven chunked driver itself is single-device for
now, same as the round-budget chunking — the tracked
`sharded-round-budget` gap.)
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from .segments import compact

NO_NODE = -1

# Fill values making a dead (index -1) window row inert for every kernel
# predicate: impossible jobs bound nowhere, count-0 slots of no queue.
_JOB_FILLS = {
    "job_req": 0,
    "job_req_fit": 0,
    "job_tolerated": 0,
    "job_selector": 0,
    "job_possible": False,
    "job_queue": -1,
    "job_prio": 0,
    "job_preemptible": False,
    "job_is_running": False,
    "job_node": NO_NODE,
    "job_key_group": -1,
    "job_pc": 0,
    "job_excluded_nodes": -1,
    "job_affinity_group": -1,
    "job_slot": -1,
    "job_bid": 0.0,
}
_SLOT_FILLS = {
    "slot_count": 0,
    "slot_queue": -1,
    "slot_is_running": False,
    "slot_req": 0,
    "slot_key_group": -1,
    "slot_jobs_before": 0,
    "slot_run_len": 0,
    "slot_batchable": False,
    "slot_uni_start": 0,
    "slot_uni_end": 0,
    "slot_price": 0.0,
    "slot_away": False,
}


def _rows(arr, idx, fill):
    """arr[idx] with idx == -1 rows replaced by `fill` (any leading axis)."""
    ok = idx >= 0
    v = jnp.take(arr, jnp.clip(idx, 0, arr.shape[0] - 1), axis=0)
    okb = ok.reshape(ok.shape + (1,) * (v.ndim - 1))
    return jnp.where(okb, v, jnp.asarray(fill, v.dtype))


def window_lookahead(dev) -> int:
    """Slots the pass-1 kernel may read ahead of a queue's head pointer:
    the fill window in the batched modes, one slot in serial mode."""
    if dev.batch_window > 0 and not dev.market_driven:
        return int(dev.batch_window)
    return 1


@partial(jax.jit, static_argnums=(3, 4))
def gather_window(dev, carry, ptr, Ws: int, Ep: int):
    """Compact the live frontier into dense window tensors.

    Returns (dev_w, carry_w, ptr_w, trunc, win_len, sidx, jidx):
      dev_w/carry_w — the window DeviceRound/Carry (slot axis Q*Ws, job
      axis Q*Ws*M + Ep; queue/node/group axes shared with the full
      round); ptr_w — window-local head pointers; trunc[q] — queue q has
      real slots beyond its window; sidx/jidx — the gather indices
      (-1 = dead row), needed by scatter_back.
    """
    Q = dev.queue_slot_end.shape[0]
    S, M = dev.slot_members.shape
    qvec = jnp.arange(Q, dtype=jnp.int32)
    ivec = jnp.arange(Ws, dtype=jnp.int32)

    win_len = jnp.clip(dev.queue_slot_end - ptr, 0, Ws)  # [Q]
    trunc = (ptr + Ws) < dev.queue_slot_end  # [Q]
    sidx = jnp.where(
        ivec[None, :] < win_len[:, None], ptr[:, None] + ivec[None, :], -1
    ).reshape(-1)  # [Q*Ws]

    # Window job axis: the members of every window slot (position-mapped,
    # so slot s_w member m lands at row s_w*M + m), then the out-of-window
    # active evicted jobs (fair-preemption candidates whose slots sit
    # beyond some window or were already consumed).
    mem = _rows(dev.slot_members, sidx, -1)  # [Q*Ws, M] global job ids
    jq = jnp.clip(dev.job_queue, 0, Q - 1)
    s_j = dev.job_slot
    in_win = (
        (dev.job_queue >= 0)
        & (s_j >= 0)
        & (s_j >= ptr[jq])
        & (s_j < ptr[jq] + win_len[jq])
    )
    ev_mask = (carry.evict_rank >= 0) & ~in_win
    # jnp.nonzero(ev_mask, size=Ep, fill_value=-1) without its scatter.
    ev_idx, _ = compact(ev_mask, Ep)
    jidx = jnp.concatenate([mem.reshape(-1), ev_idx])

    pos = jnp.arange(Q * Ws, dtype=jnp.int32)
    members_w = jnp.where(
        mem >= 0,
        pos[:, None] * M + jnp.arange(M, dtype=jnp.int32)[None, :],
        -1,
    )
    dev_w = dataclasses.replace(
        dev,
        slot_members=members_w,
        queue_slot_start=qvec * Ws,
        queue_slot_end=qvec * Ws + win_len,
        **{n: _rows(getattr(dev, n), sidx, f) for n, f in _SLOT_FILLS.items()},
        **{n: _rows(getattr(dev, n), jidx, f) for n, f in _JOB_FILLS.items()},
    )
    carry_w = carry._replace(
        job_node=_rows(carry.job_node, jidx, NO_NODE),
        job_prio=_rows(carry.job_prio, jidx, 0),
        job_evicted=_rows(carry.job_evicted, jidx, False),
        job_scheduled=_rows(carry.job_scheduled, jidx, False),
        evict_rank=_rows(carry.evict_rank, jidx, -1),
        slot_state=_rows(carry.slot_state, sidx, jnp.int8(0)),
    )
    return dev_w, carry_w, qvec * Ws, trunc, win_len, sidx, jidx


@partial(jax.jit, static_argnums=(6,), donate_argnums=(0,))
def scatter_back(carry, carry_w, ptr_w, sidx, jidx, win_base, Ws: int):
    """Write the window rows back into the full carry (whose buffers are
    donated — the scatters update in place) and map the window-local
    pointers back to full-table positions. Queue-/node-/group-axis carry
    state is taken wholesale from the window run (it was never split)."""
    J = carry.job_node.shape[0]
    S = carry.slot_state.shape[0]
    Q = win_base.shape[0]
    jd = jnp.where(jidx >= 0, jidx, J)  # out of range -> dropped
    sd = jnp.where(sidx >= 0, sidx, S)
    new_ptr = win_base + (ptr_w - jnp.arange(Q, dtype=jnp.int32) * Ws)
    merged = carry_w._replace(
        job_node=carry.job_node.at[jd].set(carry_w.job_node, mode="drop"),
        job_prio=carry.job_prio.at[jd].set(carry_w.job_prio, mode="drop"),
        job_evicted=carry.job_evicted.at[jd].set(
            carry_w.job_evicted, mode="drop"
        ),
        job_scheduled=carry.job_scheduled.at[jd].set(
            carry_w.job_scheduled, mode="drop"
        ),
        evict_rank=carry.evict_rank.at[jd].set(carry_w.evict_rank, mode="drop"),
        slot_state=carry.slot_state.at[sd].set(carry_w.slot_state, mode="drop"),
    )
    return merged, new_ptr
