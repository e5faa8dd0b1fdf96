"""The vectorized, jit-compiled scheduling round.

One `solve_round` call runs the entire preempt-and-schedule round on device
as a single XLA program, mirroring the oracle in reference.py (and therefore
the Go reference's PreemptingQueueScheduler):

  fair shares -> balance eviction -> fairness-order indexing ->
  pass 1 (evicted + queued) -> oversubscription eviction -> pass 2 ->
  finalize.

Vectorization strategy (the TPU-first re-design of the reference's
memdb/iterator machinery):
  - Feasibility is bit arithmetic + integer compares over all N nodes at
    once; candidate choice is a masked lexicographic argmin (ops/select.py)
    instead of a radix-tree walk (nodedb.go:754).
  - The queue priority queue becomes a masked argmin over per-queue cost
    keys; per-queue streams are precomputed slot tables with head selection
    by segment-min (queue_scheduler.go:628-674).
  - Fair preemption's sequential walk over evicted jobs (nodedb.go:808)
    becomes a per-node prefix-sum over eviction ranks: a node is selectable
    at the walk step where its cumulative evicted resources first cover the
    job, and the chosen node is the one with the largest such rank.
  - The gang loop is a lax.while_loop whose carry is the entire mutable
    round state; gang atomicity is functional (failed attempts keep the old
    carry, no undo log needed).

Parity notes: with JAX x64 enabled (tests), cost arithmetic is float64 and
aggregate accounting is exact for realistic magnitudes; on TPU (x64 off)
costs are float32 and parity becomes approximate in exotic tie cases.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.config import HOT_WINDOW_MIN_SLOTS_DEFAULT
from ..core.priorities import EVICTED_PRIORITY, MIN_PRIORITY
from ..ops.bitset import bits_subset
from ..ops.select import lex_argmin, lexsort, masked_lexsort
from ..utils import tracing
from .dist import LOCAL
from .kernel_prep import DeviceRound, _pow2
from .segments import (
    no_compaction, scatter_rows, segment_min_dense, segment_sum_dense,
)

# Segment-counter indices: pass-1 loop kinds for the solve profile
# (serial gang attempts, single-queue batched fill, merged multi-queue
# fill). A [3]-int32 rides the while-loop state next to the carry.
SEG_GANG, SEG_FILL, SEG_MERGED = 0, 1, 2

NO_NODE = -1

# slot_state values
PENDING, DONE, FAILED = 0, 1, 2

# failure codes from a gang attempt
OK, FAIL, FAIL_TERMINAL, FAIL_QUEUE_TERMINAL, FAIL_GANG_PROPERTY = 0, 1, 2, 3, 4

BIG = jnp.int32(2**30)


class Carry(NamedTuple):
    alloc: jax.Array  # int32[P, N, R]
    qalloc: jax.Array  # float[Q, R]
    qpc_alloc: jax.Array  # float[Q, C, R]
    job_node: jax.Array  # int32[J]
    job_prio: jax.Array  # int32[J]
    job_evicted: jax.Array  # bool[J]
    job_scheduled: jax.Array  # bool[J] newly scheduled queued jobs
    slot_state: jax.Array  # int8[S]
    evict_rank: jax.Array  # int32[J]; -1 inactive, -2 consumed
    unfeasible: jax.Array  # bool[Gk]
    only_ev_global: jax.Array  # bool
    only_ev_queue: jax.Array  # bool[Q]
    tokens: jax.Array  # float
    qtokens: jax.Array  # float[Q]
    scheduled_new: jax.Array  # float[R]
    floating: jax.Array  # float[R] pool floating-resource allocation
    # Market mode: cumulative gang cost until the spot price is set.
    spot_cost: jax.Array  # float[R]
    spot_price: jax.Array  # float scalar (nan until set)
    stop: jax.Array  # bool
    loops: jax.Array  # int32


def _f(x):
    return jnp.asarray(x, jnp.result_type(float))


def _exact_cumsum(x, axis):
    """Prefix sums of integer-valued float64 request totals. Exact in
    any association (every partial sum is an integer below 2**53), so a
    log-depth scan equals `jnp.cumsum` bit for bit — and XLA:TPU compiles
    it in seconds, where its float64 cumsum (an emulated 64-bit
    reduce-window) took 300 s at 20k entries."""
    return jax.lax.associative_scan(jnp.add, x, axis=axis)


def _pack_fill_keys(dev, dist, n_local, keys):
    """Fuse the best-fit candidate keys into ONE packed int64 when their
    static bit widths fit — the fill sort then runs a single-key sort
    instead of K+1 stable passes (the dominant cost of a big-N fill
    loop; 2x measured on 65k nodes).

    Order-exact by mixed-radix packing: every in-mask key is within
    [0, 2^bits) — a fitting node's allocatable is within [0, node
    total] on every resource (requests are non-negative), and the id
    rank is below the padded global node count — so packed comparison
    equals lexicographic comparison. Masked-out entries may clip, but
    the fill sort replaces them with sentinels anyway. Falls back to
    the multi-key path when the widths overflow 62 bits or x64 is off
    (TPU: no int64 lanes)."""
    if not jax.config.jax_enable_x64:
        return keys
    rank_bits = max(1, (n_local * dist.n_shards - 1).bit_length())
    bits = [max(1, int(b)) for b in dev.order_key_bits] + [rank_bits]
    if len(bits) != len(keys) or sum(bits) > 62:
        return keys
    acc = jnp.zeros(keys[0].shape, jnp.int64)
    for k, b in zip(keys, bits):
        acc = (acc << b) | jnp.clip(k, 0, (1 << b) - 1).astype(jnp.int64)
    return [acc]


def _drf_cost(alloc, total, mult):
    """DRF cost (fairness.go:103-105); alloc [..., R]."""
    safe = jnp.where(total > 0, total, 1.0)
    frac = jnp.where(total > 0, alloc / safe, 0.0) * mult
    return jnp.maximum(jnp.max(frac, axis=-1), 0.0)


def _fair_shares(weights, demand_costs, total_is_zero):
    """Water-filling fair shares (context/scheduling.go:252-331), jit form."""
    Q = weights.shape[0]
    # Zero total weight (every queue cordoned to weight 0) must yield
    # zero shares, not 0/0 NaNs — mirrors drf.update_fair_shares and
    # keeps the round admission firewall's nan_inf invariant clean.
    wsum = jnp.sum(weights)
    fair_share = jnp.where(
        wsum > 0.0, weights / jnp.where(wsum > 0.0, wsum, 1.0), 0.0
    )
    demand = jnp.where(total_is_zero, 1.0, demand_costs)

    def body(state):
        capped, uncapped, achieved, spare, unallocated, i = state
        total_weight = jnp.sum(jnp.where(achieved, 0.0, weights))
        total_incl = total_weight + jnp.where(achieved, weights, 0.0)
        share = jnp.where(total_incl > 0, weights / jnp.where(total_incl > 0, total_incl, 1.0), 0.0)
        uncapped = uncapped + share * (unallocated - spare)
        live = total_weight > 0.0
        capped = jnp.where(
            live & ~achieved,
            capped + (weights / jnp.where(live, total_weight, 1.0)) * unallocated,
            capped,
        )
        new_spare = capped - demand
        over = live & (new_spare > 0)
        capped = jnp.where(over, demand, capped)
        achieved = achieved | over
        spare = jnp.where(over, new_spare, 0.0)
        unallocated = jnp.where(live, jnp.sum(jnp.where(over, new_spare, 0.0)), 0.0)
        return capped, uncapped, achieved, spare, unallocated, i + 1

    def cond(state):
        *_, unallocated, i = state
        return (i < 10) & (unallocated > 0.01)

    init = (
        jnp.zeros(Q),
        jnp.zeros(Q),
        jnp.zeros(Q, dtype=bool),
        jnp.zeros(Q),
        jnp.asarray(1.0, jnp.result_type(float)),
        jnp.asarray(0, jnp.int32),
    )
    capped, uncapped, *_ = jax.lax.while_loop(cond, body, init)
    return fair_share, capped, uncapped


# ---------------------------------------------------------------------------
# Pluggable fairness policies (solver/policy.py holds the host mirrors).
# dev.fairness_policy is STATIC meta — each helper is a Python branch, so
# every policy gets its own jit specialization and the DRF branch emits
# literally the pre-policy graph (bit-exactness with recorded traces by
# construction). Keep these bit-matching with policy.py's numpy forms.
# ---------------------------------------------------------------------------


def _policy_cost(dev, alloc):
    """The queue-cost measure candidate ordering runs on: DRF's dominant
    resource, or the SUM of resource fractions under proportional
    fairness. Monotone in the allocation either way (the fill paths'
    closed-form key streams rely on that)."""
    if dev.fairness_policy[0] == "proportional":
        total = dev.total_resources
        safe = jnp.where(total > 0, total, 1.0)
        frac = jnp.where(total > 0, alloc / safe, 0.0) * dev.drf_multipliers
        return jnp.maximum(jnp.sum(frac, axis=-1), 0.0)
    return _drf_cost(alloc, dev.total_resources, dev.drf_multipliers)


def _deadline_factors(dev, boost, horizon):
    """Elementwise IEEE ops only — mirrors policy.deadline_factors
    bit-for-bit (min is rounding-free, the rest is elementwise)."""
    dl = _f(dev.queue_deadline)
    fin = jnp.isfinite(dl)
    dmin = jnp.min(jnp.where(fin, dl, jnp.inf))
    rel = jnp.maximum(dl - jnp.where(jnp.any(fin), dmin, 0.0), 0.0)
    factor = 1.0 + boost / (1.0 + rel / horizon)
    return jnp.where(fin, factor, 1.0)


def _policy_fair_shares(dev, demand_costs, total_is_zero):
    """Entitlement under the round's policy — the ``_fair_shares`` seat
    in ``_round_setup``. Returns (fair_share, capped, uncapped)."""
    kind = dev.fairness_policy[0]
    w = _f(dev.queue_weight)
    if kind == "deadline":
        boost, horizon = dev.fairness_policy[1], dev.fairness_policy[2]
        return _fair_shares(
            w * _deadline_factors(dev, boost, horizon),
            demand_costs,
            total_is_zero,
        )
    if kind == "priority":
        Q = w.shape[0]
        wsum = jnp.sum(w)
        fair_share = jnp.where(
            wsum > 0.0, w / jnp.where(wsum > 0.0, wsum, 1.0), 0.0
        )
        demand = jnp.where(total_is_zero, 1.0, demand_costs)
        # Serve whole demands in descending-weight order (name-rank
        # tiebreak); sequential single-accumulator loop matches the
        # host mirror's float association exactly.
        order = lexsort([-w, dev.queue_name_rank])

        def body(i, state):
            capped, uncapped, cum_prev = state
            qi = order[i]
            live = w[qi] > 0.0
            unc = jnp.clip(1.0 - cum_prev, 0.0, 1.0)
            capped = capped.at[qi].set(
                jnp.where(live, jnp.minimum(demand[qi], unc), 0.0)
            )
            uncapped = uncapped.at[qi].set(jnp.where(live, unc, 0.0))
            cum_prev = cum_prev + jnp.where(live, demand[qi], 0.0)
            return capped, uncapped, cum_prev

        capped, uncapped, _ = jax.lax.fori_loop(
            0,
            Q,
            body,
            (
                jnp.zeros(Q, w.dtype),
                jnp.zeros(Q, w.dtype),
                jnp.zeros((), w.dtype),
            ),
        )
        return fair_share, capped, uncapped
    return _fair_shares(w, demand_costs, total_is_zero)


def _policy_rank_key(dev):
    """Optional leading candidate/preemption lex key (smaller wins):
    None for drf/proportional — their key lists stay structurally
    identical to the pre-policy kernel."""
    kind = dev.fairness_policy[0]
    if kind == "priority":
        return -_f(dev.queue_weight)
    if kind == "deadline":
        return _f(dev.queue_deadline)
    return None


def _static_ok(dev, j, extra_sel, extra_tol=None):
    """StaticJobRequirementsMet over all nodes (nodematching.go:161-190).
    extra_sel: additional required label bits (gang uniformity value);
    extra_tol: additional tolerated-taint bits (away node types)."""
    tolerated = dev.job_tolerated[j]
    if extra_tol is not None:
        tolerated = tolerated | extra_tol
    taints_ok = jnp.all((dev.node_taints & ~tolerated) == 0, axis=-1)
    sel_ok = bits_subset(dev.job_selector[j] | extra_sel, dev.node_labels)
    total_ok = jnp.all(dev.job_req_fit[j] <= dev.node_total, axis=-1)
    # Retry anti-affinity: nodes earlier attempts failed on are infeasible.
    # node_gid carries global node ids (equals arange(N) locally; the owning
    # shard's slice of it under node sharding).
    n_idx = dev.node_gid
    excl_ok = jnp.all(
        n_idx[:, None] != dev.job_excluded_nodes[j][None, :], axis=-1
    )
    # Node affinity: one precomputed allowed-node bit per (group, node).
    a = dev.job_affinity_group[j]
    safe_a = jnp.clip(a, 0, dev.affinity_allowed.shape[0] - 1)
    aff_bits = dev.affinity_allowed[safe_a]
    aff_ok = (a < 0) | (
        (aff_bits[n_idx // 32] >> (n_idx % 32).astype(jnp.uint32)) & 1
    ).astype(bool)
    return (
        taints_ok
        & sel_ok
        & total_ok
        & excl_ok
        & aff_ok
        & ~dev.node_unschedulable
        & dev.job_possible[j]
    )


def _select_at_row(dev, dist, alloc, j, row, static_ok):
    """First-fit in best-fit order at one priority row (nodedb.go:713-752)."""
    dyn = jnp.all(dev.job_req_fit[j] <= alloc[row], axis=-1)
    mask = static_ok & dyn
    keys = []
    for k in range(dev.order_res_idx.shape[0]):
        ri = dev.order_res_idx[k]
        res = dev.order_res_resolution[k]
        keys.append(alloc[row, :, ri] // res)
    keys.append(dev.node_id_rank)
    return dist.lex_argmin_nodes(keys, mask, dev.node_gid)


def fair_preemption_order(carry):
    """Precompute the (node, -rank) walk order once per pass: ranks are
    fixed at assignment; only the active mask changes as evicted jobs are
    consumed or rescheduled, which the per-select mask handles. Inactive
    rows sort last via the shared sentinel keys (ops/select.py); their
    relative order is irrelevant — the walk zeroes their contributions
    and the selection mask excludes them."""
    rank = carry.evict_rank
    active = rank >= 0
    return masked_lexsort([carry.job_node, BIG - rank], active)


def _fair_preemption(dev, dist, carry, j, static_ok, fp_order):
    """Vectorized selectNodeForJobWithFairPreemption (nodedb.go:808-899).

    Walk evicted jobs in reverse rank order; node n becomes selectable at the
    first step where its cumulative freed resources cover the job. Choose the
    node whose threshold step is earliest (largest rank)."""
    rank = carry.evict_rank
    active = rank >= 0
    node = carry.job_node
    order = fp_order
    n_sorted = node[order]
    a_sorted = active[order]
    contrib = jnp.where(a_sorted[:, None], dev.job_req_fit[order], 0)
    pos = jnp.arange(node.shape[0])
    is_first = jnp.concatenate(
        [jnp.ones(1, bool), n_sorted[1:] != n_sorted[:-1]]
    )
    # Per-node (segmented) int32 prefix sums. Exact: the jobs bound to
    # one node never request more than its int32 capacity. A global
    # int64 cumsum here is a 64-bit reduce-window that XLA:TPU refuses
    # (scoped VMEM exhausted) at any width.
    def seg_add(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb[:, None], vb, va + vb)

    _, cwithin = jax.lax.associative_scan(seg_add, (is_first, contrib))
    cwithin = cwithin.astype(jnp.result_type(int))
    safe_node = jnp.clip(n_sorted, 0, dist.num_nodes(carry.alloc) - 1)
    avail = (
        dist.take_rows(carry.alloc[0], safe_node).astype(jnp.result_type(int))
        + cwithin
    )
    feasible = (
        a_sorted
        & jnp.all(avail >= dev.job_req_fit[j], axis=-1)
        & dist.take_rows(static_ok, safe_node)
    )
    rank_sorted = rank[order]
    idx, found = lex_argmin([-rank_sorted, pos.astype(jnp.int32)], feasible)
    sel_node = safe_node[idx]
    sel_rank = rank_sorted[idx]
    consumed = active & (node == sel_node) & (rank >= sel_rank) & found
    freed = jnp.sum(
        jnp.where(consumed[:, None], dev.job_req_fit, 0), axis=0
    ).astype(carry.alloc.dtype)
    new_alloc = dist.add_row_at(
        carry.alloc, 0, sel_node, jnp.where(found, freed, 0)
    )
    new_rank = jnp.where(consumed, -2, rank)
    preempted_at = jnp.max(
        jnp.where(consumed, carry.job_prio, MIN_PRIORITY)
    )
    return sel_node, found, preempted_at, new_alloc, new_rank


def _select_chain(dev, dist, carry, j, prio, extra_sel, extra_tol, fp_order):
    """selectNodeForJobWithTxnAtPriority (nodedb.go:597-662) at one target
    priority with optional extra tolerations (away node types). Returns
    (node, found, preempted_at, new_alloc, new_evict_rank)."""
    alloc = carry.alloc
    row_p = jnp.searchsorted(dev.priorities, prio).astype(jnp.int32)
    static_ok = _static_ok(dev, j, extra_sel, extra_tol)

    n0, f0 = _select_at_row(dev, dist, alloc, j, 0, static_ok)
    np_, fp = _select_at_row(dev, dist, alloc, j, row_p, static_ok)

    # Fair preemption involves a J-sized sort; skip it when the evicted-job
    # index is empty (every queued-only round).
    fpre_n, fpre_found, fpre_at, fpre_alloc, fpre_rank = jax.lax.cond(
        jnp.any(carry.evict_rank >= 0),
        lambda: _fair_preemption(dev, dist, carry, j, static_ok, fp_order),
        lambda: (
            jnp.int32(0),
            jnp.zeros((), bool),
            jnp.int32(MIN_PRIORITY),
            carry.alloc,
            carry.evict_rank,
        ),
    )

    # Urgency: lowest priority row (ascending) where the job fits.
    urg_n = jnp.int32(0)
    urg_found = jnp.zeros((), bool)
    urg_at = jnp.int32(MIN_PRIORITY)
    P = dev.priorities.shape[0]
    for r in range(1, P):
        allowed = dev.priorities[r] <= prio
        nr, fr = _select_at_row(dev, dist, alloc, j, r, static_ok)
        take = allowed & fr & ~urg_found
        urg_n = jnp.where(take, nr, urg_n)
        urg_at = jnp.where(take, dev.priorities[r], urg_at)
        urg_found = urg_found | take

    found = f0 | (fp & (fpre_found | urg_found))
    use_fpre = ~f0 & fp & fpre_found
    node = jnp.where(f0, n0, jnp.where(use_fpre, fpre_n, urg_n))
    preempted_at = jnp.where(
        f0, EVICTED_PRIORITY, jnp.where(use_fpre, fpre_at, urg_at)
    )
    new_alloc = jnp.where(use_fpre, fpre_alloc, carry.alloc)
    new_rank = jnp.where(use_fpre, fpre_rank, carry.evict_rank)
    return node, found, preempted_at, new_alloc, new_rank


def _select_node(dev, dist, carry, j, extra_sel, fp_order):
    """SelectNodeForJobWithTxn (nodedb.go:423-503): pinned reschedule, home
    chain, then away node types at reduced priority. Returns
    (node, found, preempted_at, new_alloc, new_evict_rank, sched_at)."""
    prio = carry.job_prio[j]
    row_p = jnp.searchsorted(dev.priorities, prio).astype(jnp.int32)
    alloc = carry.alloc

    pinned = carry.job_evicted[j]
    home = carry.job_node[j]
    safe_home = jnp.clip(home, 0, dist.num_nodes(alloc) - 1)
    home_col = dist.take_col(alloc, safe_home)
    over_alloc = jnp.any(home_col < 0)
    home_fit = jnp.all(dev.job_req_fit[j] <= home_col[row_p]) | (
        dist.take(dev.node_unschedulable, safe_home) & over_alloc
    )

    node, found, preempted_at, new_alloc, new_rank = _select_chain(
        dev, dist, carry, j, prio, extra_sel, None, fp_order
    )
    sched_at = prio

    if dev.has_away:
        # Away node types (nodedb.go:487-501): extra tolerations for the
        # well-known taints, the whole chain at the away priority, bound at
        # that priority so home jobs can urgency-preempt later. Gated behind
        # lax.cond so the (expensive) away chains only execute for jobs that
        # actually failed home scheduling.
        pc = dev.job_pc[j]
        Amax = dev.pc_away_prio.shape[1]

        def try_away(args):
            node, found, preempted_at, new_alloc, new_rank, sched_at = args
            for a in range(Amax):
                live = (a < dev.pc_away_count[pc]) & ~found
                a_prio = dev.pc_away_prio[pc, a]
                a_node, a_found, a_at, a_alloc, a_rank = _select_chain(
                    dev, dist, carry, j, a_prio, extra_sel,
                    dev.pc_away_tol[pc, a], fp_order,
                )
                take = live & a_found
                node = jnp.where(take, a_node, node)
                preempted_at = jnp.where(take, a_at, preempted_at)
                sched_at = jnp.where(take, a_prio, sched_at)
                new_alloc = jnp.where(take, a_alloc, new_alloc)
                new_rank = jnp.where(take, a_rank, new_rank)
                found = found | take
            return node, found, preempted_at, new_alloc, new_rank, sched_at

        state = (node, found, preempted_at, new_alloc, new_rank, sched_at)
        node, found, preempted_at, new_alloc, new_rank, sched_at = jax.lax.cond(
            ~found & ~pinned & (dev.pc_away_count[pc] > 0),
            try_away,
            lambda args: args,
            state,
        )

    # Pinned (evicted) jobs only ever return to their node.
    found = jnp.where(pinned, home_fit, found)
    node = jnp.where(pinned, safe_home, node)
    preempted_at = jnp.where(pinned, prio, preempted_at)
    sched_at = jnp.where(pinned, prio, sched_at)
    new_alloc = jnp.where(pinned, carry.alloc, new_alloc)
    new_rank = jnp.where(pinned, carry.evict_rank, new_rank)
    return node, found, preempted_at, new_alloc, new_rank, sched_at


def _bind(dev, dist, carry: Carry, j, n, at_prio) -> Carry:
    """bindJobToNodeInPlace (nodedb.go:911-945)."""
    preemptible = dev.job_preemptible[j]
    rows = jnp.where(
        preemptible, dev.priorities <= at_prio, jnp.ones_like(dev.priorities, bool)
    )
    delta = jnp.where(rows[:, None], dev.job_req_fit[j], 0).astype(carry.alloc.dtype)
    alloc = dist.add_col(carry.alloc, n, -delta)
    was_evicted = carry.job_evicted[j]
    alloc = dist.add_row_at(
        alloc,
        0,
        n,
        jnp.where(was_evicted, dev.job_req_fit[j], 0).astype(carry.alloc.dtype),
    )
    return carry._replace(
        alloc=alloc,
        job_node=carry.job_node.at[j].set(n),
        job_prio=carry.job_prio.at[j].set(at_prio),
        job_evicted=carry.job_evicted.at[j].set(False),
        job_scheduled=carry.job_scheduled.at[j].set(
            carry.job_scheduled[j] | (~was_evicted & ~dev.job_is_running[j])
        ),
        evict_rank=carry.evict_rank.at[j].set(
            jnp.where(was_evicted, -2, carry.evict_rank[j])
        ),
    )


def _constraint_code(dev, carry, s, all_ev):
    """Round/queue/rate-limit gates for one gang attempt
    (gang_scheduler.go:100-145). Returns an OK/FAIL* code."""
    q = dev.slot_queue[s]
    card = dev.slot_count[s].astype(jnp.result_type(float))
    pc = dev.job_pc[dev.slot_members[s, 0]]

    over_round = jnp.any(carry.scheduled_new > dev.max_round_resources)
    no_tokens = carry.tokens < 1
    gang_too_big = dev.global_burst < card
    tokens_short = carry.tokens < card
    qno_tokens = carry.qtokens[q] < 1
    qgang_too_big = dev.queue_burst < card
    qtokens_short = carry.qtokens[q] < card
    # Per-PC cap is would-exceed: CheckJobConstraints runs after
    # AddGangSchedulingContext, so the compared allocation includes the
    # candidate gang (gang_scheduler.go:132-140, constraints.go:121-135).
    pc_over = jnp.any(
        carry.qpc_alloc[q, pc] + _f(dev.slot_req[s]) > dev.queue_pc_limit[q, pc]
    )
    cordoned = dev.queue_cordoned[q]

    blocked_code = jnp.where(
        over_round | no_tokens,
        FAIL_TERMINAL,
        jnp.where(
            qno_tokens | cordoned,
            FAIL_QUEUE_TERMINAL,
            jnp.where(
                gang_too_big,
                FAIL_GANG_PROPERTY,
                jnp.where(
                    tokens_short | qgang_too_big | qtokens_short | pc_over,
                    FAIL,
                    OK,
                ),
            ),
        ),
    )
    blocked_code = jnp.where(all_ev, OK, blocked_code)
    # Floating-resource pool caps apply to every gang, evicted included
    # (IsWithinFloatingResourceLimits, gang_scheduler.go:144) — EXCEPT
    # cross-pool away gangs, whose limits were checked by their home
    # pool's round (context/scheduling.go:546-557).
    floating_over = jnp.any(
        dev.floating_mask
        & (carry.floating + _f(dev.slot_req[s]) > dev.floating_total)
    ) & ~dev.slot_away[s]
    return jnp.where((blocked_code == OK) & floating_over, FAIL, blocked_code)


def _gang_attempt(dev, dist, carry: Carry, s, all_ev, fp_order):
    """GangScheduler.Schedule + ScheduleManyWithTxn. Returns
    (carry, status_code)."""
    q = dev.slot_queue[s]
    card = dev.slot_count[s].astype(jnp.result_type(float))
    pc = dev.job_pc[dev.slot_members[s, 0]]

    blocked_code = _constraint_code(dev, carry, s, all_ev)

    # Member-by-member placement; extra_sel constrains members to one
    # uniformity-label value during the search.
    fdt = jnp.result_type(float)

    def attempt_members(c0, extra_sel, start_ok):
        def member_body(m, state):
            c, ok, pat_sum = state
            j = dev.slot_members[s, m]
            live = (m < dev.slot_count[s]) & ok
            safe_j = jnp.clip(j, 0, dev.job_req.shape[0] - 1)
            node, found, pat, new_alloc, new_rank, sched_at = _select_node(
                dev, dist, c, safe_j, extra_sel, fp_order
            )

            def do_bind(c):
                c2 = c._replace(alloc=new_alloc, evict_rank=new_rank)
                return _bind(dev, dist, c2, safe_j, node, sched_at)

            c = jax.lax.cond(live & found, do_bind, lambda c: c, c)
            pat_sum = pat_sum + jnp.where(live & found, _f(pat), 0.0)
            return c, ok & (found | ~live), pat_sum

        # Dynamic trip count: singleton slots (the common case) pay for one
        # member even when the batch contains wide gangs.
        c1, ok, pat_sum = jax.lax.fori_loop(
            0, dev.slot_count[s], member_body, (c0, start_ok, jnp.zeros((), fdt))
        )
        mean = pat_sum / jnp.maximum(card, 1.0)
        return c1, ok, mean

    # Uniformity key with no node values: unsatisfiable
    # (gang_scheduler.go:171-175), encoded as a (-1,-1) range.
    start_ok = (blocked_code == OK) & (dev.slot_uni_start[s] >= 0)
    has_uni = dev.slot_uni_end[s] > dev.slot_uni_start[s]

    def plain(c):
        c1, ok, _ = attempt_members(c, jnp.zeros_like(dev.uni_value_bits[0]), start_ok)
        return c1, ok

    def uniform(c):
        """Node-uniformity search (gang_scheduler.go:150-224): evaluate each
        label value, keep the successful value with the best fit (lowest
        mean preempted-at priority, first wins ties), then re-attempt and
        commit that value."""

        def eval_body(v, best):
            best_v, best_mean, found_any = best
            _, ok, mean = attempt_members(c, dev.uni_value_bits[v], start_ok)
            better = ok & (~found_any | (mean < best_mean))
            return (
                jnp.where(better, v, best_v),
                jnp.where(better, mean, best_mean),
                found_any | ok,
            )

        best_v, _, found_any = jax.lax.fori_loop(
            dev.slot_uni_start[s],
            dev.slot_uni_end[s],
            eval_body,
            (jnp.int32(0), jnp.asarray(jnp.inf, fdt), jnp.zeros((), bool)),
        )

        def commit(c):
            c1, ok, _ = attempt_members(c, dev.uni_value_bits[best_v], start_ok)
            return c1, ok

        return jax.lax.cond(
            found_any, commit, lambda c: (c, jnp.zeros((), bool)), c
        )

    attempted, ok = jax.lax.cond(has_uni, uniform, plain, carry)

    # Commit or roll back (functional txn).
    new_carry = jax.tree_util.tree_map(
        lambda a, b: jnp.where(ok, b, a), carry, attempted
    )

    # Success accounting (AddGangSchedulingContext + rate-limiter reserve).
    req = _f(dev.slot_req[s])
    qalloc = jnp.where(
        ok, new_carry.qalloc.at[q].add(req), new_carry.qalloc
    )
    qpc_alloc = jnp.where(
        ok, new_carry.qpc_alloc.at[q, pc].add(req), new_carry.qpc_alloc
    )
    tokens = jnp.where(ok & ~all_ev, new_carry.tokens - card, new_carry.tokens)
    qtokens = jnp.where(
        ok & ~all_ev, new_carry.qtokens.at[q].add(-card), new_carry.qtokens
    )
    scheduled_new = jnp.where(
        ok & ~all_ev, new_carry.scheduled_new + req, new_carry.scheduled_new
    )
    floating = jnp.where(
        ok,
        new_carry.floating + jnp.where(dev.floating_mask, req, 0.0),
        new_carry.floating,
    )
    if dev.market_driven:
        unset = jnp.isnan(new_carry.spot_price)
        spot_cost = jnp.where(
            ok & unset, new_carry.spot_cost + req, new_carry.spot_cost
        )
        crossed = (
            _drf_cost(spot_cost, dev.total_resources, dev.drf_multipliers)
            > dev.spot_price_cutoff
        )
        spot_price = jnp.where(
            ok & unset & crossed, dev.slot_price[s], new_carry.spot_price
        )
    else:
        spot_cost = new_carry.spot_cost
        spot_price = new_carry.spot_price
    # Member placement failures are gang-property reasons (JobDoesNotFit /
    # GangDoesNotFit, constraints.go:59-61).
    fail_code = jnp.where(blocked_code != OK, blocked_code, FAIL_GANG_PROPERTY)
    status = jnp.where(ok, OK, fail_code)
    new_carry = new_carry._replace(
        qalloc=qalloc,
        qpc_alloc=qpc_alloc,
        tokens=tokens,
        qtokens=qtokens,
        scheduled_new=scheduled_new,
        floating=floating,
        spot_cost=spot_cost,
        spot_price=spot_price,
        slot_state=new_carry.slot_state.at[s].set(
            jnp.where(ok, DONE, FAILED).astype(jnp.int8)
        ),
    )
    return new_carry, status


def _slot_valid_one(dev, carry: Carry, all_ev_flags, include_queued, use_key_skip, s):
    """Validity of ONE slot (QueuedGangIterator yield semantics). The single
    source of truth for the predicate: the full O(S) scan (_slot_validity)
    vmaps it and the head-pointer advance evaluates it per slot — they
    cannot drift apart."""
    Q = dev.queue_slot_start.shape[0]
    v = (carry.slot_state[s] == PENDING) & (dev.slot_count[s] > 0)
    all_ev = all_ev_flags[s]
    if include_queued:
        only_ev = carry.only_ev_global | carry.only_ev_queue[
            jnp.clip(dev.slot_queue[s], 0, Q - 1)
        ]
        active = jnp.where(dev.slot_is_running[s], all_ev, True)
        v = v & active & (~only_ev | all_ev)
        # Lookback: queued jobs beyond the limit stop yielding; 0 means
        # unlimited (QueuedGangIterator.stopYieldingNewJobsIfLimitHit).
        if dev.max_lookback:
            v = v & (
                dev.slot_is_running[s]
                | all_ev
                | (dev.slot_jobs_before[s] < dev.max_lookback)
            )
        if use_key_skip:
            kg = dev.slot_key_group[s]
            v = v & ~(
                (kg >= 0)
                & carry.unfeasible[jnp.clip(kg, 0, carry.unfeasible.shape[0] - 1)]
            )
    else:
        v = v & all_ev
    return v


def _slot_validity(dev, carry: Carry, include_queued, use_key_skip):
    """Which slots can be yielded right now (QueuedGangIterator semantics)."""
    S, M = dev.slot_members.shape
    members = dev.slot_members  # [S, M]
    member_mask = jnp.arange(M)[None, :] < dev.slot_count[:, None]
    safe = jnp.clip(members, 0, dev.job_req.shape[0] - 1)
    all_evicted = jnp.all(
        jnp.where(member_mask, carry.job_evicted[safe], True), axis=1
    )
    valid = jax.vmap(
        lambda s: _slot_valid_one(
            dev, carry, all_evicted, include_queued, use_key_skip, s
        )
    )(jnp.arange(S, dtype=jnp.int32))
    return valid, all_evicted


def _queue_heads(dev, valid):
    """First valid slot per queue (segment-min over slot positions)."""
    S = valid.shape[0]
    Q = dev.queue_slot_start.shape[0]
    pos = jnp.where(valid, jnp.arange(S, dtype=jnp.int32), BIG)
    seg = jnp.clip(dev.slot_queue, 0, Q - 1)
    heads = segment_min_dense(pos, seg, Q)
    return jnp.where(heads < BIG, heads, 0).astype(jnp.int32), heads < BIG


def _slot_min_prio(dev, carry, s):
    M = dev.slot_members.shape[1]
    members = dev.slot_members[s]
    mask = jnp.arange(M) < dev.slot_count[s]
    safe = jnp.clip(members, 0, dev.job_req.shape[0] - 1)
    return jnp.min(jnp.where(mask, carry.job_prio[safe], jnp.int32(2**31 - 1)))


def _pass_segment(
    dev,
    dist,
    carry: Carry,
    ptr0,
    fs0,
    budgets,
    loop_cap,
    *,
    include_queued: bool,
    use_key_skip: bool,
    consider_priority: bool,
    prefer_large: bool,
    seg0=None,
    window_trunc=None,
):
    """QueueScheduler.Schedule as a while_loop (queue_scheduler.go:91-276).

    Per-queue candidate streams are walked with **head pointers**: slots are
    sorted by (queue, segment, order), so each queue's next candidate is an
    advancing index into its slot range. Steady-state per-iteration work is
    O(Q + nodes) — independent of the total slot count S, which is what a
    1M-queued-job round needs. The O(S) full validity scan runs only at pass
    start and when a validity *flag* flips (an only-evicted marker or a
    newly registered unfeasible key — rare), because those can invalidate
    other queues' heads; everything else that validity depends on is either
    static within the pass (all-evicted membership: evictions happen between
    passes) or behind the pointers already (consumed slots).

    This is one resumable SEGMENT of the pass: it continues from
    (carry, ptr0, fs0) and stops once `carry.loops` reaches `loop_cap`
    (or the pass completes, carry.stop). The round-deadline path runs the
    pass as a sequence of segments with a wall-clock check between them;
    the segment boundary is a while-iteration boundary, where gang
    attempts are complete, so per-chunk recomputation of the all-evicted
    flags and the fair-preemption order is value-identical for every slot
    still PENDING.

    `seg0` (int32[3]) accumulates per-kind loop counts (gang / fill /
    merged-fill) for the solve profile; always returned.

    `window_trunc` (bool[Q]) marks hot-window compaction
    (solver/hotwindow.py): queues whose slot table is a truncated window
    of the real one. The loop then also stops — the REWINDOW handshake —
    as soon as any truncated queue's in-window remainder drops below the
    kernel's head lookahead (the fill window, or 1 slot in serial mode),
    so no iteration ever runs that could have seen slots beyond the
    window; the host re-gathers from the full slot order and resumes."""
    Q = dev.queue_slot_start.shape[0]
    S = dev.slot_members.shape[0]
    # Fill fast path is statically compiled in only for the queued pass of a
    # non-market round (pass 2 and market ordering stay fully serial).
    fill_enabled = (
        dev.batch_window > 0
        and include_queued
        and not dev.market_driven
        and not consider_priority
    )
    fast_fill_enabled = fill_enabled and dev.fast_fill
    loops0 = carry.loops
    lookahead = jnp.int32(dev.batch_window if fill_enabled else 1)

    def cond(state):
        c, ptr, _, _ = state
        # Every iteration either consumes >=1 slot, flips a validity flag,
        # or arms force-serial for the next one: 2S+4 bounds the segment
        # even with fill-miss/serial-retry pairs (relative to the entry
        # count — `loops` accumulates across chunks). loop_cap cuts
        # earlier when a round budget is in force (solve_round's chunked
        # driver).
        go = ~c.stop & (c.loops < loop_cap) & (c.loops - loops0 < 2 * S + 4)
        if window_trunc is not None:
            # Hot-window rewindow handshake: never enter an iteration in
            # which a truncated queue's head lookahead could cross its
            # window end — the full kernel would see real slots there.
            go = go & ~jnp.any(
                window_trunc & ((dev.queue_slot_end - ptr) < lookahead)
            )
        return go

    # all-evicted flags are stable within a pass: evictions happen between
    # passes, and a rescheduled member's slot is the one being consumed.
    _, all_ev_flags = _slot_validity(dev, carry, include_queued, use_key_skip)
    # Fair-preemption walk order: one sort per pass, not per member select.
    fp_order = fair_preemption_order(carry)

    def lazy_valid(c, s):
        """O(1) validity of slot s (shared predicate, see _slot_valid_one)."""
        return _slot_valid_one(
            dev, c, all_ev_flags, include_queued, use_key_skip, s
        )

    def advance(c, ptr, q):
        """Move queue q's pointer to its next valid slot (amortized O(1):
        total advance steps across the pass are bounded by S)."""
        end = dev.queue_slot_end[q]

        def acond(p):
            return (p < end) & ~lazy_valid(c, jnp.clip(p, 0, S - 1))

        p = jax.lax.while_loop(acond, lambda p: p + 1, ptr[q])
        return ptr.at[q].set(p)

    def ptrs_from_scratch(c):
        valid, _ = _slot_validity(dev, c, include_queued, use_key_skip)
        heads, has = _queue_heads(dev, valid)
        return jnp.where(has, heads, dev.queue_slot_end)

    # Solve-kernel path (ops/pallas_kernels.py): static meta on the
    # round, so each path is its own compiled program. The fused scoring
    # + blocked selection engage only where the int64 key pack would
    # have engaged too (pack_plan mirrors _pack_fill_keys' gate); any
    # ineligible round silently keeps the lax graph, bit-for-bit.
    kpath = getattr(dev, "kernel_path", "lax")
    kbits = None
    if kpath != "lax":
        from ..ops import pallas_kernels as _pk

        kbits = _pk.pack_plan(dev, dist.n_shards)
        if kbits is None:
            kpath = "lax"
    knbits = sum(kbits) if kbits else None

    def f0_chain(alloc0, j):
        """Best-fit candidate-chain inputs for one job key against row-0
        capacity: (fit0 mask, per-node placement caps, node order keys).
        Shared by the serial fill and the heterogeneous window fill so the
        two paths can never drift apart (set parity depends on identical
        node ordering)."""
        if kbits is not None:
            from ..ops import pallas_kernels as _pk

            return _pk.fill_score(dev, dist, alloc0, j, kpath, kbits)
        B = dev.batch_window
        req_fit = dev.job_req_fit[j]
        static_ok = _static_ok(dev, j, jnp.zeros_like(dev.uni_value_bits[0]))
        fit0 = static_ok & jnp.all(req_fit <= alloc0, axis=-1)
        safe_req = jnp.maximum(req_fit, 1)
        caps = jnp.min(
            jnp.where(req_fit[None, :] > 0, alloc0 // safe_req[None, :], BIG),
            axis=-1,
        )
        caps = jnp.clip(caps, 0, B).astype(jnp.int32)
        nkeys = []
        for k in range(dev.order_res_idx.shape[0]):
            ri = dev.order_res_idx[k]
            res = dev.order_res_resolution[k]
            nkeys.append(alloc0[:, ri] // res)
        nkeys.append(dev.node_id_rank)
        return fit0, caps, _pack_fill_keys(dev, dist, alloc0.shape[0], nkeys)

    def fill_apply(c, qstar, sstar, kmax):
        """Place up to kmax jobs from the identical-singleton run headed at
        sstar onto row-0-feasible nodes in best-fit order (the f0 chain,
        nodedb.go:713-752). Placement parity: a node that wins the best-fit
        argmin keeps winning until the job no longer fits on it (binding
        only lowers its key), so identical jobs fill nodes to capacity in
        best-fit order. Returns (carry, placed); kmax==0 or no capacity
        leaves the carry bit-identical (scatters drop, deltas zero)."""
        B = dev.batch_window
        fdt = jnp.result_type(float)
        j = jnp.clip(dev.slot_members[sstar, 0], 0, dev.job_req.shape[0] - 1)
        prio = c.job_prio[j]
        pc = dev.job_pc[j]
        preemptible = dev.job_preemptible[j]
        req_fit = dev.job_req_fit[j]
        req_full = _f(dev.job_req[j])

        fit0, caps, nkeys = f0_chain(c.alloc[0], j)
        cand_caps, cand_gids = dist.fill_candidates(
            nkeys, fit0, caps, dev.node_gid, B, kpath, knbits
        )
        prefix = jnp.cumsum(cand_caps)
        total_cap = prefix[-1]

        kstar = jnp.minimum(
            jnp.minimum(kmax.astype(jnp.int32), total_cap),
            dev.slot_run_len[sstar],
        )
        kstar = jnp.clip(kstar, 0, B)

        cnt = jnp.clip(kstar - (prefix - cand_caps), 0, cand_caps)
        ln = c.alloc.shape[1]
        delta = dist.segment_to_nodes(
            (cnt[:, None] * req_fit[None, :]).astype(c.alloc.dtype),
            cand_gids,
            ln,
        )
        rows = jnp.where(
            preemptible,
            dev.priorities <= prio,
            jnp.ones_like(dev.priorities, bool),
        )
        alloc = c.alloc - jnp.where(rows[:, None, None], delta[None, :, :], 0)

        ivec = jnp.arange(B, dtype=jnp.int32)
        widx = sstar + ivec
        wjobs = dev.slot_members[jnp.clip(widx, 0, S - 1), 0]
        valid_w = ivec < kstar
        pos = jnp.searchsorted(prefix, ivec, side="right")
        node_w = cand_gids[jnp.clip(pos, 0, cand_gids.shape[0] - 1)]
        jdrop = jnp.where(valid_w, wjobs, dev.job_req.shape[0])
        sdrop = jnp.where(valid_w, widx, S)
        k_f = kstar.astype(fdt)
        c2 = c._replace(
            alloc=alloc,
            qalloc=c.qalloc.at[qstar].add(k_f * req_full),
            qpc_alloc=c.qpc_alloc.at[qstar, pc].add(k_f * req_full),
            job_node=c.job_node.at[jdrop].set(node_w, mode="drop"),
            job_prio=c.job_prio.at[jdrop].set(prio, mode="drop"),
            job_scheduled=c.job_scheduled.at[jdrop].set(True, mode="drop"),
            slot_state=c.slot_state.at[sdrop].set(jnp.int8(DONE), mode="drop"),
            tokens=c.tokens - k_f,
            qtokens=c.qtokens.at[qstar].add(-k_f),
            scheduled_new=c.scheduled_new + k_f * req_full,
            floating=c.floating
            + jnp.where(dev.floating_mask, k_f * req_full, 0.0),
        )
        return c2, kstar

    def fill_step(c, ptr, qstar, sstar, qkeys, has_head):
        """Exact single-queue batched fill: stop exactly where the serial
        loop would have switched queues or hit a constraint gate. The
        queue's PQ key after i placements is a closed form of i, so the
        crossover vs the (static) runner-up key is computed vectorized;
        every gate is monotone in i, so the combined stop point is the min
        of the individual ones. Returns (carry, ptr, applied);
        applied=False arms the force-serial handshake."""
        B = dev.batch_window
        fdt = jnp.result_type(float)
        j = jnp.clip(dev.slot_members[sstar, 0], 0, dev.job_req.shape[0] - 1)
        pc = dev.job_pc[j]
        req_full = _f(dev.job_req[j])

        # Runner-up queue's key tuple — static during the fill (no other
        # queue's head or allocation changes while this queue wins).
        mask2 = has_head & (jnp.arange(Q) != qstar)
        q2, found2 = lex_argmin(qkeys, mask2)
        rup = [k[q2] for k in qkeys]

        ivec = jnp.arange(B, dtype=jnp.int32)
        i_f = ivec.astype(fdt)
        qa_i = (
            (c.qalloc[qstar] + _f(dev.queue_short_penalty[qstar]))[None, :]
            + i_f[:, None] * req_full[None, :]
        )
        w_q = jnp.maximum(dev.queue_weight[qstar], 1e-12)
        cur_i = _policy_cost(dev, qa_i) / w_q
        prop_i = _policy_cost(dev, qa_i + req_full[None, :]) / w_q
        my_keys = []
        prk = _policy_rank_key(dev)
        if prk is not None:
            # Constant in i (the policy rank never moves during a fill),
            # so the key stream stays monotone and zip-aligned with the
            # body's qkeys.
            my_keys.append(jnp.full(B, prk[qstar], dtype=prk.dtype))
        if prefer_large:
            size = _policy_cost(dev, req_full) * dev.queue_weight[qstar]
            over_i = (prop_i > budgets[qstar]).astype(jnp.int32)
            my_keys += [
                over_i,
                jnp.where(over_i == 1, prop_i, cur_i),
                jnp.where(over_i == 1, 0.0, -size),
            ]
        else:
            my_keys.append(prop_i)
        my_keys.append(
            jnp.full(B, dev.queue_name_rank[qstar], dtype=jnp.int32)
        )
        win = jnp.zeros(B, bool)
        gt = jnp.zeros(B, bool)
        for a, b in zip(my_keys, rup):
            win = win | (~gt & (a < b))
            gt = gt | (a > b)
        win = win | ~found2

        # Constraint gates per step (the serial loop evaluates these before
        # each attempt, _constraint_code): i = number already placed.
        tok_ok = (c.tokens - i_f) >= 1
        qtok_ok = (c.qtokens[qstar] - i_f) >= 1
        round_ok = ~jnp.any(
            c.scheduled_new[None, :] + i_f[:, None] * req_full[None, :]
            > dev.max_round_resources[None, :],
            axis=-1,
        )
        pc_ok = ~jnp.any(
            c.qpc_alloc[qstar, pc][None, :]
            + (i_f + 1.0)[:, None] * req_full[None, :]
            > dev.queue_pc_limit[qstar, pc][None, :],
            axis=-1,
        )
        float_ok = ~jnp.any(
            dev.floating_mask[None, :]
            & (
                c.floating[None, :] + (i_f + 1.0)[:, None] * req_full[None, :]
                > dev.floating_total[None, :]
            ),
            axis=-1,
        )
        allowed = win & tok_ok & qtok_ok & round_ok & pc_ok & float_ok
        kmax = jnp.sum(jnp.cumprod(allowed.astype(jnp.int32))).astype(jnp.int32)

        c2, placed = fill_apply(c, qstar, sstar, kmax)
        applied = placed >= 1
        ptr2 = jnp.where(applied, ptr.at[qstar].set(sstar + placed), ptr)
        ptr2 = jax.lax.cond(
            applied, lambda: advance(c2, ptr2, qstar), lambda: ptr2
        )
        return c2, ptr2, applied

    def window_fill_apply(c, q, widx_q, j_q, gid_q, rank_q, kq, pc):
        """Place the accepted window prefix (kq entries, keys may DIFFER)
        for one queue. Entries are grouped by interned scheduling key
        (identical req + static feasibility within a group); groups place
        sequentially — each sees row-0 capacity net of earlier groups —
        through the same best-fit candidate chain as fill_apply. Placement
        is cut at the FIRST window entry whose group ran out of capacity,
        so what is applied is always a stream prefix (the pointer
        contract); under-capacity leftovers re-enter as heads next
        iteration and degrade to the serial path. Returns (carry, placed)."""
        W = dev.batch_window
        G = dev.fill_groups
        fdt = jnp.result_type(float)
        ln = c.alloc.shape[1]
        ivec = jnp.arange(W, dtype=jnp.int32)
        ent = ivec < kq
        gidc = jnp.clip(gid_q, 0, G - 1)
        cnt_g = jax.ops.segment_sum(
            jnp.where(ent, 1, 0).astype(jnp.int32), gidc, num_segments=G
        )
        rep = jax.ops.segment_min(
            jnp.where(ent, ivec, BIG), gidc, num_segments=G
        )
        live_g = rep < BIG
        j_g = jnp.clip(j_q[jnp.clip(rep, 0, W - 1)], 0, dev.job_req.shape[0] - 1)
        j0 = j_q[0]
        prio = c.job_prio[j0]
        preemptible = dev.job_preemptible[j0]

        def g_step(used, g):
            alloc0 = c.alloc[0] - used
            j = j_g[g]
            req_fit = dev.job_req_fit[j]
            fit0, caps, nkeys = f0_chain(alloc0, j)

            def do(used):
                cand_caps, cand_gids = dist.fill_candidates(
                    nkeys, fit0, caps, dev.node_gid, W, kpath, knbits
                )
                prefix = jnp.cumsum(cand_caps)
                placed = jnp.minimum(cnt_g[g], prefix[-1]).astype(jnp.int32)
                cnt = jnp.clip(placed - (prefix - cand_caps), 0, cand_caps)
                used2 = used + dist.segment_to_nodes(
                    (cnt[:, None] * req_fit[None, :]).astype(used.dtype),
                    cand_gids,
                    ln,
                )
                # Fewer than W candidate nodes (small clusters / shard
                # merges): pad so both cond branches agree; prefix pads
                # with its last value to stay a valid searchsorted input.
                Bc = cand_caps.shape[0]
                if Bc < W:
                    cand_gids = jnp.pad(cand_gids, (0, W - Bc))
                    prefix = jnp.pad(prefix, (0, W - Bc), mode="edge")
                return used2, (cand_gids, prefix, placed)

            def skip(used):
                return used, (
                    jnp.zeros(W, jnp.int32),
                    jnp.zeros(W, jnp.int32),
                    jnp.zeros((), jnp.int32),
                )

            return jax.lax.cond(live_g[g] & (cnt_g[g] > 0), do, skip, used)

        _, (cand_gids_g, prefix_g, placed_g) = jax.lax.scan(
            g_step, jnp.zeros_like(c.alloc[0]), jnp.arange(G, dtype=jnp.int32)
        )

        ok_e = ent & (rank_q < placed_g[gidc])
        fail_pos = jnp.min(jnp.where(ent & ~ok_e, ivec, W))
        applied_n = jnp.minimum(kq, fail_pos).astype(jnp.int32)
        app = ivec < applied_n
        pos = jax.vmap(lambda a, v: jnp.searchsorted(a, v, side="right"))(
            prefix_g[gidc], rank_q
        )
        node_e = cand_gids_g[gidc, jnp.clip(pos, 0, W - 1)]
        safe_j = jnp.clip(j_q, 0, dev.job_req.shape[0] - 1)
        req_fit_e = jnp.where(app[:, None], dev.job_req_fit[safe_j], 0)
        req_full_e = jnp.where(app[:, None], _f(dev.job_req[safe_j]), 0.0)
        delta = dist.segment_to_nodes(
            req_fit_e.astype(c.alloc.dtype), jnp.where(app, node_e, -1), ln
        )
        rows = jnp.where(
            preemptible,
            dev.priorities <= prio,
            jnp.ones_like(dev.priorities, bool),
        )
        alloc = c.alloc - jnp.where(rows[:, None, None], delta[None, :, :], 0)
        k_f = applied_n.astype(fdt)
        sum_full = jnp.sum(req_full_e, axis=0)
        jdrop = jnp.where(app, j_q, dev.job_req.shape[0])
        sdrop = jnp.where(app, widx_q, S)
        c2 = c._replace(
            alloc=alloc,
            qalloc=c.qalloc.at[q].add(sum_full),
            qpc_alloc=c.qpc_alloc.at[q, pc].add(sum_full),
            job_node=c.job_node.at[jdrop].set(node_e, mode="drop"),
            job_prio=c.job_prio.at[jdrop].set(prio, mode="drop"),
            job_scheduled=c.job_scheduled.at[jdrop].set(True, mode="drop"),
            slot_state=c.slot_state.at[sdrop].set(jnp.int8(DONE), mode="drop"),
            tokens=c.tokens - k_f,
            qtokens=c.qtokens.at[q].add(-k_f),
            scheduled_new=c.scheduled_new + sum_full,
            floating=c.floating
            + jnp.where(dev.floating_mask, sum_full, 0.0),
        )
        return c2, applied_n

    def ev_batchable(s):
        """Slots the evicted-rebind window may batch: singleton running
        gangs with no uniformity search (the pinned path consults only the
        home node). One predicate shared by head eligibility and window
        membership so the two can never drift apart. (Callers must also
        require all-evicted: lazy_valid enforces it for entries,
        all_ev_flags for heads.)"""
        return (
            (dev.slot_count[s] == 1)
            & dev.slot_is_running[s]
            & (dev.slot_uni_end[s] <= dev.slot_uni_start[s])
        )

    def ev_fill_apply(c, q, widx_q, j_q, kq, pc):
        """Place the accepted window prefix of EVICTED singleton slots for
        one queue. Pinned semantics (_select_node: evicted jobs only ever
        return to their node): entry i fits iff its home node still holds
        its request at its priority row net of earlier window entries on
        the same node (or the over-allocated-unschedulable special case).
        Binding mirrors _bind for was_evicted: rows <= prio lose the
        request, row 0 nets zero. Queue accounting mirrors the serial
        all-evicted path: qalloc/qpc/floating grow, tokens and round caps
        are NOT consumed. Returns (carry, placed)."""
        W = dev.batch_window
        ln = c.alloc.shape[1]
        P = dev.priorities.shape[0]
        ivec = jnp.arange(W, dtype=jnp.int32)
        ent = ivec < kq
        safe_j = jnp.clip(j_q, 0, dev.job_req.shape[0] - 1)
        j0 = j_q[0]
        prio = c.job_prio[j0]
        preemptible = dev.job_preemptible[j0]
        row_p = jnp.searchsorted(dev.priorities, prio).astype(jnp.int32)
        nmax = dist.num_nodes(c.alloc)  # global id space (sharded-aware)
        home = jnp.clip(c.job_node[safe_j], 0, nmax - 1)  # [W] global ids
        req_fit = dev.job_req_fit[safe_j]  # [W, R]

        # Requirement earlier window entries already placed on MY node.
        same_before = (
            (home[:, None] == home[None, :])
            & (ivec[None, :] < ivec[:, None])
            & ent[None, :]
        )
        prior = jnp.einsum(
            "we,er->wr", same_before.astype(req_fit.dtype), req_fit
        )
        home_col = jax.vmap(lambda n: dist.take_col(c.alloc, n))(
            home
        )  # [W, P, R]
        rows_le = jnp.where(
            preemptible,
            dev.priorities <= prio,
            jnp.ones_like(dev.priorities, bool),
        )
        # Earlier entries' effect per row: rows<=prio except row 0 (the
        # evicted add-back keeps row 0 flat).
        rows_eff = rows_le & (jnp.arange(P) > 0)
        col_after = home_col - jnp.where(
            rows_eff[None, :, None], prior[:, None, :], 0
        ).astype(home_col.dtype)
        fit = jnp.all(req_fit <= col_after[:, row_p, :], axis=-1)
        unsched = jax.vmap(lambda n: dist.take(dev.node_unschedulable, n))(
            home
        )
        over_alloc = jnp.any(col_after < 0, axis=(1, 2))
        ok_e = ent & (fit | (unsched & over_alloc))
        fail_pos = jnp.min(jnp.where(ent & ~ok_e, ivec, W))
        applied_n = jnp.minimum(kq, fail_pos).astype(jnp.int32)
        app = ivec < applied_n

        req_fit_e = jnp.where(app[:, None], req_fit, 0)
        req_full_e = jnp.where(app[:, None], _f(dev.job_req[safe_j]), 0.0)
        delta = dist.segment_to_nodes(
            req_fit_e.astype(c.alloc.dtype), jnp.where(app, home, -1), ln
        )
        alloc = c.alloc - jnp.where(rows_eff[:, None, None], delta[None], 0)
        sum_full = jnp.sum(req_full_e, axis=0)
        jdrop = jnp.where(app, j_q, dev.job_req.shape[0])
        sdrop = jnp.where(app, widx_q, S)
        c2 = c._replace(
            alloc=alloc,
            qalloc=c.qalloc.at[q].add(sum_full),
            qpc_alloc=c.qpc_alloc.at[q, pc].add(sum_full),
            job_evicted=c.job_evicted.at[jdrop].set(False, mode="drop"),
            evict_rank=c.evict_rank.at[jdrop].set(-2, mode="drop"),
            slot_state=c.slot_state.at[sdrop].set(jnp.int8(DONE), mode="drop"),
            floating=c.floating + jnp.where(dev.floating_mask, sum_full, 0.0),
        )
        return c2, applied_n

    def merged_fill_step(c, ptr, heads, has_head, qkeys, all_ev_h, eligible):
        """Fast-mode multi-queue HETEROGENEOUS fill: ONE iteration batches
        the whole multi-queue sweep over windows of consecutive batchable
        slots whose scheduling keys may differ. Each queue's candidate-cost
        sequence is computed from the cumulative window requests (costs are
        monotone in the cumulative allocation, so each queue's key stream
        is non-decreasing and the exact serial attempt order across queues
        is a SORT of all (queue, i) entry keys), cut at the first
        ineligible head's key (the barrier — that attempt needs the serial
        path, and nothing after it may be batched). Global gates (tokens,
        round caps, floating) cut the merged suffix; per-queue gates cut
        only that queue's entries, exactly as the serial loop's FAIL
        handling skips one queue without stopping others. Placement is then
        greedy per queue grouped by key (set-exact vs serial whenever
        everything fits at row 0; node assignment may differ from the
        reference trace). Returns (carry, ptr, progressed)."""
        W = dev.batch_window
        G = dev.fill_groups
        fdt = jnp.result_type(float)
        J = dev.job_req.shape[0]
        ivec = jnp.arange(W, dtype=jnp.int32)
        i_f = ivec.astype(fdt)

        # Per-queue windows: maximal prefix of consecutive in-range,
        # batchable, valid slots sharing the head's priority class. Two
        # window KINDS, chosen by the head: queued windows batch
        # slot_batchable slots through the grouped best-fit fill; EVICTED
        # windows (head is an all-evicted running singleton) batch pinned
        # rebinds — every singleton evicted slot qualifies (the pinned
        # path consults only the home node, _select_node), uniform
        # priority so the bind rows agree.
        raw = heads[:, None] + ivec[None, :]
        widx = jnp.clip(raw, 0, S - 1)  # [Q, W]
        in_range = raw < dev.queue_slot_end[:, None]
        j_w = jnp.clip(dev.slot_members[widx, 0], 0, J - 1)
        pc_h = dev.job_pc[j_w[:, 0]]
        vv = jax.vmap(lambda s: lazy_valid(c, s))(widx.reshape(-1)).reshape(Q, W)
        kind_ev = dev.slot_is_running[jnp.clip(heads, 0, S - 1)]  # [Q]
        ev_ok = ev_batchable(widx)
        prio_w = c.job_prio[j_w]
        kind_ok = jnp.where(
            kind_ev[:, None],
            ev_ok & (prio_w == prio_w[:, :1]),
            dev.slot_batchable[widx] & ~dev.slot_is_running[widx],
        )
        base = (
            eligible[:, None]
            & in_range
            & kind_ok
            & vv
            & (dev.job_pc[j_w] == pc_h[:, None])
        )
        base = jnp.cumprod(base.astype(jnp.int8), axis=1).astype(bool)

        # Group structure by interned key. Masked entries get unique
        # sentinels so they only self-match. gid = first-appearance rank of
        # the entry's key within the window; rank_in_g = how many earlier
        # window entries share its key. Windows are cut at key number G+1.
        # (Evicted windows skip grouping entirely — placement is pinned.)
        # Occurrence ranking runs as ONE (queue, key, position) sort over
        # the Q*W entries plus segment scans — O(QW log QW) — instead of
        # the [Q, W, W] equality matrix, whose O(W^2) traffic capped
        # usable fill windows at a few hundred slots (and measured
        # slower even at W=512 on this host).
        grp = jnp.where(base, dev.slot_key_group[widx], -2 - ivec[None, :])
        QW = Q * W
        flat_idx = jnp.arange(QW, dtype=jnp.int32)
        qrow = flat_idx // W
        pos_f = jnp.broadcast_to(ivec[None, :], (Q, W)).reshape(-1)
        grp_f = grp.reshape(-1)
        order_g = lexsort([qrow, grp_f, pos_f])
        q_s = qrow[order_g]
        g_s = grp_f[order_g]
        p_s = pos_f[order_g]
        run_head = jnp.concatenate(
            [
                jnp.ones(1, bool),
                (q_s[1:] != q_s[:-1]) | (g_s[1:] != g_s[:-1]),
            ]
        )
        head_at = jax.lax.associative_scan(
            jnp.maximum, jnp.where(run_head, flat_idx, 0)
        )
        rank_in_g = (
            jnp.zeros(QW, jnp.int32)
            .at[order_g]
            .set((flat_idx - head_at).astype(jnp.int32))
            .reshape(Q, W)
        )
        first_j = (
            jnp.zeros(QW, jnp.int32)
            .at[order_g]
            .set(p_s[head_at])
            .reshape(Q, W)
        )
        first_occ = (first_j == ivec[None, :]) & base
        gnum = jnp.cumsum(first_occ.astype(jnp.int32), axis=1)
        gid = jnp.take_along_axis(gnum, first_j, axis=1) - 1
        base = base & ((gid < G) | kind_ev[:, None])
        base = jnp.cumprod(base.astype(jnp.int8), axis=1).astype(bool)

        # Entry costs from cumulative window requests (exact serial
        # closed form: entry i's queue allocation is qalloc + sum of the
        # i previous window requests).
        req_e = jnp.where(base[:, :, None], _f(dev.slot_req[widx]), 0.0)
        csum_incl = _exact_cumsum(req_e, axis=1)  # [Q, W, R]
        csum_prev = csum_incl - req_e
        qa = c.qalloc + _f(dev.queue_short_penalty)  # [Q, R]
        w = jnp.maximum(dev.queue_weight, 1e-12)
        qa_i = qa[:, None, :] + csum_prev
        cur = _policy_cost(dev, qa_i) / w[:, None]
        prop = _policy_cost(dev, qa_i + req_e) / w[:, None]
        ekeys = []
        prk = _policy_rank_key(dev)
        if prk is not None:
            # Constant per queue — monotone within every window and
            # zip-aligned with the body's qkeys for the barrier compare.
            ekeys.append(jnp.broadcast_to(prk[:, None], (Q, W)))
        if prefer_large:
            size = _policy_cost(dev, req_e) * dev.queue_weight[:, None]  # [Q, W]
            over = (prop > budgets[:, None]).astype(jnp.int32)
            ekeys += [
                over,
                jnp.where(over == 1, prop, cur),
                jnp.where(over == 1, 0.0, -size),
            ]
        else:
            ekeys.append(prop)
        rank2d = jnp.broadcast_to(dev.queue_name_rank[:, None], (Q, W))
        ekeys.append(rank2d)

        # Merge exactness requires each queue's key stream non-decreasing
        # (costs are monotone in the cumulative allocation; only the
        # prefer-large -size tiebreak at exactly tied costs can invert).
        # Cut the window at the first inversion.
        dec = jnp.zeros((Q, W), bool)
        gtp = jnp.zeros((Q, W), bool)
        for k in ekeys:
            prev = jnp.concatenate([k[:, :1], k[:, :-1]], axis=1)
            dec = dec | (~gtp & (k < prev))
            gtp = gtp | (k > prev)
        dec = dec.at[:, 0].set(False)
        base = base & ~dec
        base = jnp.cumprod(base.astype(jnp.int8), axis=1).astype(bool)

        # Barrier: the best ineligible head's key; batched entries must be
        # strictly lex-below it (ranks are unique, so strict < suffices).
        bmask = has_head & ~eligible
        qb, has_barrier = lex_argmin(qkeys, bmask)
        bk = [k[qb] for k in qkeys]

        # Entry validity: per-queue prefix gates (qtokens, per-PC caps)
        # and the barrier. Evicted windows bypass both — the serial path's
        # _constraint_code forces OK for all-evicted gangs (tokens and
        # caps are not consumed by rebinds).
        qtok_ok = ((c.qtokens[:, None] - i_f[None, :]) >= 1) | kind_ev[:, None]
        aq = jnp.arange(Q)
        qpc = c.qpc_alloc[aq, pc_h]  # [Q, R]
        pc_lim = dev.queue_pc_limit[aq, pc_h]  # [Q, R]
        pc_ok = ~jnp.any(
            qpc[:, None, :] + csum_incl > pc_lim[:, None, :], axis=-1
        ) | kind_ev[:, None]
        below = jnp.zeros((Q, W), bool)
        gt = jnp.zeros((Q, W), bool)
        for a, b in zip(ekeys, bk):
            below = below | (~gt & (a < b))
            gt = gt | (a > b)
        barrier_ok = below | ~has_barrier
        entry_ok = base & qtok_ok & pc_ok & barrier_ok
        entry_ok = jnp.cumprod(entry_ok.astype(jnp.int8), axis=1).astype(bool)

        # Merged order: sort all entries by key; stable + the i tiebreak
        # keeps same-queue equal-cost entries in stream order.
        flat_keys = [k.reshape(-1) for k in ekeys] + [
            jnp.broadcast_to(ivec[None, :], (Q, W)).reshape(-1)
        ]
        order = lexsort(flat_keys)
        take = entry_ok.reshape(-1)[order]
        qidx = (jnp.arange(Q * W, dtype=jnp.int32) // W)[order]
        req_s = req_e.reshape(Q * W, -1)[order]  # [QW, R]
        req_taken = jnp.where(take[:, None], req_s, 0.0)
        # Evicted entries consume neither tokens nor round caps (the
        # serial all-evicted exemptions); they DO count toward floating.
        ev_flat = kind_ev[qidx]
        consuming = take & ~ev_flat
        req_consumed = jnp.where(consuming[:, None], req_s, 0.0)
        cum_cnt_b = jnp.cumsum(consuming.astype(jnp.int32)) - consuming.astype(
            jnp.int32
        )
        cum_req = _exact_cumsum(req_taken, axis=0)
        cum_req_c = _exact_cumsum(req_consumed, axis=0)
        cum_req_cb = cum_req_c - req_consumed
        tok_ok_g = ((c.tokens - cum_cnt_b.astype(fdt)) >= 1) | ev_flat
        round_ok_g = (
            ~jnp.any(
                c.scheduled_new[None, :] + cum_req_cb
                > dev.max_round_resources[None, :],
                axis=-1,
            )
            | ev_flat
        )
        float_ok_g = ~jnp.any(
            dev.floating_mask[None, :]
            & (c.floating[None, :] + cum_req > dev.floating_total[None, :]),
            axis=-1,
        )
        viol = take & ~(tok_ok_g & round_ok_g & float_ok_g)
        any_viol = jnp.any(viol)
        first_viol = jnp.argmax(viol)
        posn = jnp.arange(Q * W)
        final_take = take & (~any_viol | (posn < first_viol))
        k_q = jax.ops.segment_sum(
            final_take.astype(jnp.int32), qidx, num_segments=Q
        )

        # Sequential per-queue placement (deterministic queue order); each
        # queue's fill sees the capacity the previous queues consumed.
        def apply_q(q, state):
            c, ptr, progressed, shortfall = state

            def do(args):
                c, ptr, progressed, shortfall = args
                c2, placed = jax.lax.cond(
                    kind_ev[q],
                    lambda c: ev_fill_apply(
                        c, q, widx[q], j_w[q], k_q[q], pc_h[q]
                    ),
                    lambda c: window_fill_apply(
                        c, q, widx[q], j_w[q], gid[q], rank_in_g[q], k_q[q],
                        pc_h[q],
                    ),
                    c,
                )
                ptr2 = jnp.where(
                    placed > 0, ptr.at[q].set(heads[q] + placed), ptr
                )
                ptr2 = jax.lax.cond(
                    placed > 0, lambda: advance(c2, ptr2, q), lambda: ptr2
                )
                return (
                    c2,
                    ptr2,
                    progressed | (placed > 0),
                    shortfall | (placed < k_q[q]),
                )

            return jax.lax.cond(
                k_q[q] > 0, do, lambda a: a, (c, ptr, progressed, shortfall)
            )

        c2, ptr2, progressed, shortfall = jax.lax.fori_loop(
            0, Q, apply_q,
            (c, ptr, jnp.zeros((), bool), jnp.zeros((), bool)),
        )
        # Capacity shortfall with >1 active queue: some taken entries did
        # not fit, yet entries merged-sorted AFTER them (other queues)
        # were applied — a capacity-contested interleave the batch cannot
        # express. Roll the whole iteration back (functional txn, like the
        # serial gang attempt) and let the serial path resolve it exactly.
        # Single-queue iterations keep the prefix commit: that IS the
        # serial order.
        multi = jnp.sum((k_q > 0).astype(jnp.int32)) > 1
        keep = ~(shortfall & multi)
        c = jax.tree_util.tree_map(
            lambda a, b: jnp.where(keep, b, a), c, c2
        )
        ptr = jnp.where(keep, ptr2, ptr)
        progressed = progressed & keep
        return c, ptr, progressed

    def body(state):
        c, ptr, force_serial, segc = state
        has_head = ptr < dev.queue_slot_end
        heads = jnp.clip(ptr, 0, S - 1)

        req_h = _f(dev.slot_req[heads])  # [Q, R]
        qalloc_cost = c.qalloc + _f(dev.queue_short_penalty)
        cur = _policy_cost(dev, qalloc_cost)
        w = jnp.maximum(dev.queue_weight, 1e-12)
        current = cur / w
        proposed = _policy_cost(dev, qalloc_cost + req_h) / w
        size = _policy_cost(dev, req_h) * dev.queue_weight
        pcp = jax.vmap(lambda s: _slot_min_prio(dev, c, s))(heads)

        keys = []
        if dev.market_driven:
            # Highest gang price first (market_iterator.go).
            keys.append(-dev.slot_price[heads])
        elif consider_priority:
            keys.append(-pcp)
        if not dev.market_driven:
            prk = _policy_rank_key(dev)
            if prk is not None:
                keys.append(prk)
            if prefer_large:
                over = (proposed > budgets).astype(jnp.int32)
                k1 = jnp.where(over == 1, proposed, current)
                k2 = jnp.where(over == 1, 0.0, -size)
                keys += [over, k1, k2]
            else:
                keys.append(proposed)
        keys.append(dev.queue_name_rank)

        qstar, any_head = lex_argmin(keys, has_head)
        sstar = heads[qstar]

        def serial_step(c, ptr):
            def attempt(c):
                c2, status = _gang_attempt(
                    dev, dist, c, sstar, all_ev_flags[sstar], fp_order
                )
                # Terminal handling (queue_scheduler.go:176-190).
                c2 = c2._replace(
                    only_ev_global=c2.only_ev_global | (status == FAIL_TERMINAL),
                    only_ev_queue=c2.only_ev_queue.at[dev.slot_queue[sstar]].set(
                        c2.only_ev_queue[dev.slot_queue[sstar]]
                        | (status == FAIL_QUEUE_TERMINAL)
                    ),
                )
                # Register unfeasible keys: single-member, non-evicted slots
                # with gang-property failures (gang_scheduler.go:80-95).
                kg = dev.slot_key_group[sstar]
                register = (
                    (status == FAIL_GANG_PROPERTY)
                    & (dev.slot_count[sstar] == 1)
                    & (kg >= 0)
                    & ~all_ev_flags[sstar]
                )
                safe_kg = jnp.clip(kg, 0, c2.unfeasible.shape[0] - 1)
                c2 = c2._replace(
                    unfeasible=c2.unfeasible.at[safe_kg].set(
                        c2.unfeasible[safe_kg] | register
                    )
                )
                return c2

            flags_before = (c.only_ev_global, c.only_ev_queue, c.unfeasible)
            c = jax.lax.cond(any_head, attempt, lambda c: c._replace(stop=True), c)

            flags_changed = (
                (c.only_ev_global != flags_before[0])
                | jnp.any(c.only_ev_queue != flags_before[1])
                | jnp.any(c.unfeasible != flags_before[2])
            )
            # Consume the winning slot and advance its queue's pointer to the
            # next valid slot; a flag flip can invalidate OTHER queues' heads,
            # so it triggers the full O(S) recompute instead.
            ptr = jnp.where(any_head, ptr.at[qstar].set(sstar + 1), ptr)
            ptr = jax.lax.cond(
                flags_changed,
                lambda: ptrs_from_scratch(c),
                lambda: jax.lax.cond(
                    any_head,
                    lambda: advance(c, ptr, qstar),
                    lambda: ptr,
                ),
            )
            return c, ptr

        if fast_fill_enabled:
            all_ev_h = all_ev_flags[heads]
            # Evicted heads (all-evicted running singletons) batch through
            # the pinned-rebind window; queued heads through the grouped
            # best-fit window.
            ev_head = all_ev_h & ev_batchable(heads)
            # Constraint codes with the serial path's all-evicted
            # exemptions applied to evicted heads (tokens/caps bypassed,
            # floating still gates).
            code_h = jax.vmap(
                lambda s, ae: _constraint_code(dev, c, s, ae)
            )(heads, ev_head)
            eligible = (
                has_head
                & (code_h == OK)
                & ((dev.slot_batchable[heads] & ~all_ev_h) | ev_head)
            )
            do_merge = jnp.any(eligible) & ~force_serial

            def merged_branch(args):
                c, ptr = args
                c2, ptr2, progressed = merged_fill_step(
                    c, ptr, heads, has_head, keys, all_ev_h, eligible
                )
                return c2, ptr2, ~progressed

            def serial_branch(args):
                c2, ptr2 = serial_step(*args)
                return c2, ptr2, jnp.zeros((), bool)

            c, ptr, fs = jax.lax.cond(
                do_merge, merged_branch, serial_branch, (c, ptr)
            )
            segc = segc.at[jnp.where(do_merge, SEG_MERGED, SEG_GANG)].add(1)
        elif fill_enabled:
            do_fill = (
                any_head
                & ~force_serial
                & (dev.slot_run_len[sstar] > 0)
                & ~all_ev_flags[sstar]
                & (_constraint_code(dev, c, sstar, jnp.zeros((), bool)) == OK)
            )

            def fill_branch(args):
                c, ptr = args
                c2, ptr2, applied = fill_step(c, ptr, qstar, sstar, keys, has_head)
                return c2, ptr2, ~applied

            def serial_branch(args):
                c2, ptr2 = serial_step(*args)
                return c2, ptr2, jnp.zeros((), bool)

            c, ptr, fs = jax.lax.cond(do_fill, fill_branch, serial_branch, (c, ptr))
            segc = segc.at[jnp.where(do_fill, SEG_FILL, SEG_GANG)].add(1)
        else:
            c, ptr = serial_step(c, ptr)
            fs = jnp.zeros((), bool)
            segc = segc.at[SEG_GANG].add(1)
        return c._replace(loops=c.loops + 1), ptr, fs, segc

    if seg0 is None:
        seg0 = jnp.zeros(3, jnp.int32)
    carry, ptr, fs, segc = jax.lax.while_loop(
        cond, body, (carry, ptr0, fs0, seg0)
    )
    return carry, ptr, fs, segc


def _pass_init_ptrs(dev, carry, include_queued, use_key_skip):
    """Initial head pointers for a pass: first valid slot per queue."""
    valid0, _ = _slot_validity(dev, carry, include_queued, use_key_skip)
    heads0, has0 = _queue_heads(dev, valid0)
    return jnp.where(has0, heads0, dev.queue_slot_end)


def _schedule_pass(
    dev,
    dist,
    carry: Carry,
    budgets,
    *,
    include_queued: bool,
    use_key_skip: bool,
    consider_priority: bool,
    prefer_large: bool,
):
    """One full (un-budgeted) pass: init pointers, run to completion."""
    S = dev.slot_members.shape[0]
    ptr0 = _pass_init_ptrs(dev, carry, include_queued, use_key_skip)
    # The counter restarts per pass (the reference's loopNumber is also
    # per-QueueScheduler, queue_scheduler.go:99).
    carry = carry._replace(stop=jnp.zeros((), bool), loops=jnp.zeros((), jnp.int32))
    carry, _, _, _ = _pass_segment(
        dev,
        dist,
        carry,
        ptr0,
        jnp.zeros((), bool),
        budgets,
        2 * S + 4,
        include_queued=include_queued,
        use_key_skip=use_key_skip,
        consider_priority=consider_priority,
        prefer_large=prefer_large,
    )
    return carry


def _apply_evictions(dev, dist, carry: Carry, evict_mask):
    """Move evicted jobs' usage to the evicted row and update queue
    accounting (EvictJobsFromNode + sctx.EvictJob). Returns (carry,
    compaction path count, solver/segments.py)."""
    P = dev.priorities.shape[0]
    Q = dev.queue_weight.shape[0]
    C = dev.pc_priority.shape[0]
    req = dev.job_req
    alloc = carry.alloc
    ln = alloc.shape[1]
    paths = no_compaction()
    for r in range(1, P):
        in_rows = jnp.where(
            dev.job_preemptible,
            dev.priorities[r] <= carry.job_prio,
            True,
        )
        contrib = jnp.where(
            (evict_mask & in_rows)[:, None], dev.job_req_fit, 0
        ).astype(alloc.dtype)
        add, path = dist.sparse_segment_to_nodes(contrib, carry.job_node, ln)
        alloc = alloc.at[r].add(add)
        paths = paths + path

    qseg = jnp.clip(dev.job_queue, 0, Q - 1)
    evicted_req = jnp.where(evict_mask[:, None], _f(req), 0.0)
    qsub = segment_sum_dense(evicted_req, qseg, Q)
    # per-PC accounting
    qpc_sub = segment_sum_dense(evicted_req, qseg * C + dev.job_pc, Q * C)
    floating_sub = jnp.sum(
        jnp.where(
            (evict_mask[:, None] & dev.floating_mask[None, :]), _f(req), 0.0
        ),
        axis=0,
    )
    return carry._replace(
        alloc=alloc,
        qalloc=carry.qalloc - qsub,
        qpc_alloc=carry.qpc_alloc - qpc_sub.reshape(carry.qpc_alloc.shape),
        floating=carry.floating - floating_sub,
        job_evicted=carry.job_evicted | evict_mask,
    ), paths


def _assign_evict_ranks(dev, carry: Carry, budgets, prefer_large: bool):
    """addEvictedJobsToNodeDb (preempting_queue_scheduler.go:584-633): walk
    evicted slots in candidate order with static allocations, assigning a
    global fairness rank to each member."""
    S, M = dev.slot_members.shape
    Q = dev.queue_weight.shape[0]
    member_mask = jnp.arange(M)[None, :] < dev.slot_count[:, None]
    safe = jnp.clip(dev.slot_members, 0, dev.job_req.shape[0] - 1)
    slot_all_ev = jnp.all(
        jnp.where(member_mask, carry.job_evicted[safe], True), axis=1
    )
    eligible0 = (carry.slot_state == PENDING) & slot_all_ev & (dev.slot_count > 0)

    w = jnp.maximum(dev.queue_weight, 1e-12)
    qalloc_cost = carry.qalloc + _f(dev.queue_short_penalty)
    cur = _policy_cost(dev, qalloc_cost) / w

    def cond(state):
        _, _, remaining, i = state
        return remaining & (i < S + 1)

    def body(state):
        rank, done, _, i = state
        elig = eligible0 & ~done
        heads, has_head = _queue_heads(dev, elig)
        req_h = _f(dev.slot_req[heads])
        proposed = _policy_cost(dev, qalloc_cost + req_h) / w
        size = _policy_cost(dev, req_h) * dev.queue_weight
        keys = []
        if dev.market_driven:
            keys.append(-dev.slot_price[heads])
        else:
            prk = _policy_rank_key(dev)
            if prk is not None:
                # Same leading key as the scheduling passes: low-rank
                # queues schedule later, so fair preemption (largest
                # rank first) consumes them first.
                keys.append(prk)
            if prefer_large:
                over = (proposed > budgets).astype(jnp.int32)
                keys += [over, jnp.where(over == 1, proposed, cur),
                         jnp.where(over == 1, 0.0, -size)]
            else:
                keys.append(proposed)
        keys.append(dev.queue_name_rank)
        qstar, any_head = lex_argmin(keys, has_head)
        sstar = heads[qstar]
        mmask = jnp.arange(M) < dev.slot_count[sstar]
        js = jnp.clip(dev.slot_members[sstar], 0, rank.shape[0] - 1)
        base = i * M
        new_rank = rank.at[js].set(
            jnp.where(mmask, base + jnp.arange(M, dtype=jnp.int32), rank[js])
        )
        rank = jnp.where(any_head, new_rank, rank)
        done = done.at[sstar].set(done[sstar] | any_head)
        return rank, done, any_head, i + 1

    rank0 = jnp.full(dev.job_req.shape[0], -1, dtype=jnp.int32)
    done0 = jnp.zeros(S, dtype=bool)
    rank, *_ = jax.lax.while_loop(
        cond, body, (rank0, done0, jnp.ones((), bool), jnp.asarray(0, jnp.int32))
    )
    # Ranks increase with scheduling preference; fair preemption consumes the
    # LARGEST ranks first (latest in the fairness order). Here larger rank =
    # scheduled later = consumed first, matching ReverseLowerBound.
    return carry._replace(evict_rank=rank)


def _oversubscribed_mask(dev, dist, carry: Carry):
    """OversubscribedEvictor (eviction.go:133-180)."""
    P = dev.priorities.shape[0]
    bound = (carry.job_node >= 0) & ~carry.job_evicted
    mask = jnp.zeros(dev.job_req.shape[0], dtype=bool)
    for r in range(1, P):
        over_nodes = jnp.any(carry.alloc[r] < 0, axis=-1)  # [local N]
        at_prio = carry.job_prio == dev.priorities[r]
        over_at_job = dist.take_rows(over_nodes, carry.job_node)
        mask = mask | (bound & dev.job_preemptible & at_prio & over_at_job)
    return mask & (dev.job_queue >= 0)


def _gang_complete_mask(dev, carry: Carry, evict_mask):
    """Extend an eviction mask to whole gangs (evictGangs). Returns
    (mask, compaction path count, solver/segments.py)."""
    S, M = dev.slot_members.shape
    if M == 1:
        # No slot holds two members, so no gang has one to add.
        return evict_mask, no_compaction()
    J = evict_mask.shape[0]
    safe = jnp.clip(dev.slot_members, 0, J - 1)
    member_mask = jnp.arange(M)[None, :] < dev.slot_count[:, None]
    slot_has_evicted = jnp.any(member_mask & evict_mask[safe], axis=1)
    bound = (carry.job_node >= 0) & ~carry.job_evicted
    slot_sel = slot_has_evicted & (dev.slot_count > 1)
    flat = safe.reshape(-1)
    sel_flat = (slot_sel[:, None] & member_mask).reshape(-1)

    def bounded(idx):
        jobs = jnp.where(idx >= 0, flat[jnp.clip(idx, 0, flat.shape[0] - 1)], J)
        return jnp.zeros_like(evict_mask).at[jobs].set(True, mode="drop")

    add, path = scatter_rows(
        sel_flat, bounded, lambda: jnp.zeros_like(evict_mask).at[flat].max(sel_flat)
    )
    return evict_mask | (add & bound), path


def _round_setup(dev: DeviceRound, dist=LOCAL):
    """Fair shares, initial carry, balance eviction, eviction ranks —
    everything before pass 1. Returns
    (carry, budgets, fair_share, demand_capped, uncapped)."""
    J = dev.job_req.shape[0]
    Q = dev.queue_weight.shape[0]
    S = dev.slot_members.shape[0]
    C = dev.pc_priority.shape[0]
    R = dev.job_req.shape[1]

    fdt = jnp.result_type(float)

    # Fair shares from constrained demand.
    demand_capped_pc = jnp.minimum(
        _f(dev.queue_demand_pc), dev.queue_pc_limit
    )
    constrained = jnp.sum(demand_capped_pc, axis=1)  # [Q, R]
    total_is_zero = jnp.all(dev.total_resources == 0)
    demand_costs = _policy_cost(dev, constrained)
    fair_share, demand_capped, uncapped = _policy_fair_shares(
        dev, demand_costs, total_is_zero
    )
    budgets = jnp.where(
        dev.queue_weight > 0, demand_capped / _f(dev.queue_weight), jnp.inf
    )

    carry = Carry(
        alloc=jnp.asarray(dev.alloc0, jnp.int32),
        qalloc=_f(dev.queue_alloc0),
        qpc_alloc=jnp.zeros((Q, C, R), fdt),
        job_node=jnp.asarray(dev.job_node, jnp.int32),
        job_prio=jnp.asarray(dev.job_prio, jnp.int32),
        job_evicted=jnp.zeros(J, bool),
        job_scheduled=jnp.zeros(J, bool),
        slot_state=jnp.zeros(S, jnp.int8),
        evict_rank=jnp.full(J, -1, jnp.int32),
        unfeasible=jnp.zeros(max(1, dev.num_key_groups), bool),
        only_ev_global=jnp.zeros((), bool),
        only_ev_queue=jnp.zeros(Q, bool),
        tokens=jnp.asarray(dev.global_tokens, fdt),
        qtokens=_f(dev.queue_tokens),
        scheduled_new=jnp.zeros(R, fdt),
        floating=jnp.sum(
            jnp.where(
                (dev.job_is_running & (dev.job_node >= 0))[:, None]
                & dev.floating_mask[None, :],
                _f(dev.job_req),
                0.0,
            ),
            axis=0,
        ),
        spot_cost=jnp.zeros(R, fdt),
        spot_price=jnp.asarray(jnp.nan, fdt),
        stop=jnp.zeros((), bool),
        loops=jnp.zeros((), jnp.int32),
    )
    # Initial per-PC allocation of running jobs.
    qseg = jnp.clip(dev.job_queue, 0, Q - 1) * C + dev.job_pc
    run_alloc = segment_sum_dense(
        jnp.where(
            (dev.job_is_running & (dev.job_queue >= 0))[:, None],
            _f(dev.job_req),
            0.0,
        ),
        qseg,
        Q * C,
    ).reshape(Q, C, R)
    carry = carry._replace(qpc_alloc=run_alloc)

    # 1. Balance eviction (NodeEvictor + gang completion).
    actual_cost = _policy_cost(dev, carry.qalloc)
    fs = jnp.maximum(demand_capped, fair_share)
    fraction = jnp.where(fs > 0, actual_cost / fs, jnp.inf)
    evict_queue = fraction > dev.protected_fraction
    qidx = jnp.clip(dev.job_queue, 0, Q - 1)
    if dev.market_driven:
        # Market mode: everything bound is evictable; price order decides
        # who returns (preempting_queue_scheduler.go:117-119).
        evict0 = (
            dev.job_is_running & (dev.job_queue >= 0) & (carry.job_node >= 0)
        )
    else:
        evict0 = (
            dev.job_is_running
            & dev.job_preemptible
            & (dev.job_queue >= 0)
            & (carry.job_node >= 0)
            & evict_queue[qidx]
        )
    evict0, gang_paths = _gang_complete_mask(dev, carry, evict0)
    carry, evict_paths = _apply_evictions(dev, dist, carry, evict0)
    carry = _assign_evict_ranks(dev, carry, budgets, dev.prefer_large)
    compactions = gang_paths + evict_paths
    return carry, budgets, fair_share, demand_capped, uncapped, compactions


def _round_finish(
    dev: DeviceRound, dist, carry, budgets, fair_share, demand_capped, uncapped,
    compactions,
):
    """Steps 3-5 after pass 1: oversubscription eviction, pass 2 and
    finalization into the result dict. `compactions` is the setup's
    path count (solver/segments.py); the dict's `compactions` adds this
    program's."""
    J = dev.job_req.shape[0]
    Q = dev.queue_weight.shape[0]

    # 3. Oversubscription eviction.
    over = _oversubscribed_mask(dev, dist, carry)
    over, gang_paths = _gang_complete_mask(dev, carry, over)
    # Back out per-round scheduled resources for re-evicted new jobs.
    sched_backout = jnp.sum(
        jnp.where((over & carry.job_scheduled)[:, None], _f(dev.job_req), 0.0),
        axis=0,
    )
    carry, evict_paths = _apply_evictions(dev, dist, carry, over)
    carry = carry._replace(scheduled_new=carry.scheduled_new - sched_backout)
    # Re-open ONLY slots whose members were just oversubscription-evicted
    # (pass 2 considers the fresh eviction set, not pass-1 leftovers).
    S_, M_ = dev.slot_members.shape
    member_mask = jnp.arange(M_)[None, :] < dev.slot_count[:, None]
    safe = jnp.clip(dev.slot_members, 0, J - 1)
    slot_all_over = jnp.all(
        jnp.where(member_mask, over[safe], True), axis=1
    ) & (dev.slot_count > 0)
    any_over = jnp.any(over)
    carry = carry._replace(
        slot_state=jnp.where(slot_all_over, jnp.int8(PENDING), carry.slot_state),
        only_ev_global=jnp.zeros((), bool),
        only_ev_queue=jnp.zeros(Q, bool),
    )
    carry = jax.lax.cond(
        any_over,
        lambda c: _assign_evict_ranks(dev, c, budgets, dev.prefer_large),
        lambda c: c,
        carry,
    )

    # 4. Pass 2: evicted only, considering priority-class priority.
    carry = jax.lax.cond(
        any_over,
        lambda c: _schedule_pass(
            dev,
            dist,
            c,
            budgets,
            include_queued=False,
            use_key_skip=False,
            consider_priority=True,
            prefer_large=dev.prefer_large,
        ),
        lambda c: c,
        carry,
    )

    # 5. Finalize.
    preempted = dev.job_is_running & carry.job_evicted
    scheduled = carry.job_scheduled & ~carry.job_evicted
    assigned = jnp.where(carry.job_evicted, NO_NODE, carry.job_node)
    return {
        "assigned_node": assigned,
        "scheduled_priority": carry.job_prio,
        "scheduled_mask": scheduled,
        "preempted_mask": preempted,
        "fair_share": fair_share,
        "demand_capped_fair_share": demand_capped,
        "uncapped_fair_share": uncapped,
        "num_loops": carry.loops,
        "spot_price": carry.spot_price,
        "compactions": compactions + gang_paths + evict_paths,
    }


def solve_impl(dev: DeviceRound, dist=LOCAL):
    carry, budgets, fair_share, demand_capped, uncapped, compactions = (
        _round_setup(dev, dist)
    )

    # 2. Pass 1: evicted + queued.
    carry = _schedule_pass(
        dev,
        dist,
        carry,
        budgets,
        include_queued=True,
        use_key_skip=True,
        consider_priority=False,
        prefer_large=dev.prefer_large,
    )
    return _round_finish(
        dev, dist, carry, budgets, fair_share, demand_capped, uncapped,
        compactions,
    )


_solve = jax.jit(solve_impl)


# ---------------------------------------------------------------------------
# Budget-aware (round-deadline) driver: the pass-1 while_loop runs as a
# sequence of jitted SEGMENTS with a host-side wall-clock check between
# them. The decision stream is identical to the fused program's (segment
# boundaries are while-iteration boundaries), so a truncated round's
# QUEUED placements are a strict prefix of the full round's; evicted
# running jobs get their pinned rebind attempt in the finish's rescue
# pass, so truncation also never preempts a running job the full round
# would have kept (truncated preemptions ⊆ full preemptions).
# ---------------------------------------------------------------------------


def _pass1_begin_impl(dev: DeviceRound):
    carry, budgets, fair_share, demand_capped, uncapped, compactions = (
        _round_setup(dev)
    )
    ptr0 = _pass_init_ptrs(dev, carry, True, True)
    carry = carry._replace(
        stop=jnp.zeros((), bool), loops=jnp.zeros((), jnp.int32)
    )
    return carry, ptr0, budgets, fair_share, demand_capped, uncapped, compactions


def _pass1_chunk_impl(dev: DeviceRound, carry, ptr, fs, segc, budgets, loop_cap):
    return _pass_segment(
        dev,
        LOCAL,
        carry,
        ptr,
        fs,
        budgets,
        loop_cap,
        include_queued=True,
        use_key_skip=True,
        consider_priority=False,
        prefer_large=dev.prefer_large,
        seg0=segc,
    )


def _normalize_window_ptrs(dev, carry, ptr, include_queued, use_key_skip):
    """Advance each pointer to its queue's next valid slot at or after it.

    The kernel's pointer invariant is "ptr rests on a valid slot or the
    queue end"; a window segment can break it when the in-window advance
    is cut at the window edge (the remaining skip happens beyond the
    gathered slots). Validity is monotone non-increasing within a pass
    (flags only set, consumption only forward), so completing the skip
    here — against the same carry — lands exactly where the full
    kernel's advance would have; for pointers already on valid slots
    this is the identity."""
    valid, _ = _slot_validity(dev, carry, include_queued, use_key_skip)
    S = valid.shape[0]
    Q = dev.queue_slot_start.shape[0]
    pos = jnp.arange(S, dtype=jnp.int32)
    seg = jnp.clip(dev.slot_queue, 0, Q - 1)
    ahead = valid & (pos >= ptr[seg])
    heads = segment_min_dense(jnp.where(ahead, pos, BIG), seg, Q)
    return jnp.where(heads < BIG, heads, dev.queue_slot_end).astype(jnp.int32)


def _pass1_norm_impl(dev: DeviceRound, carry, ptr):
    """Full-table pointer normalization between hot windows. Run before
    every gather so a window never opens on an invalid head: a cut
    in-window advance continues here in ONE full scan — crucially, a
    queue whose remaining stream is entirely invalid (tokens spent,
    only-evicted flags) jumps straight to its end instead of walking it
    window by window (the drain phase that cost 48 re-gathers on the
    first burst run)."""
    return _normalize_window_ptrs(dev, carry, ptr, True, True)


def _pass1_window_chunk_impl(
    dev_w: DeviceRound, carry, ptr, fs, segc, budgets, loop_cap, trunc
):
    """One pass-1 segment over a hot-window compacted round
    (solver/hotwindow.py): identical machinery, W-sized slot/job axes,
    plus the rewindow stop for truncated queues."""
    return _pass_segment(
        dev_w,
        LOCAL,
        carry,
        ptr,
        fs,
        budgets,
        loop_cap,
        include_queued=True,
        use_key_skip=True,
        consider_priority=False,
        prefer_large=dev_w.prefer_large,
        seg0=segc,
        window_trunc=trunc,
    )


def _finish_impl(dev: DeviceRound, carry, budgets, fair_share, demand_capped,
                 uncapped, compactions, rescue: bool):
    # Rescue pass for truncated rounds: pass 1 evicts running jobs up
    # front, so stopping it early would finalize evicted-but-never-
    # attempted jobs as PREEMPTED — mass preemption, not degradation. An
    # evicted-only pass gives every still-pending evicted slot its pinned
    # rebind attempt (evicted jobs only ever return to their own node,
    # _select_node). After a COMPLETE pass 1 no pending evicted slots
    # remain and the pass is a structural no-op — `rescue` is static
    # (only truncated rounds compile/run it), keeping the untruncated
    # host-driven round loop-for-loop identical to the fused program.
    # Rebind capacity at the truncation point is a superset of what the
    # full round's later attempts would see, so truncated preemptions
    # are a subset of the full round's.
    if rescue:
        loops0 = carry.loops
        carry = _schedule_pass(
            dev,
            LOCAL,
            carry,
            budgets,
            include_queued=False,
            use_key_skip=False,
            consider_priority=False,
            prefer_large=dev.prefer_large,
        )
        carry = carry._replace(loops=loops0 + carry.loops)
    return _round_finish(
        dev, LOCAL, carry, budgets, fair_share, demand_capped, uncapped,
        compactions,
    )


_pass1_begin = jax.jit(_pass1_begin_impl)
# The chunked carries are DONATED: each segment updates the previous
# chunk's buffers in place instead of copying the J-sized job arrays and
# the [P, N, R] allocation per chunk.
_pass1_chunk = jax.jit(_pass1_chunk_impl, donate_argnums=(1, 2, 3, 4))
_pass1_norm = jax.jit(_pass1_norm_impl, donate_argnums=(2,))
_pass1_window_chunk = jax.jit(
    _pass1_window_chunk_impl, donate_argnums=(1, 2, 3, 4)
)
_round_finish_jit = jax.jit(_finish_impl, static_argnums=(7,))


def _window_precheck(dev: DeviceRound, window, min_slots):
    """Static hot-window sizing, or None when compaction cannot pay off.

    Ws is the per-queue window in slots: the configured size rounded up
    to the kernel's head lookahead and bucketed to a power of two (one
    compiled window program per bucket, not per round). Compaction
    engages only when the window axes are strictly smaller (below half)
    than the full ones AND the slot axis clears `min_slots` — the
    host-driven driver costs a fixed ~0.1-0.2s of dispatch/sync
    overhead per round, which a mid-size round (the tracking_100k
    regression on the first measured run) cannot amortize even though
    the geometric shrink looks fine. Needs no device data, so the
    fused-vs-host-driven choice is made before anything runs."""
    if not window or int(window) <= 0:
        return None
    from .hotwindow import window_lookahead

    Q = int(dev.queue_weight.shape[0])
    S, M = (int(x) for x in dev.slot_members.shape)
    J = int(dev.job_req.shape[0])
    if S < int(min_slots):
        return None
    la = window_lookahead(dev)
    Ws = _pow2(max(int(window), la), 1)
    # Slot side below HALF (the shrink that pays); job side merely below
    # the full axis — M is the max gang width, so Q*Ws*M wildly
    # overestimates the member count of singleton-dominated windows and
    # a half-rule there would veto legitimate gang rounds.
    if 2 * Q * Ws >= S or Q * Ws * M + 1 >= J:
        return None
    return Ws, la


def _window_plan(dev: DeviceRound, carry, pre):
    """Finish the window plan against the live carry: Ep is the padded
    capacity for out-of-window evicted jobs, bucketed from the round's
    actual evicted count (one scalar device->host sync per round; the
    set only shrinks during pass 1, so the bucket holds all pass long).
    A huge evicted set can still veto compaction here — the job axis
    would not shrink."""
    if pre is None:
        return None
    Ws, la = pre
    Q = int(dev.queue_weight.shape[0])
    M = int(dev.slot_members.shape[1])
    J = int(dev.job_req.shape[0])
    n_evicted = int(np.asarray(jnp.sum(carry.evict_rank >= 0)))
    Ep = _pow2(max(n_evicted, 1), 1)
    if Q * Ws * M + Ep >= J:
        return None
    return Ws, Ep, la


# Round readback trim (solve_round(readback_rows=...)): the per-job
# decision arrays whose padded tail is inert by construction — pad rows
# are impossible jobs bound nowhere (kernel_prep.pad_device_round), so
# the solve can never move them off these fills.
_JOB_READBACK = {
    "assigned_node": NO_NODE,
    "scheduled_priority": 0,
    "scheduled_mask": False,
    "preempted_mask": False,
}
# Device-slice lengths are bucketed (sticky upward, per padded-J shape)
# so a slowly growing live-job count reuses one compiled slice program
# instead of recompiling per round — warm cycles must stay at 0 compiles
# (bench_gate GATED_TRANSFER pins that).
_READBACK_CHUNK = 16384
_readback_buckets: dict = {}


def _readback_bucket(padded_j: int, rows: int) -> int:
    need = min(padded_j, -(-max(int(rows), 1) // _READBACK_CHUNK) * _READBACK_CHUNK)
    cur = _readback_buckets.get(padded_j, 0)
    if need > cur:
        _readback_buckets[padded_j] = need
        cur = need
    return cur


def _materialize_out(out, dev, readback_rows):
    """Device outputs -> numpy, reading back only the unpadded prefix of
    the per-job decision arrays when the caller told us the live row
    count (schedulers know num_jobs; hot-window rounds their window).
    Returns (np dict for the transfer ledger, re-expand callable) — the
    ledger books the trimmed D2H traffic, then the caller re-expands to
    the padded length with the inert pad fills so every downstream
    consumer (validate_round, lease extraction, the fairness ledger)
    still sees padded-shape arrays, byte-identical to a full readback."""
    padded_j = int(dev.job_req.shape[0])
    if readback_rows is None or int(readback_rows) >= padded_j:
        return {k: np.asarray(v) for k, v in out.items()}, lambda o: o
    bucket = _readback_bucket(padded_j, readback_rows)
    np_out = {}
    for k, v in out.items():
        if k in _JOB_READBACK and getattr(v, "shape", ())[:1] == (padded_j,):
            v = v[:bucket]
        np_out[k] = np.asarray(v)

    def expand(o):
        for k, fill in _JOB_READBACK.items():
            arr = o.get(k)
            if arr is not None and arr.shape[:1] == (bucket,):
                o[k] = np.pad(
                    arr, (0, padded_j - bucket), constant_values=fill
                )
        return o

    return np_out, expand


def _note_compactions(span, out):
    """Move the solve's compaction path count (solver/segments.py) from
    its outputs onto its `solve` span: `compact_bounded` and
    `compact_fallback`, how many full-axis scatters ran on the
    compacted rows and how many on the whole axis."""
    bounded, fallback = (int(x) for x in out.pop("compactions"))
    span.attrs.update(compact_bounded=bounded, compact_fallback=fallback)


def solve_round(
    dev: DeviceRound,
    *,
    budget_s: float | None = None,
    chunk_loops: int = 1,
    window: int | None = None,
    window_min_slots: int = HOT_WINDOW_MIN_SLOTS_DEFAULT,
    profile: bool = False,
    readback_rows: int | None = None,
):
    """Run the round solve; returns numpy outputs (plus a `truncated`
    flag when budgeted and a `profile` dict on the host-driven paths).

    budget_s=None (default) runs pass 1 to completion. With a budget,
    pass 1 runs in chunks of while-loop iterations (fill loops) with the
    wall clock checkpointed between chunks; once the budget is spent the
    pass stops yielding new loops, the oversubscription repair + pass 2 +
    finalize still run (they only rebind evicted running jobs — cheap,
    and required for a committable result), and the caller gets
    `truncated=True`. The chunk size starts at `chunk_loops` (default 1:
    at most one fill loop of slack past the deadline) and adapts upward
    only while per-loop time is far below the budget, so fast serial
    regimes don't pay a host sync per iteration.

    window=W enables hot-window compaction (solver/hotwindow.py): pass 1
    runs over a gathered active set of ~W slots per queue with results
    scattered back at chunk boundaries, re-gathering (REWINDOW) whenever
    a queue's window runs low — bit-exact with the uncompacted kernel.
    Engages only when the window axes actually shrink the round AND the
    slot axis clears `window_min_slots` (`_window_precheck`); smaller
    rounds fall through to the fused program unchanged. Tests and the
    bench pass window_min_slots=0 to exercise compaction at any scale.

    profile=True forces the host-driven segmented driver even without a
    budget or window, so per-segment timings are measured. Any
    host-driven run attaches out["profile"]: wall clock per solve
    segment (setup / pass-1 / gather+scatter / finish) and pass-1 loop
    counts by kind (gang / fill / merged-fill), plus rewindow counts.

    Spans (utils/tracing.span, nested under the caller's open span): a
    `solve` span with children `solve.fused` + `solve.readback` on the
    fused path, and `solve.setup`, one `solve.pass1` per chunk run (per
    window run when compacted), `solve.gather` per window gather and per
    scatter-back, `solve.finish` and `solve.readback` on the host-driven
    one. The profile's `*_s` values are those spans' durations. The
    `solve` span carries `host_syncs` (every block_until_ready or
    np.asarray the driver makes before the readback), the rewindows and
    the loop counts on the host-driven path, and on both paths
    `compact_bounded` / `compact_fallback` (solver/segments.py), read
    in the readback.

    readback_rows (the unpadded live-job count) trims the device->host
    readback of the per-job decision arrays to that prefix — the padded
    tail is inert by construction and is re-expanded host-side, so
    callers see byte-identical padded outputs while the transfer ledger
    books only the prefix (`_materialize_out`).

    Device-resident inputs (snapshot/residency.py): `dev` may arrive
    with leaves already on device. Both paths keep the ledger honest —
    `note_up` books host (numpy) leaves only, so an already-resident
    tree books ZERO upload here, and `jax.device_put` below is a no-op
    for committed device arrays. Neither path donates `dev` (only the
    pass-1 carries are donated), so the resident buffers survive the
    solve and the next cycle delta-syncs them in place.
    """
    from ..observe import ledger as _tledger

    use_budget = bool(budget_s) and budget_s > 0
    pre = _window_precheck(dev, window, window_min_slots)
    if not use_budget and pre is None and not profile:
        # Fused single-program path (small rounds land here even with a
        # window configured), and no `truncated` key — existing
        # consumers iterate the result's array-valued keys. The transfer
        # ledger (observe/ledger.py) books the implicit dispatch upload
        # of the host arrays and the numpy materialization of the
        # outputs into whatever round ledger the caller activated.
        from .validate import maybe_assert_finite

        with tracing.span("solve", path="fused", host_syncs=1) as solve_span:
            with tracing.span("solve.fused"):
                _tledger.note_up(dev, site="solve.dispatch")
                out = _solve(dev)
                jax.block_until_ready(out)
            with tracing.span("solve.readback"):
                out, _expand = _materialize_out(out, dev, readback_rows)
                _note_compactions(solve_span, out)
                _tledger.note_down(out, site="solve.d2h")
                out = _expand(out)
                maybe_assert_finite(out, "kernel.solve_round[fused]")
        return out

    import time as _time

    syncs = 0

    def _wait(x):
        nonlocal syncs
        syncs += 1
        jax.block_until_ready(x)

    def _host(x):
        nonlocal syncs
        syncs += 1
        return np.asarray(x)

    # Per-solve transfer ledger: the host-driven driver attaches its own
    # complete up/down/donated accounting to out["profile"]["transfer"]
    # (notes also book into any outer, e.g. scheduler-round, ledger).
    with _tledger.round_ledger() as _led, tracing.span(
        "solve", path="host"
    ) as solve_span:
        deadline = _time.monotonic() + float(budget_s) if use_budget else None
        with tracing.span("solve.setup") as setup:
            # One upload: every chunk reuses the resident round tensors
            # instead of re-transferring the host arrays per segment.
            _tledger.note_up(dev, site="solve.h2d")
            dev = jax.device_put(dev)
            (carry, ptr, budgets, fair_share, demand_capped, uncapped,
             compactions) = _pass1_begin(dev)
            _wait(carry.loops)
            plan = _window_plan(dev, carry, pre)
            syncs += pre is not None  # its evicted-count readback
        fs = jnp.zeros((), bool)
        segc = jnp.zeros(3, jnp.int32)
        S = int(dev.slot_members.shape[0])
        hard_cap = 2 * S + 4
        chunk = max(1, int(chunk_loops))
        truncated = False
        rewindows = 0
        pass1, gathers = [], []

        def _adapt_chunk(t0, executed):
            # Re-check the clock roughly every budget/8 while never batching
            # more than one loop when a single loop exceeds that interval
            # (the burst regime), keeping overshoot to one fill loop.
            target = max(float(budget_s) / 8.0, 0.02)
            per_loop = (_time.monotonic() - t0) / executed
            return max(1, min(int(target / max(per_loop, 1e-7)), 4096))

        if plan is None:
            done = False
            while not done:
                with tracing.span("solve.pass1") as sp:
                    pass1.append(sp)
                    _wait(carry.loops)
                    loops = int(_host(carry.loops))
                    if bool(_host(carry.stop)) or loops >= hard_cap:
                        done = True
                    # Forward-progress floor: even a budget spent before the
                    # first loop (snapshot build ate it) runs ONE loop, so a
                    # persistently tiny budget drains the backlog instead of
                    # starving it.
                    elif (
                        deadline is not None
                        and loops > 0
                        and _time.monotonic() >= deadline
                    ):
                        truncated = done = True
                    else:
                        cap = (
                            hard_cap if deadline is None
                            else min(loops + chunk, hard_cap)
                        )
                        t0 = _time.monotonic()
                        # The chunk donates its carries: device buffers
                        # updated in place, not re-uploaded — booked on the
                        # donated side of the ledger so the copied-vs-donated
                        # split is real.
                        _tledger.note_donated(
                            (carry, ptr, fs, segc), site="pass1.chunk"
                        )
                        carry, ptr, fs, segc = _pass1_chunk(
                            dev, carry, ptr, fs, segc, budgets, jnp.int32(cap)
                        )
                        _wait(carry.loops)
                        executed = max(1, int(_host(carry.loops)) - loops)
                        if deadline is not None:
                            chunk = _adapt_chunk(t0, executed)
        else:
            from .hotwindow import gather_window, scatter_back

            Ws, Ep, lookahead = plan
            Q = int(dev.queue_weight.shape[0])
            done = False
            while not done:
                with tracing.span("solve.gather") as sp:
                    gathers.append(sp)
                    ptr = _pass1_norm(dev, carry, ptr)
                    win_base = ptr
                    dev_w, carry_w, ptr_w, trunc, win_len, sidx, jidx = (
                        gather_window(dev, carry, ptr, Ws, Ep)
                    )
                    trunc_np = _host(trunc)
                    end_np = np.arange(Q) * Ws + _host(win_len)
                with tracing.span("solve.pass1") as sp:
                    pass1.append(sp)
                    while True:
                        _wait(carry_w.loops)
                        loops = int(_host(carry_w.loops))
                        stop = bool(_host(carry_w.stop))
                        short = (end_np - _host(ptr_w)) < lookahead
                        rewind = (not stop) and bool(np.any(trunc_np & short))
                        if stop or loops >= hard_cap:
                            done = True
                            break
                        if rewind:
                            break
                        if (
                            deadline is not None
                            and loops > 0
                            and _time.monotonic() >= deadline
                        ):
                            truncated = True
                            done = True
                            break
                        cap = (
                            hard_cap if deadline is None
                            else min(loops + chunk, hard_cap)
                        )
                        t0 = _time.monotonic()
                        _tledger.note_donated(
                            (carry_w, ptr_w, fs, segc), site="pass1.window_chunk"
                        )
                        carry_w, ptr_w, fs, segc = _pass1_window_chunk(
                            dev_w, carry_w, ptr_w, fs, segc, budgets,
                            jnp.int32(cap), trunc,
                        )
                        _wait(carry_w.loops)
                        executed = max(1, int(_host(carry_w.loops)) - loops)
                        if deadline is not None:
                            chunk = _adapt_chunk(t0, executed)
                with tracing.span("solve.gather") as sp:
                    gathers.append(sp)
                    # scatter_back donates the full carry (in-place window
                    # row writes — hot-window's whole point).
                    _tledger.note_donated(carry, site="scatter_back")
                    carry, ptr = scatter_back(
                        carry, carry_w, ptr_w, sidx, jidx, win_base, Ws
                    )
                    if done:
                        _wait(carry.loops)
                if not done:
                    rewindows += 1

        with tracing.span("solve.finish") as finish:
            out = _round_finish_jit(
                dev, carry, budgets, fair_share, demand_capped, uncapped,
                compactions, truncated,
            )
            _wait(out["num_loops"])
            seg_np = _host(segc)
        with tracing.span("solve.readback"):
            out, _expand = _materialize_out(out, dev, readback_rows)
            _note_compactions(solve_span, out)
            _tledger.note_down(out, site="solve.d2h")
            out = _expand(out)
            # ARMADA_DEBUG_FINITE=1 debug net: name the first non-finite
            # output array at the seam it left the device, before any
            # downstream consumer can launder the NaN into a placement.
            from .validate import maybe_assert_finite

            maybe_assert_finite(out, "kernel.solve_round[host-driven]")
        loop_mix = {
            "gang_loops": int(seg_np[SEG_GANG]),
            "fill_loops": int(seg_np[SEG_FILL]),
            "merged_fill_loops": int(seg_np[SEG_MERGED]),
        }
        solve_span.attrs.update(
            host_syncs=syncs, rewindows=rewindows, pass1_runs=len(pass1),
            gathers=len(gathers), **loop_mix,
        )
        if use_budget:
            out["truncated"] = truncated
        out["profile"] = {
            "setup_s": round(setup.duration_s, 4),
            "pass1_s": round(sum(s.duration_s for s in pass1), 4),
            "gather_s": round(sum(s.duration_s for s in gathers), 4),
            "finish_s": round(finish.duration_s, 4),
            **loop_mix,
            "compacted": plan is not None,
            "window_slots": int(plan[0]) if plan else 0,
            "rewindows": rewindows,
            # The solve's own complete transfer accounting
            # (observe/ledger.py): bytes/arrays up and down plus the
            # donated-buffer traffic the chunked drivers avoided.
            "transfer": _led.as_dict(),
        }
        return out
