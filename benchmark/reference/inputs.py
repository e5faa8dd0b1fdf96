"""The reference's input, built from the benchmark's own record.

`build_round` turns plain columns (the nodes with their capacity, the
queues with their weights, and every job of the round with what was
submitted for it and whether and where it runs) into the round the
oracle reads: the node table with its allocatable per priority row, the
job table with its within-queue order, the queue aggregates and the gang
table. It reads the deployment's settings (priority classes, resource
types, indexed resources) from the SchedulingConfig, and nothing that
the program computed, so a fault in the program's own snapshot
construction shows as a divergence from the reference.

It covers what the benchmark's deployments use: one pool, no taints,
selectors, affinities, away scheduling, floating resources, retries or
market pricing.
"""

from __future__ import annotations

import math
from fractions import Fraction

import numpy as np

EVICTED_PRIORITY = -1
NO_NODE = -1
NO_GANG = -1

_BINARY = {"Ki": 2**10, "Mi": 2**20, "Gi": 2**30, "Ti": 2**40, "Pi": 2**50, "Ei": 2**60}
_DECIMAL = {"m": Fraction(1, 1000), "k": 10**3, "M": 10**6, "G": 10**9,
            "T": 10**12, "P": 10**15, "E": 10**18}


def quantity(value) -> Fraction:
    """A Kubernetes quantity ("100m", "8Gi", "2", 1.5) as an exact Fraction."""
    if isinstance(value, (int, np.integer)):
        return Fraction(int(value))
    if isinstance(value, float):
        return Fraction(str(value))
    s = str(value).strip()
    for suffix, mult in _BINARY.items():
        if s.endswith(suffix):
            return Fraction(s[: -len(suffix)]) * mult
    if s[-1] in _DECIMAL:
        return Fraction(s[:-1]) * _DECIMAL[s[-1]]
    if "e" in s or "E" in s:
        head, _, exp = s.partition("e" if "e" in s else "E")
        return Fraction(head) * Fraction(10) ** int(exp)
    return Fraction(s)


class Resources:
    """The deployment's resource columns: each quantity is stored in
    units of 10**scale, where the scale is the power of ten of the
    column's configured resolution."""

    def __init__(self, config):
        self.names = [t.name for t in config.supported_resource_types]
        self.scales = []
        for t in config.supported_resource_types:
            r = quantity(t.resolution)
            self.scales.append(-3 if r <= 0 else math.floor(math.log10(float(r))))
        self.name_to_index = {n: i for i, n in enumerate(self.names)}
        self._memo: dict = {}

    @property
    def num_resources(self) -> int:
        return len(self.names)

    def encode(self, requests: dict, *, ceil: bool) -> np.ndarray:
        key = (tuple(sorted(requests.items())), ceil)
        row = self._memo.get(key)
        if row is None:
            row = np.zeros(self.num_resources, dtype=np.int64)
            for name, qty in requests.items():
                i = self.name_to_index[name]
                v = quantity(qty) / Fraction(10) ** self.scales[i]
                row[i] = math.ceil(v) if ceil else math.floor(v)
            self._memo[key] = row
        return row

    def encode_all(self, rows, *, ceil: bool) -> np.ndarray:
        """One row per mapping; mappings shared between jobs (as a
        traffic mix's sizes are) are encoded once."""
        rows = list(rows)
        seen: dict = {}
        which = np.empty(len(rows), dtype=np.int64)
        table = []
        for i, requests in enumerate(rows):
            k = seen.get(id(requests))
            if k is None:
                k = seen[id(requests)] = len(table)
                table.append(self.encode(requests, ceil=ceil))
            which[i] = k
        if not table:
            return np.zeros((0, self.num_resources), dtype=np.int64)
        return np.stack(table)[which]


class Round:
    """One round as the oracle reads it."""

    def __init__(self, **fields):
        self.__dict__.update(fields)

    @property
    def num_nodes(self) -> int:
        return len(self.node_ids)

    @property
    def num_jobs(self) -> int:
        return len(self.job_ids)

    @property
    def num_queues(self) -> int:
        return len(self.queue_names)

    @property
    def num_gangs(self) -> int:
        return len(self.gang_card)

    @property
    def num_priorities(self) -> int:
        return len(self.priorities)

    def priority_row(self, priority: int) -> int:
        return int(np.flatnonzero(self.priorities == priority)[0])

    def job_req_fit(self) -> np.ndarray:
        return self.job_req

    def drf_multipliers(self) -> np.ndarray:
        mult = np.zeros(self.factory.num_resources, dtype=np.float64)
        for name, m in self.config.dominant_resource_fairness_resources.items():
            i = self.factory.name_to_index.get(name)
            if i is not None:
                mult[i] = m if m > 0 else 1.0
        return mult


def build_round(config, pool, nodes, queues, jobs, *,
                global_rate_tokens=None, queue_rate_tokens=None) -> Round:
    """nodes: [(node id, {resource: quantity})]. queues: [(name, weight)].
    jobs: columns of equal length: `id`, `queue` (name), `priority_class`,
    `priority` (the submitted job priority), `submitted` (submit time, or
    any number in the same order), `requests` ({resource: quantity}),
    `node` (the node id it runs on, "" when queued), `running_priority`
    (the priority it was scheduled at; ignored when queued), `gang_id`
    ("" for none) and `gang_card`. Rate-limit tokens: None is a full
    burst."""
    res = Resources(config)
    R = res.num_resources
    classes = config.priority_classes
    levels = {EVICTED_PRIORITY} | {int(pc.priority) for pc in classes.values()}
    for pc in classes.values():
        levels |= {int(a.priority) for a in pc.away_node_types}
    priorities = np.asarray(sorted(levels), dtype=np.int32)
    P = len(priorities)

    node_ids = [n for n, _ in nodes]
    node_index = {n: i for i, n in enumerate(node_ids)}
    N = len(node_ids)
    node_total = res.encode_all([r for _, r in nodes], ceil=False)
    node_id_rank = np.argsort(np.argsort(np.asarray(node_ids))).astype(np.int32)

    queue_names = [q for q, _ in queues]
    queue_weight = np.asarray([w for _, w in queues], dtype=np.float64)
    queue_index = {q: i for i, q in enumerate(queue_names)}
    Q = len(queue_names)

    ids = list(jobs["id"])
    J = len(ids)
    job_req = res.encode_all(jobs["requests"], ceil=True)
    job_queue = np.asarray([queue_index.get(q, -1) for q in jobs["queue"]],
                           dtype=np.int32)
    pc_names = [pc if pc in classes else config.default_priority_class
                for pc in jobs["priority_class"]]
    job_preemptible = np.asarray([classes[pc].preemptible for pc in pc_names],
                                 dtype=bool)
    job_node = np.asarray([node_index.get(n, NO_NODE) if n else NO_NODE
                           for n in jobs["node"]], dtype=np.int32)
    job_is_running = np.asarray([bool(n) for n in jobs["node"]], dtype=bool)
    job_priority = np.where(
        job_is_running,
        np.asarray(jobs["running_priority"], dtype=np.int64),
        np.asarray([classes[pc].priority for pc in pc_names], dtype=np.int64),
    ).astype(np.int32)

    # Within-queue order: submitted job priority, then submit time, then id.
    perm = np.lexsort((
        np.asarray(ids),
        np.asarray(jobs["submitted"], dtype=np.float64),
        np.asarray(jobs["priority"], dtype=np.int64),
    ))
    job_order = np.empty(J, dtype=np.int64)
    job_order[perm] = np.arange(J)

    # Running jobs hold their requests on their node: a preemptible job
    # at and below its priority row, a non-preemptible one at every row.
    allocatable = np.broadcast_to(node_total, (P, N, R)).copy()
    bound = job_is_running & (job_node >= 0)
    for p in range(P):
        held = bound & (~job_preemptible | (priorities[p] <= job_priority))
        np.subtract.at(allocatable[p], job_node[held], job_req[held])

    queue_allocated = np.zeros((Q, R), dtype=np.int64)
    queue_demand = np.zeros((Q, R), dtype=np.int64)
    valid = job_queue >= 0
    np.add.at(queue_demand, job_queue[valid], job_req[valid])
    run = valid & job_is_running
    np.add.at(queue_allocated, job_queue[run], job_req[run])

    # Gangs: queued members of a gang (cardinality above 1) share a row;
    # every other job is a gang of one.
    gang_ids = list(jobs["gang_id"])
    cards = np.asarray(jobs["gang_card"], dtype=np.int64)
    member = (cards > 1) & ~job_is_running
    singles = np.flatnonzero(~member).astype(np.int32)
    groups: dict = {}
    for j in np.flatnonzero(member):
        groups.setdefault((int(job_queue[j]), gang_ids[j]), []).append(int(j))
    S = len(singles)
    G = S + len(groups)
    job_gang = np.full(J, NO_GANG, dtype=np.int32)
    job_gang[singles] = np.arange(S, dtype=np.int32)
    gang_queue = np.zeros(G, dtype=np.int32)
    gang_card = np.ones(G, dtype=np.int32)
    gang_total_req = np.zeros((G, R), dtype=np.int64)
    gang_order = np.zeros(G, dtype=np.int64)
    gang_complete = np.ones(G, dtype=bool)
    gang_member_offsets = np.arange(G + 1, dtype=np.int32)
    gang_queue[:S] = job_queue[singles]
    gang_total_req[:S] = job_req[singles]
    gang_order[:S] = job_order[singles]
    members_flat = list(singles)
    for gi, ((q, _), members) in enumerate(groups.items()):
        g = S + gi
        members.sort(key=lambda m: job_order[m])
        job_gang[members] = g
        members_flat.extend(members)
        gang_member_offsets[g + 1] = len(members_flat)
        gang_queue[g] = q
        gang_card[g] = cards[members[0]]
        gang_total_req[g] = job_req[members].sum(axis=0)
        gang_order[g] = job_order[members].max()
        gang_complete[g] = len(members) == cards[members[0]]

    order_idx, order_res = [], []
    for name, resolution in config.indexed_resources.items():
        i = res.name_to_index.get(name)
        if i is None:
            continue
        order_idx.append(i)
        order_res.append(max(1, int(quantity(resolution) / Fraction(10) ** res.scales[i])))

    C = len(classes)
    K = max(1, int(config.max_retries))
    return Round(
        config=config,
        factory=res,
        pool=pool,
        priorities=priorities,
        node_ids=node_ids,
        allocatable=allocatable,
        node_total=node_total,
        node_taint_bits=np.zeros((N, 1), dtype=np.uint32),
        node_label_bits=np.zeros((N, 1), dtype=np.uint32),
        node_id_rank=node_id_rank,
        node_unschedulable=np.zeros(N, dtype=bool),
        order_res_idx=np.asarray(order_idx, dtype=np.int32),
        order_res_resolution=np.asarray(order_res, dtype=np.int64),
        queue_names=queue_names,
        queue_weight=queue_weight,
        queue_cordoned=np.zeros(Q, dtype=bool),
        queue_allocated=queue_allocated,
        queue_demand=queue_demand,
        queue_short_penalty=np.zeros((Q, R), dtype=np.int64),
        job_ids=ids,
        job_req=job_req,
        job_tolerated=np.zeros((J, 1), dtype=np.uint32),
        job_selector=np.zeros((J, 1), dtype=np.uint32),
        job_possible=np.ones(J, dtype=bool),
        job_queue=job_queue,
        job_priority=job_priority,
        job_preemptible=job_preemptible,
        job_is_running=job_is_running,
        job_away=np.zeros(J, dtype=bool),
        job_node=job_node,
        job_order=job_order,
        job_excluded_nodes=np.full((J, K), -1, dtype=np.int32),
        job_affinity_group=np.full(J, -1, dtype=np.int32),
        affinity_allowed=np.zeros((1, max(1, (N + 31) // 32)), dtype=np.uint32),
        job_gang=job_gang,
        job_gang_id=gang_ids,
        job_pc_name=pc_names,
        job_bid=np.zeros(J, dtype=np.float64),
        job_bid_running=np.zeros(J, dtype=np.float64),
        gang_queue=gang_queue,
        gang_card=gang_card,
        gang_member_offsets=gang_member_offsets,
        gang_members=np.asarray(members_flat, dtype=np.int32),
        gang_total_req=gang_total_req,
        gang_order=gang_order,
        gang_complete=gang_complete,
        gang_uniformity_key=[""] * G,
        pc_names=list(classes),
        pc_away_count=np.zeros(C, dtype=np.int32),
        pc_away_prio=np.zeros((C, 1), dtype=np.int32),
        pc_away_tol=np.zeros((C, 1, 1), dtype=np.uint32),
        label_vocab=None,
        global_rate_tokens=global_rate_tokens,
        queue_rate_tokens=queue_rate_tokens,
        total_resources=node_total.sum(axis=0),
        floating_mask=np.zeros(R, dtype=bool),
        floating_total=np.zeros(R, dtype=np.int64),
        queue_deadline=None,
    )
