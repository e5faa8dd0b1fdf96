"""The comparison that decides `correct`: the program's round against the
plain reference (`benchmark/reference/oracle.py`) on the same state.

Two numbers per compared round:

- `placement_divergences`: jobs whose decision differs, matched by job
  id: scheduled or not, preempted or not, the node (by node id) and the
  priority it runs at. An exact comparison; its limit is 0.
- `fair_share_gap`: the widest gap between the program's and the
  reference's per-queue fair shares (fair, demand-capped and uncapped,
  matched by queue name), over the largest reference share. The cost
  keys are float64 by configuration; a float32 computation reads about
  1e-8 here.
"""

from __future__ import annotations

import numpy as np

JOB_KEYS = ("scheduled_mask", "preempted_mask", "scheduled_priority")
SHARE_KEYS = ("fair_share", "demand_capped_fair_share", "uncapped_fair_share")


def reference_result(snap):
    from benchmark.reference.oracle import ReferenceSolver

    res = ReferenceSolver(snap).solve()
    return {
        k: np.asarray(getattr(res, k))
        for k in ("assigned_node",) + JOB_KEYS + SHARE_KEYS
    }


def _job_table(snap, out):
    """Live jobs of a snapshot by id, with their decisions; node indices
    become node ids."""
    J = snap.num_jobs
    ids = np.asarray(snap.job_ids)[:J].astype(str)
    live = np.flatnonzero(ids != "")
    node_ids = np.asarray(list(snap.node_ids) + [""], dtype=object)
    node = np.asarray(out["assigned_node"])[:J][live].astype(np.int64)
    table = {"id": ids[live], "node": node_ids[np.where(node >= 0, node, -1)]}
    for k in JOB_KEYS:
        table[k] = np.asarray(out[k])[:J][live]
    order = np.argsort(table["id"], kind="stable")
    return {k: v[order] for k, v in table.items()}


def compare_round(prog_snap, prog_out, ref_snap, ref_out) -> dict:
    a = _job_table(prog_snap, prog_out)
    b = _job_table(ref_snap, ref_out)
    if len(a["id"]) != len(b["id"]) or not np.array_equal(a["id"], b["id"]):
        common = np.intersect1d(a["id"], b["id"])
        missing = len(a["id"]) + len(b["id"]) - 2 * len(common)
        ia = np.isin(a["id"], common)
        ib = np.isin(b["id"], common)
        a = {k: v[ia] for k, v in a.items()}
        b = {k: v[ib] for k, v in b.items()}
    else:
        missing = 0
    differs = a["node"] != b["node"]
    for k in JOB_KEYS:
        differs |= a[k] != b[k]
    prog_q = {n: i for i, n in enumerate(prog_snap.queue_names)}
    gap = 0.0
    for k in SHARE_KEYS:
        want = np.asarray(ref_out[k], dtype=np.float64)[: ref_snap.num_queues]
        got = np.asarray(prog_out[k], dtype=np.float64)
        got = np.asarray(
            [got[prog_q[n]] for n in ref_snap.queue_names], dtype=np.float64
        )
        scale = float(np.max(np.abs(want))) or 1.0
        gap = max(gap, float(np.max(np.abs(got - want))) / scale)
    return {
        "placement_divergences": int(differs.sum()) + int(missing),
        "fair_share_gap": gap,
    }


def compare_to_reference(prog_snap, prog_out, ref_snap) -> dict:
    return compare_round(prog_snap, prog_out, ref_snap, reference_result(ref_snap))
