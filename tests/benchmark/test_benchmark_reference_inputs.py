"""The reference's own input builder (`benchmark/reference/inputs.py`)
against the program's snapshot construction on the same jobs: every
field the reference reads agrees, and so does the reference's answer.
A fault planted in the program's snapshot state (queue weights lost
between the queue records and the round) makes a tiny run of each
runner not correct, since the reference builds its round from the
benchmark's record and the deployment file."""

import numpy as np
import pytest

from benchmark import check, generate
from benchmark.reference.inputs import build_round, quantity
from benchmark_tiny import plant_snapshot_fault

FIELDS = (
    "priorities", "node_ids", "allocatable", "node_total", "node_id_rank",
    "order_res_idx", "order_res_resolution", "queue_names", "queue_weight",
    "queue_allocated", "queue_demand", "job_ids", "job_req", "job_queue",
    "job_priority", "job_preemptible", "job_is_running", "job_node", "job_order",
    "job_gang", "job_gang_id", "job_pc_name", "gang_queue", "gang_card",
    "gang_member_offsets", "gang_members", "gang_total_req", "gang_order",
    "gang_complete", "total_resources",
)


def _scenario(seed):
    """A small round: two priority classes, running jobs of both, queued
    jobs of mixed sizes and job priorities, one complete gang and one
    incomplete gang; submit times with ties."""
    from armada_tpu.core.types import Gang, JobSpec, RunningJob

    config = {
        "pool": "default",
        "nodes": {"count": 12, "prefix": "node-", "cpu": "16", "memory": "64Gi"},
        "queues": {"count": 4, "prefix": "queue-",
                   "priority_factors": [1.0, 0.5, 1.0, 0.5]},
        "priority_classes": {"high": {"priority": 30000, "preemptible": False},
                             "low": {"priority": 1000, "preemptible": True}},
        "default_priority_class": "low",
        "protected_fraction_of_fair_share": 1.0,
        "rate_limits": {"global_rate": 100.0, "global_burst": 40,
                        "queue_rate": 50.0, "queue_burst": 15},
    }
    rng = np.random.default_rng(seed)
    names = generate.queue_names(config)
    sizes = [("1", "2Gi"), ("2", "8Gi"), ("500m", "1536Mi"), ("4", "4Gi")]
    nodes = generate.node_table(config)
    running, queued = [], []
    for i in range(20):
        cpu, mem = sizes[rng.integers(len(sizes))]
        pc = "high" if i % 3 == 0 else "low"
        running.append(RunningJob(
            job=JobSpec(id=f"run-{i:03d}", queue=names[rng.integers(4)],
                        priority_class=pc, requests={"cpu": cpu, "memory": mem},
                        submitted_ts=float(-i)),
            node_id=nodes[rng.integers(len(nodes))][0],
            scheduled_at_priority=30000 if pc == "high" else 1000,
        ))
    for i in range(120):
        cpu, mem = sizes[rng.integers(len(sizes))]
        queued.append(JobSpec(
            id=f"job-{rng.integers(10**6):06d}-{i:03d}", queue=names[rng.integers(4)],
            priority=int(rng.integers(2)), priority_class=("high", "low")[i % 2],
            requests={"cpu": cpu, "memory": mem}, submitted_ts=float(i // 7),
        ))
    for g, card, present in (("gang-a", 4, 4), ("gang-b", 3, 2)):
        for k in range(present):
            queued.append(JobSpec(
                id=f"{g}-{k}", queue=names[1], priority_class="low",
                requests={"cpu": "2", "memory": "4Gi"}, submitted_ts=50.0 + k,
                gang=Gang(id=g, cardinality=card),
            ))
    return config, running, queued


def _columns(running, queued):
    jobs = [r.job for r in running] + queued
    return {
        "id": [j.id for j in jobs],
        "queue": [j.queue for j in jobs],
        "priority_class": [j.priority_class for j in jobs],
        "priority": [j.priority for j in jobs],
        "submitted": [j.submitted_ts for j in jobs],
        "requests": [j.requests for j in jobs],
        "node": [r.node_id for r in running] + [""] * len(queued),
        "running_priority": [r.scheduled_at_priority for r in running] + [0] * len(queued),
        "gang_id": [j.gang.id if j.gang else "" for j in jobs],
        "gang_card": [j.gang.cardinality if j.gang else 1 for j in jobs],
    }


@pytest.mark.parametrize("seed", [1, 2, 2**33 + 7])
def test_benchmark_reference_round_matches_the_program_snapshot(seed):
    from armada_tpu.snapshot.round import build_round_snapshot

    config, running, queued = _scenario(seed)
    cfg = generate.scheduling_config(config)
    prog = build_round_snapshot(cfg, "default", generate.nodes(config),
                                generate.queues(config), running, queued)
    ref = build_round(cfg, "default", generate.node_table(config),
                      generate.queue_weights(config), _columns(running, queued))
    for name in FIELDS:
        a, b = getattr(prog, name), getattr(ref, name)
        if isinstance(a, list):
            assert list(a) == list(b), name
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)
    assert list(prog.factory.names) == list(ref.factory.names)
    np.testing.assert_array_equal(prog.drf_multipliers(), ref.drf_multipliers())
    want = check.reference_result(prog)
    got = check.reference_result(ref)
    for k, v in want.items():
        np.testing.assert_array_equal(v, got[k], err_msg=k)
    assert want["scheduled_mask"].any()


@pytest.mark.parametrize("text,value", [
    ("100m", 0.1), ("8Gi", 8 * 2**30), ("1536Mi", 1536 * 2**20), ("2", 2),
    ("2e3", 2000), ("1.5k", 1500), (3, 3),
])
def test_benchmark_reference_quantity(text, value):
    assert quantity(text) == pytest.approx(value)


@pytest.mark.parametrize("cell", ["tiny.steady", "tiny.served"])
def test_benchmark_snapshot_fault_is_not_correct(tiny_run, monkeypatch, cell):
    plant_snapshot_fault(monkeypatch)
    out, _ = tiny_run(cell, seed=2**32 + 3, seconds=2.0)
    assert not out["correct"]
    assert out["check"]["fair_share_gap"]["value"] > out["check"]["fair_share_gap"]["limit"]
