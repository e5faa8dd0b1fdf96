"""The served runner on a tiny cell, on the CPU: the whole control plane
over gRPC is correct when sound, and not correct when the solve's answer
is broken where it is produced."""

import json

import pytest

from benchmark_tiny import FAULTS, plant_fault


def test_benchmark_served_is_correct(tiny_run):
    out, _ = tiny_run("tiny.served", seed=3 * 2**31 + 1, seconds=2.0)
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "lease_p50_s", "lease_p95_s"}
    assert 0 < out["metrics"]["lease_p50_s"]["value"] <= out["metrics"]["lease_p95_s"]["value"]
    assert list(out)[-1] == "check"
    for name in ("placement_divergences", "lease_mismatches", "unleased", "failovers"):
        assert out["check"][name]["limit"] == 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_benchmark_served_fault_is_not_correct(tiny_run, monkeypatch, fault):
    plant_fault(monkeypatch, fault)
    out, _ = tiny_run("tiny.served", seconds=2.0)
    assert not out["correct"]
    # A broken answer that the admission firewall refuses is solved again
    # by a fallback rung: a failover, which is not correct either.
    check = out["check"]
    assert check["placement_divergences"]["value"] + check["failovers"]["value"] > 0


def test_benchmark_served_fill_preempts(tiny_root, tiny_run, capsys):
    """A fleet that the fill and the backlog hold full of preemptible
    jobs, and urgent arrivals: set-up waits for the fill, arrivals lease
    by preempting, and the compared rounds hold preemptions that the
    reference makes too."""
    from benchmark_tiny import add_cell, tiny_configs

    config = tiny_configs()["tiny-fleet"]
    config["name"] = "tiny-full"
    config["fill"] = {
        "count": 600, "priority_class": "low", "runtime_s": 900.0,
        "until_fraction": 0.7,
        "sizes": [{"share": 1, "cpu": "8", "memory": "64Gi"}],
    }
    # Long enough a warm-up for the round shapes to settle once the
    # backlog is leased.
    mix = tiny_configs()["tiny-served"]
    mix.update(warmup_s=15.0, compare_rounds=3)
    add_cell(tiny_root, "tiny.full", config, mix, ["lease_p50_s", "lease_p95_s"])
    out, _ = tiny_run("tiny.full", seed=5 * 2**31 + 3, seconds=3.0)
    lines = {line.split(" ", 1)[0]: line.split(" ", 1)[1]
             for line in capsys.readouterr().out.splitlines() if " {" in line}
    setup, ref = json.loads(lines["setup"]), json.loads(lines["reference"])
    assert setup["fill_leased_cpu"] >= 0.7 * 2 * 20 * 128
    assert ref["preempted"] > 0 and ref["preempting_leases"] > 0
    assert out["correct"], out["check"]
    assert out["failed"] == 0


def _served():
    from benchmark.harness import Cell
    from benchmark_tiny import REPO

    return Cell(REPO, "fleet.served").runner()


@pytest.mark.parametrize("cpu,fleet", [("8", 640.0), ("0.5", 40.0), ("500m", 40.0),
                                       ("1e1", 800.0), (4, 320.0)])
def test_benchmark_served_fleet_cpu(cpu, fleet):
    """The fleet's CPU, from executors x nodes x each node's CPU quantity,
    in any of the quantity's forms."""
    config = {"executors": {"count": 4, "nodes": 20, "cpu": cpu}}
    assert _served().fleet_cpu(config) == fleet


def test_benchmark_served_fill_jobs_same_work_on_every_seed():
    """The fill: `count` jobs over the queues in exact counts and exact
    shares of its sizes; a seed changes only their order."""
    from benchmark import generate
    from benchmark_tiny import tiny_configs

    served = _served()
    config = tiny_configs()["tiny-fleet"]
    config["fill"] = {
        "count": 101, "priority_class": "low", "runtime_s": 900.0, "until_fraction": 0.5,
        "sizes": [{"share": 1, "cpu": "8", "memory": "64Gi"},
                  {"share": 3, "priority_class": "high", "cpu": "2", "memory": "8Gi"}],
    }

    def kinds(seed):
        out = {}
        for queue, jobs in served.fill_jobs(config, seed):
            for j in jobs:
                assert j["priority"] == config["backlog"]["priority"]
                key = (queue, j["priority_class"], j["requests"]["cpu"])
                out[key] = out.get(key, 0) + 1
        return out

    a = kinds(1)
    assert a == kinds(2**33 + 7)
    assert sum(a.values()) == 101
    for q in range(10):
        queue = f"queue-{q:02d}"
        n = a[(queue, "low", "8")] + a[(queue, "high", "2")]
        assert n == (11 if q == 0 else 10)
        assert [a[(queue, "low", "8")], a[(queue, "high", "2")]] == list(
            generate.exact_counts(n, [1, 3]))


def test_benchmark_served_preemptions_counts_leases_onto_preempting_nodes():
    """A round that preempts two jobs on node 1 and places three jobs, two
    of them on node 1: two preempted, two preempting leases."""
    from types import SimpleNamespace

    import numpy as np

    snap = SimpleNamespace(num_jobs=6, job_node=np.array([1, 1, 0, -1, -1, -1, -1]))
    out = {
        "preempted_mask": np.array([True, True, False, False, False, False, True]),
        "scheduled_mask": np.array([False, False, False, True, True, True, False]),
        "assigned_node": np.array([-1, -1, 0, 1, 1, 0, -1]),
    }
    assert _served().preemptions(snap, out) == (2, 2)
    out["preempted_mask"] = np.zeros(7, bool)
    assert _served().preemptions(snap, out) == (0, 0)
