"""The served runner on a tiny cell, on the CPU: the whole control plane
over gRPC is correct when sound, and not correct when the solve's answer
is broken where it is produced."""

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
