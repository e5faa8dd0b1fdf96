"""Measure kernel parity of the x64-OFF FALLBACK mode (float32 costs).

The SHIPPED solver configuration enables x64
(utils/platform.enable_exact_costs): every large tensor is explicitly
int32/uint32, so x64 only widens the Q-sized cost vectors to float64 —
measured free — and placement parity with the float64 host oracle is then
exact (the whole x64 parity suite is the proof). This tool quantifies the
OPT-OUT configuration (ARMADA_TPU_X64=0: float32 cost keys), where ties
can resolve differently (kernel.py parity notes). It sweeps the
production-shaped big_scenario populations (and a market-mode sweep
covering the spot-price money path) comparing the float32 kernel against
the float64 host oracle, and prints one JSON line:

  {"scenarios": N, "placement_mismatch_jobs": ..., "sched_set_diffs": ...,
   "max_fair_share_err": ..., "spot_price_max_err": ...}

Run (x64 must stay off — do NOT run under pytest/conftest):
  PYTHONPATH=/root/repo JAX_PLATFORMS=cpu python tools/float32_parity.py
Results are recorded in docs/parity.md.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# Deliberately NOT utils/platform.setup_jax: that enables x64, and this
# tool measures the x64-OFF fallback.
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

import jax  # noqa: E402

assert not jax.config.jax_enable_x64, "run without conftest (x64 must be off)"

from armada_tpu.core.config import PriorityClass, SchedulingConfig  # noqa: E402
from armada_tpu.core.types import JobSpec, NodeSpec, QueueSpec  # noqa: E402
from armada_tpu.snapshot.round import build_round_snapshot  # noqa: E402
from armada_tpu.solver.kernel import solve_round  # noqa: E402
from armada_tpu.solver.kernel_prep import (  # noqa: E402
    pad_device_round,
    prep_device_round,
)
from armada_tpu.solver.reference import ReferenceSolver  # noqa: E402

sys.path.insert(
    0,
    os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests"
    ),
)
from test_parity_scale import CFG, big_scenario  # noqa: E402


def compare(cfg, nodes, queues, running, queued, stats, **snap_kw):
    snap = build_round_snapshot(
        cfg, "default", nodes, queues, running, queued, **snap_kw
    )
    oracle = ReferenceSolver(snap).solve()
    out = solve_round(pad_device_round(prep_device_round(snap)))
    J = snap.num_jobs
    Q = snap.num_queues
    k_nodes = np.asarray(out["assigned_node"])[:J]
    k_sched = np.asarray(out["scheduled_mask"])[:J]
    k_preempt = np.asarray(out["preempted_mask"])[:J]
    stats["scenarios"] += 1
    stats["jobs"] += int(J)
    stats["placement_mismatch_jobs"] += int(
        (oracle.assigned_node != k_nodes).sum()
    )
    stats["sched_set_diffs"] += int((oracle.scheduled_mask != k_sched).sum())
    stats["preempt_set_diffs"] += int((oracle.preempted_mask != k_preempt).sum())
    stats["max_fair_share_err"] = max(
        stats["max_fair_share_err"],
        float(
            np.abs(
                oracle.demand_capped_fair_share
                - np.asarray(out["demand_capped_fair_share"])[:Q]
            ).max()
        ),
    )
    if out.get("spot_price") is not None and oracle.spot_price is not None:
        stats["spot_price_max_err"] = max(
            stats["spot_price_max_err"],
            abs(float(out["spot_price"]) - float(oracle.spot_price)),
        )
    return snap


def market_scenario(seed, n_nodes=64, n_jobs=400):
    """Market mode: bid-ordered scheduling + Vickrey spot price — the
    money-ordering path (solver/pricer.py) where float32 accumulation
    could reorder bids or shift the spot price."""
    rng = np.random.default_rng(seed)
    cfg = SchedulingConfig(
        priority_classes={"d": PriorityClass("d", 1000, preemptible=True)},
        default_priority_class="d",
        market_driven=True,
    )
    nodes = [
        NodeSpec(
            id=f"n{i:04d}",
            pool="default",
            total_resources={"cpu": "16", "memory": "64Gi"},
        )
        for i in range(n_nodes)
    ]
    queues = [QueueSpec(f"q{i}", 1.0) for i in range(4)]
    bids = np.round(rng.uniform(0.01, 10.0, size=n_jobs), 4)
    queued = [
        JobSpec(
            id=f"j{i:05d}",
            queue=f"q{i % 4}",
            requests={
                "cpu": str(int(rng.choice([1, 2, 4]))),
                "memory": "2Gi",
            },
            submitted_ts=float(i),
            bid_prices={"default": float(bids[i])},
        )
        for i in range(n_jobs)
    ]
    return cfg, nodes, queues, [], queued


def main():
    stats = {
        "x64": bool(jax.config.jax_enable_x64),
        "scenarios": 0,
        "jobs": 0,
        "placement_mismatch_jobs": 0,
        "sched_set_diffs": 0,
        "preempt_set_diffs": 0,
        "max_fair_share_err": 0.0,
        "spot_price_max_err": 0.0,
    }
    for seed in range(4):
        nodes, queues, running, queued = big_scenario(
            seed, n_nodes=128, n_jobs=600
        )
        compare(CFG, nodes, queues, running, queued, stats)
    for seed in range(4):
        nodes, queues, running, queued = big_scenario(
            100 + seed, n_nodes=256, n_jobs=1200
        )
        compare(CFG, nodes, queues, running, queued, stats)
    # Market sweep: per-job bids exercise money ordering + the Vickrey
    # spot-price accumulation.
    for seed in range(4):
        cfg, nodes, queues, running, queued = market_scenario(200 + seed)
        compare(cfg, nodes, queues, running, queued, stats)
    print(json.dumps(stats))


if __name__ == "__main__":
    main()
