"""Round-by-round diagnosis of the round loop: each round the program
solves, compared with the reference at once, with a second witness.

    python3 benchmark/tools/diag_rounds.py --nodes 50000 --jobs 1000000 \
        --running 200000 --rounds 8 --seed 101 [--ramp] [--cpu-witness]

It builds the flagship deployment at the given size (with `--ramp`, queue
weights 1 to 2 evenly spaced, priority factors 1 / (1 + q / (Q - 1)),
instead of the file's), runs the round loop's rounds (bind the
last leases, complete as many running jobs, submit as many new ones,
sync, solve), and after each round prints the placements that differ
from the reference on a round built by `benchmark/reference/inputs.py`
from the benchmark's own record. With `--cpu-witness`, a round that
differs is solved again by the same program on the host's CPU backend,
from the same snapshot, and that answer is compared with both.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--nodes", type=int, required=True)
    ap.add_argument("--jobs", type=int, required=True)
    ap.add_argument("--running", type=int, required=True)
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--seed", type=int, default=101)
    ap.add_argument("--ramp", action="store_true")
    ap.add_argument("--cpu-witness", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from armada_tpu.utils.platform import setup_jax

    setup_jax()
    import jax
    import numpy as np

    from armada_tpu.snapshot.incremental import IncrementalRound
    from armada_tpu.snapshot.residency import ResidentRound
    from armada_tpu.solver.kernel import solve_round
    from armada_tpu.solver.kernel_prep import pad_device_round, prep_device_round
    from benchmark import check, generate
    from benchmark.runners.round_loop import Ledger, lease_list

    with open(os.path.join(ROOT, "benchmark/configs/flagship-1m-50k.json")) as f:
        config = json.load(f)
    config["nodes"]["count"] = args.nodes
    config["backlog"]["count"] = args.jobs
    config["running"]["count"] = args.running
    if args.ramp:
        q = int(config["queues"]["count"])
        config["queues"]["priority_factors"] = [1.0 / (1.0 + i / (q - 1)) for i in range(q)]
    cfg = generate.scheduling_config(config)
    nodes = generate.nodes(config)
    running = generate.running_jobs(config, nodes, args.seed)
    stream = generate.JobStream(config["backlog"], config, args.seed, 1, "job-")
    queued = stream.take(args.jobs)
    inc = IncrementalRound(cfg, config["pool"], nodes, generate.queues(config),
                           running, queued)
    state = Ledger(running, queued)
    rng = generate.rng_for(args.seed, 5)
    resident = ResidentRound()
    period = float(config["schedule_period_s"])
    leases = []
    for k in range(args.rounds):
        n = len(leases)
        done = state.complete(n, rng)
        fresh = stream.take(n)
        inc.bind(leases)
        inc.remove_jobs(done)
        inc.add_jobs(fresh)
        state.apply(leases, done, fresh)
        out = solve_round(resident.device_round(inc), window=cfg.hot_window_slots or None,
                          window_min_slots=cfg.hot_window_min_slots,
                          readback_rows=inc._size)
        snap = inc.snapshot()
        ref_round = state.rebuild(cfg, config)
        ref = check.reference_result(ref_round)
        numbers = check.compare_round(snap, out, ref_round, ref)
        print("round", k, "placed", int(np.asarray(out["scheduled_mask"]).sum()),
              numbers, flush=True)
        if numbers["placement_divergences"]:
            a, b = check._job_table(snap, out), check._job_table(ref_round, ref)
            keys = ("node", "scheduled_mask", "preempted_mask", "scheduled_priority")
            differs = np.zeros(len(a["id"]), dtype=bool)
            for key in keys:
                differs |= a[key] != b[key]
            for i in np.flatnonzero(differs)[:12]:
                print("   ", a["id"][i], {key: (a[key][i], b[key][i]) for key in keys})
            if args.cpu_witness:
                with jax.default_device(jax.devices("cpu")[0]):
                    host = solve_round(pad_device_round(prep_device_round(snap)))
                host = {key: np.asarray(v) for key, v in host.items()}
                print("   cpu witness vs reference:",
                      check.compare_round(snap, host, ref_round, ref),
                      "vs chip:", check.compare_round(snap, host, snap, out), flush=True)
        leases = lease_list(snap, out, k * period)
    return 0


if __name__ == "__main__":
    sys.exit(main())
