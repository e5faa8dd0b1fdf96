"""The served cell's rate sweep: one run of `fleet.served` at each
arrival rate, each in a process of its own, to find the highest rate the
system sustains (the rate a served cell then runs at four fifths of).

    python3 benchmark/tools/sweep.py --seed 3000000101 --seconds 51 20 40 60 70 80

Each run prints `SWEEP <rate> <result line>` and its `window` report,
whose `lease_p50_first_half_s` and `lease_p50_second_half_s` say whether
the queue grew through the window. The cell's mix is read from its file
and only the rate is changed, in memory.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def child(args) -> int:
    sys.path.insert(0, ROOT)
    from benchmark.harness import Cell, Run, init_jax, result_line

    cell = Cell(ROOT, args.workload)
    cell.traffic["arrivals"]["rate_per_s"] = float(args.rate)
    run = Run(cell, args.seed, args.seconds, False, os.path.join(ROOT, ".bench"))
    run.cache_log.install()
    run.devices = init_jax(cell, require_accelerator=True)
    cell.runner().run(run)
    print(json.dumps(result_line(run, lambda m: None)), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="fleet.served")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--rate", type=float)
    ap.add_argument("rates", nargs="*", type=float)
    args = ap.parse_args(argv)
    if args.child:
        return child(args)
    for rate in args.rates:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child", "--workload",
             args.workload, "--seed", str(args.seed), "--seconds", str(args.seconds),
             "--rate", str(rate)],
            cwd=ROOT, capture_output=True, text=True, timeout=1250,
        )
        lines = proc.stdout.splitlines()
        window = next((line for line in lines if line.startswith("window ")), "")
        print(f"SWEEP {rate} rc={proc.returncode} {window}", flush=True)
        print(f"SWEEP {rate} {lines[-1] if lines else proc.stderr[-2000:]}", flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
