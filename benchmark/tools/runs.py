"""Run benchmark cells one after another, each in a process of its own,
and summarise them: the measurements behind BENCHMARK.json's bounds and
PERF.md's readings.

    python3 benchmark/tools/runs.py --out .bench/sets \
        --budget-s 3300 fleet.served:A:3000000001:51:0 ...

Each run is `<cell>:<label>:<seed>:<seconds>:<trace>[:<ENV=value>,...]`.
It writes `<out>/<cell>_<label>_<seed>.{out,err}` and prints two lines:
`RUN ...` with the set-up, window and reference report lines, and
`LINE <cell>:<label> <seed> <result line>` for `spread.py`. A run that
would end past the budget is skipped. `--stop-unless-correct` stops
after the first run that is not correct or whose window compiled.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--budget-s", type=float, default=3300.0)
    ap.add_argument("--run-limit-s", type=float, default=1250.0)
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--stop-unless-correct", action="store_true")
    ap.add_argument("runs", nargs="+")
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    t_start = time.monotonic()
    expected = 0.0
    for spec in args.runs:
        parts = spec.split(":")
        cell, label, seed, seconds, trace = parts[:5]
        env = dict(os.environ)
        for kv in (parts[5].split(",") if len(parts) > 5 and parts[5] else []):
            k, _, v = kv.partition("=")
            env[k] = v
        if time.monotonic() - t_start + max(expected, 120.0) > args.budget_s:
            print(f"SKIP {cell} {label} {seed}", flush=True)
            continue
        stem = os.path.join(args.out, f"{cell}_{label}_{seed}")
        t0 = time.monotonic()
        with open(stem + ".out", "w") as out, open(stem + ".err", "w") as err:
            try:
                rc = subprocess.run(
                    [sys.executable, "benchmark/run.py", "--workload", cell,
                     "--seed", seed, "--seconds", seconds, "--trace", trace],
                    cwd=args.root, env=env, stdout=out, stderr=err,
                    timeout=args.run_limit_s,
                ).returncode
            except subprocess.TimeoutExpired:
                rc = 124
        wall = time.monotonic() - t0
        expected = wall
        with open(stem + ".out") as f:
            lines = f.read().splitlines()
        report = " ".join(
            line for line in lines if line.split(" ", 1)[0] in ("setup", "window", "reference")
        )
        print(f"RUN {cell} {label} seed={seed} rc={rc} wall={wall:.0f} {report[:900]}",
              flush=True)
        last = lines[-1] if lines else ""
        print(f"LINE {cell}:{label} {seed} {last[:3000]}", flush=True)
        if args.stop_unless_correct:
            try:
                result = json.loads(last)
            except ValueError:
                result = {}
            if not result.get("correct") and "X64=0" not in spec:
                print("STOP: not correct", flush=True)
                return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
