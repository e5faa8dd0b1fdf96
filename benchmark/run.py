"""Run one benchmark cell once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The last line of standard output is one
JSON object: `correct`, `attempted`, `failed`, `metrics` (the cell's
end-to-end metrics with `--trace 0`, its per-layer metrics with
`--trace 1`), `device`, with `--trace 1` a `breakdown`, and last `check`,
each number compared with its limit. The same numbers are the last lines
of standard error. Earlier lines report the set-up. With no accelerator,
or fewer chips than the cell asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
    from benchmark.harness import NoAccelerator, run_cell

    checks = []
    try:
        out = run_cell(
            ROOT, args.workload, args.seed, args.seconds, bool(args.trace),
            log=checks.append,
        )
    except NoAccelerator as e:
        print(f"no result: {e}", file=sys.stderr, flush=True)
        return 2
    sys.stdout.flush()
    for line in checks:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # The served runner's gRPC threads must not hold the exit.
    os._exit(code)
