"""Fairness-policy A/B: replay a corpus under candidate policies.

    python tools/policy_ab.py trace.atrace
    python tools/policy_ab.py trace.atrace --policy drf --policy priority
    python tools/policy_ab.py trace.atrace --json --rounds 20

Every non-truncated round in the bundle(s) is re-solved under each
candidate fairness policy (solver/policy.py) — the spec is swapped into
the recorded DeviceRound's static meta, so each candidate sees the
exact round inputs production saw — and scored with the live fairness
observatory's ledger + scorecard math (observe/fairness.py). The
rendered table puts the candidates side by side: Jain trajectory,
per-queue delivered share vs regret, starvation totals, preemptions.

This is the evidence the rollout runbook (docs/operations.md, "Rolling
out a fairness policy") asks for before a live flip: `armadactl policy
set` refuses a non-DRF flip without a registered shadow scorecard
unless forced. `armadactl policy ab` is the same harness behind the
CLI.

Exit codes: 0 ok, 2 unusable input (no rounds / undecodable bundle /
foreign target without --allow-foreign / unknown policy).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("traces", nargs="+", help=".atrace bundles to replay")
    ap.add_argument(
        "--policy",
        action="append",
        metavar="POLICY",
        help="candidate policy (repeatable); default: all four kinds",
    )
    ap.add_argument(
        "--solver",
        default="LOCAL",
        help="replay solver spec: LOCAL | hotwindow[:W] | 2x4 (default LOCAL)",
    )
    ap.add_argument(
        "--rounds", type=int, default=None,
        help="cap the number of rounds scored per bundle",
    )
    ap.add_argument(
        "--allow-foreign", action="store_true",
        help="accept bundles recorded on a different host/toolchain",
    )
    ap.add_argument("--json", action="store_true",
                    help="emit the A/B document as one JSON line")
    args = ap.parse_args(argv)

    # Match the production solver configuration (x64 exact costs)
    # BEFORE any jax-touching import: an x64 mismatch against an
    # x64-recorded bundle is a guaranteed target refusal.
    from armada_tpu.utils.platform import setup_jax

    setup_jax()

    from armada_tpu.trace import TraceFormatError
    from armada_tpu.trace.policy_ab import (
        DEFAULT_CANDIDATES,
        ab_compare,
        render_ab,
    )
    from armada_tpu.trace.replayer import TraceTargetMismatch

    try:
        result = ab_compare(
            args.traces,
            args.policy or DEFAULT_CANDIDATES,
            solver=args.solver,
            allow_foreign=args.allow_foreign,
            max_rounds=args.rounds,
        )
    except (OSError, TraceFormatError, TraceTargetMismatch, ValueError) as e:
        print(f"policy_ab: {e}")
        return 2
    if args.json:
        print(json.dumps(result))
    else:
        print(render_ab(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
