"""Spreads and medians from the `LINE` lines of `runs.py`.

    python3 benchmark/tools/spread.py <cell> <log> [<log> ...]

For each end-to-end metric: per set (label A, B) the median and the
spread, (Q3 - Q1) / median by `statistics.quantiles(values, n=4)`, the
wider of the two, five times it (the bound it suggests, never under 1%),
and the ratio of the medians. Then every run's correctness and compared
numbers, and each traced run's per-layer metrics and breakdown.
"""

from __future__ import annotations

import json
import re
import statistics
import sys


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv) -> None:
    cell, paths = argv[0], argv[1:]
    rows: dict = {}
    for path in paths:
        for line in open(path):
            m = re.match(r"LINE (\S+):(\w+) (\d+) (\{.*\})\s*$", line)
            if not m or m.group(1) != cell:
                continue
            try:
                rows.setdefault(m.group(2), []).append((int(m.group(3)), json.loads(m.group(4))))
            except ValueError:
                print("unparsed", m.group(2), m.group(3))
    for label, rs in sorted(rows.items()):
        print(label, [(s, d["correct"], d.get("failed")) for s, d in rs])
    names = sorted({k for label in ("A", "B") for _, d in rows.get(label, [])
                    for k in d["metrics"]})
    for name in names:
        sets = []
        for label in ("A", "B"):
            vals = [d["metrics"][name]["value"] for _, d in rows.get(label, [])
                    if name in d["metrics"]]
            if len(vals) >= 2:
                sets.append((label, statistics.median(vals), spread(vals), vals))
        if not sets:
            continue
        widest = max(s[2] for s in sets)
        print(name, [(lab, med, sp) for lab, med, sp, _ in sets],
              "widest", widest, "bound5x", max(0.01, 5 * widest))
        for lab, _, _, vals in sets:
            print("  ", lab, vals)
        if len(sets) == 2:
            print("   median B/A", sets[1][1] / sets[0][1])
    for label, rs in sorted(rows.items()):
        for s, d in rs:
            print(label, s, d["correct"], json.dumps(d["check"]))
            if "breakdown" in d:
                print("   metrics", json.dumps(d["metrics"]), json.dumps(d["device"]))
                print("   breakdown", json.dumps(d["breakdown"]))


if __name__ == "__main__":
    main(sys.argv[1:])
