"""Profiler trace -> device busy/idle share, top device ops, idle gaps.

    python3 benchmark/trace_reduce.py <file.xplane.pb> [--window NAME]

`reduce_trace` reads one `.xplane.pb` with `jax.profiler.ProfileData`
(nothing but JAX) and returns:

- `window_s`: the traced window, the span of the host annotation named
  `window` (the harness wraps the traced rounds in `bench.window`), or
  else the first to the last device event;
- `busy_s`: the union of the intervals in which an operation ran on the
  device, clipped to the window, averaged over the device planes;
- `idle_share`: 1 - busy_s / window_s;
- `device_ops`: the ten op names with the most device time, as
  `[name, seconds]`, seconds averaged over the devices;
- `idle_gaps`: the device's idle time inside the window, attributed to
  the innermost host annotation (prefix `bench.`) that covers each gap's
  midpoint, summed by annotation, the ten largest as `[name, seconds]`.

Device events are taken from each device plane's "XLA Ops" line where
there is one (the per-op line), and from all its lines otherwise. A
trace with no device plane (a CPU run) yields None: there is no device
to be busy.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import os

ANNOTATION_PREFIX = "bench."
DEVICE_PLANE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def device_peaks(kind: str) -> dict:
    """One chip's published peaks by `device_kind` (benchmark/peaks.json),
    for roofline shares. A device that is not in the table is an error,
    never a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in benchmark/peaks.json")
    return table[kind]


def find_xplane(trace_dir: str) -> str | None:
    """The newest `.xplane.pb` under a `jax.profiler.start_trace` dir."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi]


def op_name(text: str) -> str:
    """`%fusion.12 = bf16[...] fusion(...)` -> `fusion.12`."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def module_name(text: str) -> str:
    """`jit_solve_impl(6074760096634504725)` -> `jit_solve_impl`."""
    return text.split("(", 1)[0]


def _device_events(plane):
    """(name, start, end) per device op; an op is named by its program
    and its HLO op name, `jit_solve_impl/fusion.12`."""
    lines = list(plane.lines)
    ops = [ln for ln in lines if ln.name == OPS_LINE]
    modules = sorted(
        (float(ev.start_ns), float(ev.start_ns + ev.duration_ns), module_name(ev.name))
        for ln in lines if ln.name == MODULES_LINE for ev in ln.events
    )
    starts = [m[0] for m in modules]
    for line in ops or lines:
        for ev in line.events:
            if ev.duration_ns <= 0:
                continue
            s, e = float(ev.start_ns), float(ev.start_ns + ev.duration_ns)
            name = op_name(ev.name)
            i = bisect.bisect_right(starts, s) - 1
            if ops and i >= 0 and s < modules[i][1]:
                name = f"{modules[i][2]}/{name}"
            yield name, s, e


def _host_annotations(planes):
    spans = []
    for plane in planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(ANNOTATION_PREFIX):
                    spans.append(
                        (ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
                    )
    return spans


def reduce_trace(path: str, window: str = "bench.window") -> dict | None:
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    devices = [
        p for p in planes
        if p.name.startswith(DEVICE_PLANE_PREFIX) and ":CUSTOM:" not in p.name
    ]
    per_device = [list(_device_events(p)) for p in devices]
    per_device = [evs for evs in per_device if evs]
    if not per_device:
        return None
    spans = _host_annotations(planes)
    windows = [(s, e) for name, s, e in spans if name == window]
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        lo = min(s for evs in per_device for _, s, _ in evs)
        hi = max(e for evs in per_device for _, _, e in evs)
    window_ns = hi - lo
    if window_ns <= 0:
        return None
    labels = [(name, s, e) for name, s, e in spans if name != window]
    busy, op_time, gap_time = [], {}, {}
    for evs in per_device:
        merged = _clip(_union([(s, e) for _, s, e in evs]), lo, hi)
        busy.append(sum(e - s for s, e in merged))
        for name, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_time[name] = op_time.get(name, 0.0) + d
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) / 2
            covering = [
                (ce - cs, name) for name, cs, ce in labels if cs <= mid < ce
            ]
            label = min(covering)[1] if covering else "(no host span)"
            gap_time[label] = gap_time.get(label, 0.0) + (e - s)
    n = len(per_device)
    busy_s = sum(busy) / n / 1e9
    window_s = window_ns / 1e9

    def top(d):
        rows = sorted(d.items(), key=lambda kv: -kv[1])[:TOP]
        return [[name, v / n / 1e9] for name, v in rows]

    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "devices": n,
        "device_ops": top(op_time),
        "idle_gaps": top(gap_time),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("path", help="an .xplane.pb file or a trace directory")
    ap.add_argument("--window", default="bench.window")
    args = ap.parse_args(argv)
    path = args.path if os.path.isfile(args.path) else find_xplane(args.path)
    if path is None:
        raise SystemExit(f"no .xplane.pb under {args.path}")
    print(json.dumps(reduce_trace(path, window=args.window)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
