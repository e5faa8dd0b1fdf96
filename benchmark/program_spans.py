"""The program's own spans and GC counter, for the per-layer readers.

The program records its spans in `armada_tpu.utils.tracing.TRACER`
(`spans(name)`: finished spans with `start` and `end` on
time.monotonic(), `span_id`, `parent_id`, `attrs`) and the garbage
collector's pauses in `tracing.GC_PAUSES`. A reader takes nothing else.
Where the program has neither, each function here returns None, and so
does the reader.

The window opens at `harness.T_START + run.e2e["setup_s"]`: both
runners take `setup_s` on time.monotonic() as the window opens.
"""

from __future__ import annotations


def _tracing():
    try:
        from armada_tpu.utils import tracing
    except ImportError:
        return None
    return tracing


def tracer():
    """The program's process tracer, where it has the read API."""
    tracing = _tracing()
    t = getattr(tracing, "TRACER", None)
    return t if callable(getattr(t, "spans", None)) else None


def window_open(run) -> float | None:
    from benchmark.harness import T_START

    setup = run.e2e.get("setup_s")
    return None if setup is None else T_START + setup


def total_s(spans) -> float:
    return sum(s.end - s.start for s in spans)


def traced_rounds(run):
    """Round loop: the first `trace_rounds` `solve` spans that start in
    the window, and the `residency.sync` spans of the same rounds (those
    that start between the opening and the last of those solves), as
    (solves, syncs); None where there are none."""
    t, t0 = tracer(), window_open(run)
    if t is None or t0 is None:
        return None
    n = int(run.cell.traffic.get("trace_rounds", 0))
    solves = sorted((s for s in t.spans("solve") if s.start >= t0),
                    key=lambda s: s.start)[:n]
    if not solves:
        return None
    last = solves[-1].start
    syncs = [s for s in t.spans("residency.sync") if t0 <= s.start < last]
    return solves, syncs


def children(parents, name: str) -> list:
    """The `name` spans whose parent is one of `parents`."""
    ids = {p.span_id for p in parents}
    return [s for s in tracer().spans(name) if s.parent_id in ids]


def per_round_ms(run, names) -> float | None:
    """Served: the spans of `names` that start within `trace_seconds` of
    the opening, summed per `scheduler.round` they run under, the mean
    over those rounds, in milliseconds."""
    t, t0 = tracer(), window_open(run)
    if t is None or t0 is None:
        return None
    t1 = t0 + float(run.cell.traffic["trace_seconds"])
    rounds = {s.span_id for s in t.spans("scheduler.round")}
    parent_of = {s.span_id: s.parent_id for s in t.spans()}
    by_round: dict[str, float] = {}
    for name in names:
        for s in t.spans(name):
            if not t0 <= s.start < t1:
                continue
            pid = s.parent_id
            while pid and pid not in rounds:
                pid = parent_of.get(pid, "")
            if pid:
                by_round[pid] = by_round.get(pid, 0.0) + (s.end - s.start)
    if not by_round:
        return None
    return 1000.0 * sum(by_round.values()) / len(by_round)


def gc_pause_s(t0: float | None, seconds: float) -> float | None:
    """The garbage collector's pause seconds between t0 and t0 +
    seconds."""
    tracing = _tracing()
    counter = getattr(tracing, "GC_PAUSES", None)
    if counter is None or t0 is None or seconds <= 0:
        return None
    return counter.pause_s_between(t0, t0 + seconds)
