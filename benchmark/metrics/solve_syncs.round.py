"""`solve_syncs.round`: the `host_syncs` attribute of the program's
`solve` span (each time the host waits on the device inside one solve),
the median over the traced rounds. Read from the program's tracer
(benchmark/program_spans.py); nothing where the span has no such
attribute."""

import statistics

from benchmark import program_spans


def read(run):
    rounds = program_spans.traced_rounds(run)
    if rounds is None:
        return None
    solves, _ = rounds
    syncs = [s.attrs["host_syncs"] for s in solves if "host_syncs" in s.attrs]
    return statistics.median(syncs) if syncs else None
