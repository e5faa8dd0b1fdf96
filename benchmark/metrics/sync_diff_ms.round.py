"""`sync_diff_ms.round`: the program's `residency.diff` span (the slot
map and the changed rows of every field against the host mirror),
summed per traced round and averaged over them, in milliseconds. Read
from the program's tracer (benchmark/program_spans.py)."""

from benchmark import program_spans


def read(run):
    rounds = program_spans.traced_rounds(run)
    if rounds is None:
        return None
    solves, syncs = rounds
    diff = program_spans.children(syncs, "residency.diff")
    return 1000.0 * program_spans.total_s(diff) / len(solves)
