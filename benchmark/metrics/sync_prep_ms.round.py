"""`sync_prep_ms.round`: the program's `residency.prep` span (the padded
host round the residency sync diffs: `IncrementalRound.device_round` and
`pad_device_round`), summed per traced round and averaged over them, in
milliseconds. Read from the program's tracer (benchmark/program_spans.py)."""

from benchmark import program_spans


def read(run):
    rounds = program_spans.traced_rounds(run)
    if rounds is None:
        return None
    solves, syncs = rounds
    prep = program_spans.children(syncs, "residency.prep")
    return 1000.0 * program_spans.total_s(prep) / len(solves)
