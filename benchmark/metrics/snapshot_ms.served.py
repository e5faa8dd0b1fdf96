"""`snapshot_ms.served`: the program's `round.snapshot` span (the round's
inputs: the jobdb delta folded into the incremental round, or a rebuilt
snapshot), per scheduling round over the traced seconds, in
milliseconds. Read from the program's tracer
(benchmark/program_spans.py)."""

from benchmark import program_spans


def read(run):
    return program_spans.per_round_ms(run, ["round.snapshot"])
