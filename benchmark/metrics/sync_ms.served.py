"""`sync_ms.served`: the program's `residency.sync` span (the delta sync
of the device-resident round), per scheduling round over the traced
seconds, in milliseconds. Read from the program's tracer
(benchmark/program_spans.py)."""

from benchmark import program_spans


def read(run):
    return program_spans.per_round_ms(run, ["residency.sync"])
