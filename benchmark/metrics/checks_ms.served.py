"""`checks_ms.served`: the program's `round.fairness` (the fairness
ledger) and `round.validate` (the admission firewall) spans, per
scheduling round over the traced seconds, in milliseconds. Read from the
program's tracer (benchmark/program_spans.py)."""

from benchmark import program_spans


def read(run):
    return program_spans.per_round_ms(run, ["round.fairness", "round.validate"])
