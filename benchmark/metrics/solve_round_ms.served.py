"""`solve_round_ms.served`: the program's `solve` span
(`solver/kernel.solve_round`: dispatch to decisions read back), per
scheduling round over the traced seconds, in milliseconds. Read from the
program's tracer (benchmark/program_spans.py)."""

from benchmark import program_spans


def read(run):
    return program_spans.per_round_ms(run, ["solve"])
