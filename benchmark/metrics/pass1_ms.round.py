"""`pass1_ms.round`: the program's `solve.pass1` spans (pass 1 of the
host-driven solve: each chunk or window run with its host checks),
summed per traced round's `solve` and averaged over the rounds, in
milliseconds; 0 where the fused solve ran. Read from the program's
tracer (benchmark/program_spans.py)."""

from benchmark import program_spans


def read(run):
    rounds = program_spans.traced_rounds(run)
    if rounds is None:
        return None
    solves, _ = rounds
    pass1 = program_spans.children(solves, "solve.pass1")
    return 1000.0 * program_spans.total_s(pass1) / len(solves)
