"""`gc_ms.round`: the garbage collector's pause time over the whole
window (from its opening for `cycle_s` times the rounds it ran), per
round, in milliseconds. Read from the program's GC counter
(`armada_tpu.utils.tracing.GC_PAUSES`, benchmark/program_spans.py)."""

from benchmark import program_spans


def read(run):
    cycle_s = run.e2e.get("cycle_s")
    if cycle_s is None or not run.attempted:
        return None
    paused = program_spans.gc_pause_s(
        program_spans.window_open(run), cycle_s * run.attempted
    )
    return None if paused is None else 1000.0 * paused / run.attempted
