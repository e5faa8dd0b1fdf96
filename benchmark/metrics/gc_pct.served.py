"""`gc_pct.served`: the garbage collector's pause time over the whole
window (`--seconds` from its opening), as a share of it in %. Read from
the program's GC counter (`armada_tpu.utils.tracing.GC_PAUSES`,
benchmark/program_spans.py)."""

from benchmark import program_spans


def read(run):
    paused = program_spans.gc_pause_s(program_spans.window_open(run), run.seconds)
    return None if paused is None else 100.0 * paused / run.seconds
