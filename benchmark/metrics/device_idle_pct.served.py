"""`device_idle_pct.served`: the device's idle share over the traced
window, in percent: 1 - (union of device-op intervals / window), from
the profiler trace (benchmark/trace_reduce.py). Nothing when the trace
has no device."""


def read(run):
    summary = run.trace_summary
    if not summary:
        return None
    return 100.0 * summary["idle_share"]
