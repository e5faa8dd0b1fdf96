"""`round_ms.served`: the program's `scheduler_cycle_seconds` histogram
over the traced window, sum over count, in milliseconds. Observed by
SchedulerService.cycle around each whole cycle
(services/scheduler.py)."""


def read(run):
    count = run.counters.get("scheduler_cycle_seconds_count")
    if not count:
        return None
    return 1000.0 * run.counters["scheduler_cycle_seconds_sum"] / count
