"""`solve_ms.served`: the program's `scheduler_solve_seconds` histogram
over the traced window, sum over count, in milliseconds. Observed
around each pool round's solve attempt (services/scheduler.py)."""


def read(run):
    count = run.counters.get("scheduler_solve_seconds_count")
    if not count:
        return None
    return 1000.0 * run.counters["scheduler_solve_seconds_sum"] / count
