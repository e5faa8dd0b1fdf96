"""`delta_ms.round`: mean host-clock time of the `bench.deltas` span over
the traced rounds, in milliseconds. The span covers
IncrementalRound.bind (last round's leases), remove_jobs (completions)
and add_jobs (new submissions): the snapshot layer's host work."""


def read(run):
    times = run.spans.get("deltas")
    if not times:
        return None
    return 1000.0 * sum(times) / len(times)
