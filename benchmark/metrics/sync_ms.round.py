"""`sync_ms.round`: mean host-clock time of the `bench.sync` span over
the traced rounds, in milliseconds. The span covers
ResidentRound.device_round: prep, the diff against the host mirror and
the scatter upload, synced."""


def read(run):
    times = run.spans.get("sync")
    if not times:
        return None
    return 1000.0 * sum(times) / len(times)
