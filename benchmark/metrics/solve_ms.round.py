"""`solve_ms.round`: mean host-clock time of the `bench.solve` span over
the traced rounds, in milliseconds. The span covers
solver/kernel.solve_round up to its decisions on the host, readback
included."""


def read(run):
    times = run.spans.get("solve")
    if not times:
        return None
    return 1000.0 * sum(times) / len(times)
