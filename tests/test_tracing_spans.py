"""The program's spans (utils/tracing.py) where the work happens: on the
JAX profiler's host clock, nested under the scheduler's round span in
whichever tracer the scheduler holds, kept per name so one flood cannot
evict another, and the garbage collector's pauses counted and spanned."""

import gc
import glob
import os
import time

import jax
import numpy as np
import pytest

from armada_tpu.core.config import PriorityClass, SchedulingConfig
from armada_tpu.core.types import JobSpec, NodeSpec, QueueSpec
from armada_tpu.snapshot.incremental import IncrementalRound
from armada_tpu.snapshot.residency import ResidentRound
from armada_tpu.solver.kernel import solve_round
from armada_tpu.solver.kernel_prep import pad_device_round
from armada_tpu.utils import tracing
from armada_tpu.utils.tracing import GC_PAUSES, TRACER, GcPauses, Tracer

CFG = SchedulingConfig(
    priority_classes={
        "high": PriorityClass("high", 30000, preemptible=False),
        "low": PriorityClass("low", 1000, preemptible=True),
    },
    default_priority_class="low",
    batch_fill_window=4,
)
QUEUES = [QueueSpec(f"q{i}", 1.0 + i % 2) for i in range(3)]


def _jobs(first, n):
    return [
        JobSpec(id=f"j{i:04d}", queue=f"q{i % 3}",
                requests={"cpu": str(1 + i % 2), "memory": "1Gi"},
                submitted_ts=float(i))
        for i in range(first, first + n)
    ]


def _inc():
    nodes = [
        NodeSpec(id=f"n{i:02d}", pool="default",
                 total_resources={"cpu": "8", "memory": "32Gi"})
        for i in range(6)
    ]
    return IncrementalRound(CFG, "default", nodes, QUEUES, [], _jobs(0, 90))


def _windowed_solve():
    """A host-driven solve: a 2-slot hot window forced at any size."""
    dev = pad_device_round(_inc().device_round())
    solve_round(dev, window=2, window_min_slots=0)


def _delta_sync():
    """A ResidentRound delta sync after new submissions."""
    inc = _inc()
    resident = ResidentRound()
    resident.device_round(inc)
    inc.add_jobs(_jobs(500, 7))
    resident.device_round(inc)
    assert resident.last_sync["mode"] == "delta"
    jax.block_until_ready(resident._dev.job_req)


def _host_events(path):
    """(name, start_ns, end_ns) of every event on the non-device planes."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


@pytest.mark.parametrize(
    "work,names",
    [
        (_windowed_solve, ["solve", "solve.setup", "solve.gather",
                           "solve.pass1", "solve.finish", "solve.readback"]),
        (_delta_sync, ["residency.sync", "residency.prep", "residency.diff",
                       "residency.upload"]),
    ],
    ids=["solve", "residency"],
)
def test_program_spans_on_the_profiler_host_plane(tmp_path, work, names):
    """A profiler trace on the CPU backend holds the program's spans on
    its host plane, each inside the enclosing annotation."""
    work()  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("test.outer"):
            work()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    events = _host_events(path)
    (outer,) = [e for e in events if e[0] == "test.outer"]
    for name in names:
        found = [e for e in events if e[0] == name]
        assert found, f"{name} missing from the host plane"
        assert all(outer[1] <= s and e <= outer[2] for _, s, e in found), name


def test_solve_children_cover_the_solve():
    _windowed_solve()  # compile first
    _windowed_solve()
    (solve,) = TRACER.spans("solve")[-1:]
    kids = [s for s in TRACER.spans() if s.parent_id == solve.span_id]
    assert {s.name for s in kids} == {"solve.setup", "solve.gather", "solve.pass1",
                                      "solve.finish", "solve.readback"}
    covered = sum(s.duration_s for s in kids)
    assert covered >= 0.95 * solve.duration_s
    assert solve.attrs["path"] == "host"
    assert solve.attrs["host_syncs"] >= 2 * solve.attrs["pass1_runs"]
    assert solve.attrs["rewindows"] == solve.attrs["gathers"] // 2 - 1


def test_solve_profile_is_read_from_its_spans():
    dev = pad_device_round(_inc().device_round())
    out = solve_round(dev, window=2, window_min_slots=0)
    solve = TRACER.spans("solve")[-1]
    kids = [s for s in TRACER.spans() if s.parent_id == solve.span_id]

    def seconds(name):
        return round(sum(s.duration_s for s in kids if s.name == name), 4)

    prof = out["profile"]
    assert prof["pass1_s"] == seconds("solve.pass1")
    assert prof["gather_s"] == seconds("solve.gather")
    assert prof["setup_s"] == seconds("solve.setup")
    assert prof["finish_s"] == seconds("solve.finish")
    assert prof["rewindows"] == solve.attrs["rewindows"]


@pytest.mark.parametrize("attached", [False, True], ids=["process", "attached"])
def test_round_stages_nest_under_the_round_span(attached):
    """In a kernel-backed scheduler cycle, the residency sync, the solve
    and the round's stages are descendants of `scheduler.round`, in the
    process tracer or in one attached to the scheduler."""
    from armada_tpu.events import InMemoryEventLog
    from armada_tpu.services.fake_executor import FakeExecutor, make_nodes
    from armada_tpu.services.scheduler import SchedulerService
    from armada_tpu.services.submit import SubmitService

    log = InMemoryEventLog()
    sched = SchedulerService(CFG, log, backend="kernel")
    tracer = TRACER
    if attached:
        tracer = Tracer()
        sched.attach_tracer(tracer)
    submit = SubmitService(CFG, log, scheduler=sched)
    submit.create_queue(QueueSpec("team"))
    ex = FakeExecutor("ex-a", log, sched, nodes=make_nodes("ex-a", count=2, cpu="8"))
    submit.submit("team", "s", [
        JobSpec(id=f"t{i}", queue="team", jobset="s",
                requests={"cpu": "2", "memory": "1Gi"}, submitted_ts=float(i))
        for i in range(6)
    ], now=0.0)
    t0 = time.monotonic()
    for t in (0.0, 1.0):
        ex.tick(t)
        sched.cycle(now=t)
    spans = [s for s in tracer.spans() if s.start >= t0]
    by_id = {s.span_id: s for s in spans}
    rounds = {s.span_id for s in spans if s.name == "scheduler.round"}
    assert rounds

    def under_round(s):
        pid = s.parent_id
        while pid and pid not in rounds:
            pid = by_id[pid].parent_id if pid in by_id else ""
        return bool(pid)

    stages = ("residency.sync", "residency.prep", "residency.diff", "solve",
              "round.snapshot", "round.fairness", "round.validate",
              "round.record", "round.commit")
    for name in stages:
        found = [s for s in spans if s.name == name]
        if name == "residency.diff":
            found = found or [s for s in spans if s.name == "residency.upload"]
        assert found, name
        assert all(under_round(s) for s in found), name


def test_a_flood_of_one_name_keeps_the_others():
    tracer = Tracer(keep=64)
    with tracer.span("scheduler.round"):
        pass
    for _ in range(5000):
        with tracer.span("rpc.SubmitJobs"):
            pass
    assert len(tracer.spans("scheduler.round")) == 1
    assert len(tracer.spans("rpc.SubmitJobs")) == 64
    finished = tracer.finished
    assert finished[0].name == "scheduler.round"
    assert [s.seq for s in finished] == sorted(s.seq for s in finished)


def test_library_span_nests_in_the_tracer_that_opened_the_parent():
    mine = Tracer()
    with mine.span("outer") as outer:
        with tracing.span("inner", k=1) as inner:
            pass
    assert inner.parent_id == outer.span_id and inner.trace_id == outer.trace_id
    assert mine.spans("inner") == [inner] and inner.attrs == {"k": 1}
    with tracing.span("alone") as alone:
        pass
    assert alone.parent_id == "" and TRACER.spans("alone")[-1] is alone


def test_gc_collect_counts_and_spans_generation_two():
    GC_PAUSES.install()
    before = GC_PAUSES.count[2]
    seconds = GC_PAUSES.seconds[2]
    t0 = time.monotonic()
    spans0 = len([s for s in TRACER.spans("gc") if s.start >= t0])
    gc.collect()
    t1 = time.monotonic()
    new = [s for s in TRACER.spans("gc") if s.start >= t0]
    assert GC_PAUSES.count[2] > before
    assert len(new) - spans0 == GC_PAUSES.count[2] - before
    assert new[-1].attrs["generation"] == 2 and "collected" in new[-1].attrs
    assert GC_PAUSES.seconds[2] > seconds
    paused = GC_PAUSES.pause_s_between(t0 - 1.0, t1 + 1.0)
    assert paused >= new[-1].duration_s > 0


def test_gc_pause_timeline_answers_windows_and_forgets_old_ones():
    pauses = GcPauses(Tracer(), grain_s=1.0, keep=4)
    assert pauses.pause_s_between(0.0, 1.0) is None  # not installed
    pauses.installed = True
    for t_end, pause, gen in ((10.2, 0.1, 0), (10.6, 0.2, 2), (12.5, 0.4, 1),
                              (13.1, 0.8, 0)):
        pauses._note(gen, 3, t_end - pause, t_end)
    assert pauses.count == [2, 1, 1] and pauses.collected == [6, 3, 3]
    assert [s.attrs for s in pauses.tracer.spans("gc")] == [
        {"generation": 2, "collected": 3}
    ]
    assert np.isclose(pauses.pause_s_between(11.0, 13.5), 1.2)
    assert np.isclose(pauses.pause_s_between(9.0, 13.5), 1.5)
    assert pauses.pause_s_between(5.0, 9.5) == 0.0
    for t_end in (20.0, 21.0, 22.0):  # the ring of 4 grains wraps
        pauses._note(0, 0, t_end, t_end)
    assert pauses.pause_s_between(9.0, 22.5) is None
    assert pauses.pause_s_between(14.0, 22.5) == 0.0
