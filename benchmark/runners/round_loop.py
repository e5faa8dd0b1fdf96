"""Round loop: back-to-back scheduling rounds in one process.

The timed path is the program's own warm cycle, as the scheduler service
runs it for one pool: `IncrementalRound` deltas (bind last round's
leases, end the jobs it preempted, remove completed jobs, add new
submissions), the device-resident sync (`ResidentRound.device_round`),
and `solver/kernel.solve_round` with the configuration's hot-window
settings and the program's default kernel path, read back to the host.
Nothing is chosen for the program that the scheduler service would not
choose.

Set-up builds the deployment from the seed, uploads it, and runs
`warmup_rounds` full rounds so every program the window calls is
compiled or loaded from the persistent cache. Where a warm-up round
preempts, delta sizes vary from round to round, and set-up then also
compiles every delta program of the residency sync
(`harness.warm_resident`). The window then runs rounds back to back for
`--seconds`; `cycle_s` is the window's time over its rounds; a window
that compiles anything is not correct. After the window, the last
round's decisions are compared with the plain reference on a round that
the reference's own input code (`benchmark/reference/inputs.py`) makes
from the benchmark's record of the jobs, so the deltas, the snapshot and
the residency sync are checked along with the solve.
"""

from __future__ import annotations

import gc
import time

import numpy as np


def run(run):
    from armada_tpu.snapshot.incremental import IncrementalRound
    from armada_tpu.snapshot.residency import ResidentRound
    from armada_tpu.solver.kernel import solve_round

    from benchmark import check, generate
    from benchmark.harness import (
        T_START, check_window_compiles, json_line, log_setup, warm_resident,
        window_programs,
    )

    cell = run.cell
    config, traffic = cell.config, cell.traffic
    t0 = time.monotonic()
    cfg = generate.scheduling_config(config)
    pool = config["pool"]
    node_list = generate.nodes(config)
    queue_list = generate.queues(config)
    running = generate.running_jobs(config, node_list, run.seed)
    backlog = generate.JobStream(config["backlog"], config, run.seed, 1, "job-")
    queued = backlog.take(int(config["backlog"]["count"]))
    inc = IncrementalRound(cfg, pool, node_list, queue_list, running, queued)
    inputs_s = time.monotonic() - t0

    state = Ledger(running, queued)
    rng = generate.rng_for(run.seed, 5)
    resident = ResidentRound()
    window = cfg.hot_window_slots or None
    min_slots = cfg.hot_window_min_slots
    period = float(config["schedule_period_s"])

    def one_round(k, leases, preempted):
        """Apply last round's decisions as the scheduler service does:
        its leases bind, and the jobs it preempted end (a round's
        preemption is terminal for the job, jobdb/ingest.py). Complete as
        many running jobs as were leased, submit as many new ones, sync,
        solve; returns the round's output, leases and preempted jobs."""
        with run.span("round"):
            with run.span("deltas"):
                n = len(leases)
                state.end(preempted)
                done = state.complete(n, rng)
                fresh = backlog.take(n)
                inc.bind(leases)
                inc.remove_jobs(preempted + done)
                inc.add_jobs(fresh)
                state.apply(leases, done, fresh)
            with run.span("sync"):
                dev = resident.device_round(inc)
            with run.span("solve"):
                out = solve_round(
                    dev, window=window, window_min_slots=min_slots,
                    readback_rows=inc._size,
                )
            with run.span("commit"):
                snap = inc.snapshot()
                nxt = lease_list(snap, out, k * period)
                gone = preempted_list(snap, out)
        return out, nxt, gone

    t0 = time.monotonic()
    out, leases, preempted = one_round(0, [], [])
    first_round_s = time.monotonic() - t0
    warmup_preempted = len(preempted)
    for k in range(1, 1 + int(traffic["warmup_rounds"])):
        out, leases, preempted = one_round(k, leases, preempted)
        warmup_preempted += len(preempted)
    if warmup_preempted:
        run.report.update(residency_programs_warmed=warm_resident(resident))
    from armada_tpu.observe.xla import TELEMETRY

    comp0 = TELEMETRY.snapshot()
    misses0 = len(run.cache_log.lookups)
    setup_s = time.monotonic() - T_START
    run.report.update(
        inputs_s=round(inputs_s, 3),
        first_round_s=round(first_round_s, 3),
        jobs=inc._size,
        nodes=len(node_list),
    )
    log_setup(run, setup_s)

    # ---- the window ----------------------------------------------------
    k0 = 1 + int(traffic["warmup_rounds"])
    rounds = 0
    window_preempted = 0
    traced = int(traffic["trace_rounds"])
    run.start_trace()
    t_start = time.perf_counter()
    deadline = t_start + run.seconds
    while True:
        out, leases, preempted = one_round(k0 + rounds, leases, preempted)
        rounds += 1
        window_preempted += len(preempted)
        if run.recording and rounds >= traced:
            run.stop_trace()
        if time.perf_counter() >= deadline:
            break
    t_end = time.perf_counter()
    run.stop_trace()
    window_compiles = TELEMETRY.delta_since(comp0)
    check_window_compiles(run, misses0, window_compiles)
    run.read_memory_peak()
    run.e2e["setup_s"] = setup_s
    run.e2e["cycle_s"] = (t_end - t_start) / rounds
    run.attempted = rounds
    print(
        "window "
        + json_line(rounds=rounds, seconds=t_end - t_start,
                    preempted=window_preempted,
                    compiles=window_compiles["compiles"],
                    traces=window_compiles["traces"],
                    compiled_in_window=window_programs(run, misses0)),
        flush=True,
    )
    run.reduce_trace()

    # ---- the check -------------------------------------------------------
    t0 = time.monotonic()
    prog_snap = inc.snapshot()
    resident.reset()
    del resident
    gc.collect()
    ref_snap = state.rebuild(cfg, config)
    rebuild_s = time.monotonic() - t0
    numbers = check.compare_to_reference(prog_snap, out, ref_snap)
    run.check.update(
        (name, (value, cell.limits[name])) for name, value in numbers.items()
    )
    print("reference " + json_line(rebuild_s=rebuild_s,
                                   seconds=time.monotonic() - t0), flush=True)


class Ledger:
    """The benchmark's own record of which jobs are queued and running,
    kept from the leases and preemptions it applies: the reference's
    input is rebuilt from it, not taken from the program's incremental
    state."""

    def __init__(self, running, queued):
        from armada_tpu.core.types import RunningJob

        self._running_cls = RunningJob
        self.queued = {j.id: j for j in queued}
        self.running = {r.job.id: r for r in running}
        self.ids = list(self.running)
        self.where = {jid: i for i, jid in enumerate(self.ids)}

    def complete(self, n, rng):
        """n running jobs chosen from the seed (each at most once)."""
        n = min(n, len(self.ids))
        if not n:
            return []
        picks = rng.choice(len(self.ids), size=n, replace=False)
        return [self.ids[i] for i in picks]

    def apply(self, leases, done, fresh):
        for jid, node, prio, ts in leases:
            job = self.queued.pop(jid)
            self.running[jid] = self._running_cls(
                job=job, node_id=node, scheduled_at_priority=prio, leased_ts=ts
            )
            self.where[jid] = len(self.ids)
            self.ids.append(jid)
        self.end(done)
        for job in fresh:
            self.queued[job.id] = job

    def end(self, ids):
        """Running jobs that end: completed, or preempted by a round."""
        for jid in ids:
            del self.running[jid]
            i = self.where.pop(jid)
            last = self.ids.pop()
            if last != jid:
                self.ids[i] = last
                self.where[last] = i

    def rebuild(self, cfg, config):
        """The reference's round, built from this record alone."""
        from benchmark import generate
        from benchmark.reference.inputs import build_round

        running = list(self.running.values())
        queued = list(self.queued.values())
        jobs = [r.job for r in running] + queued
        cols = {
            "id": [j.id for j in jobs],
            "queue": [j.queue for j in jobs],
            "priority_class": [j.priority_class for j in jobs],
            "priority": [j.priority for j in jobs],
            "submitted": [j.submitted_ts for j in jobs],
            "requests": [j.requests for j in jobs],
            "node": [r.node_id for r in running] + [""] * len(queued),
            "running_priority": [r.scheduled_at_priority for r in running]
            + [0] * len(queued),
            "gang_id": [j.gang.id if j.gang is not None else "" for j in jobs],
            "gang_card": [j.gang.cardinality if j.gang is not None else 1
                          for j in jobs],
        }
        return build_round(
            cfg, config["pool"], generate.node_table(config),
            generate.queue_weights(config), cols,
        )


def lease_list(snap, out, ts):
    """The round's new leases as IncrementalRound.bind takes them."""
    J = snap.num_jobs
    sched = np.flatnonzero(np.asarray(out["scheduled_mask"])[:J])
    node = np.asarray(out["assigned_node"])[:J]
    prio = np.asarray(out["scheduled_priority"])[:J]
    return [
        (str(snap.job_ids[j]), snap.node_ids[int(node[j])], int(prio[j]), ts)
        for j in sched
    ]


def preempted_list(snap, out):
    """The ids of the running jobs the round preempted."""
    J = snap.num_jobs
    return [str(snap.job_ids[j])
            for j in np.flatnonzero(np.asarray(out["preempted_mask"])[:J])]
