"""Served: the whole control plane, driven over gRPC from client threads.

The system under test is `services/server.ControlPlane(backend="kernel")`
with the deployment's fake executors, as the program ships it: its own
cycle loop, solver ladder, admission firewall and event log. Everything
runs in this one process, which alone holds the chip.

Set-up creates the queues, submits the fill (where the deployment has
one) and the backlog over gRPC, submits a few arrival-shaped jobs so
their scheduling keys exist, starts the plane and waits out its first
rounds (the compile, or the persistent cache's load). A deployment's
optional `fill` block (`count` jobs in the exact shares of its `sizes`,
of its `priority_class` where a size names none, each running
`runtime_s`) starts the window on a full fleet: set-up then waits until
the fill's leases, as the watchers see them, hold `until_fraction` of
the fleet's CPU, and fails the run after `FILL_WAIT_S` seconds. The fill
goes into each queue's arrivals jobset, so the watchers that time the
arrivals see its leases too (the server admits a bounded number of
watch streams).

The window is an open loop: arrivals are due at fixed Poisson gaps from
the seed, each one a SubmitJobs call into the next queue of an even
round; one watcher thread per queue follows the arrivals' jobset on
WatchJobSet. A job's lease latency is from when it was due to the moment
its watcher sees its JobRunLeased event. After the window, arrivals are
followed until leased, for up to `drain_s` (outside the window); one
still unleased then has failed.

Set-up also compiles every delta program of the residency sync
(`harness.warm_resident`), and a window that compiles anything is not
correct.

Every round the window solves is kept (its snapshot copied, its
decisions); after the window a sample of them, drawn from the seed, is
solved again by the plain reference, on a round built from the
benchmark's record of what it submitted (`Record.round_for`), and
compared; every lease that a watcher saw from those rounds must be the
round's decision.
"""

from __future__ import annotations

import dataclasses
import json
import threading
import time
import zlib

import numpy as np

from benchmark import check, generate
from benchmark.harness import (
    T_START, check_window_compiles, log_setup, warm_resident, window_programs,
)
from benchmark.reference.inputs import quantity


# Seconds set-up waits, after the first solved round, for the fill to
# lease its share of the fleet.
FILL_WAIT_S = 300.0


def run(run):
    from armada_tpu.services.server import ControlPlane

    config = run.cell.config
    ex = config["executors"]
    fakes = [
        {"name": f"{ex['prefix']}{i:02d}", "nodes": int(ex["nodes"]),
         "cpu": ex["cpu"], "memory": ex["memory"], "pool": config["pool"]}
        for i in range(int(ex["count"]))
    ]
    plane = ControlPlane(
        generate.scheduling_config(config), backend="kernel",
        cycle_period=float(config["cycle_period_s"]), fake_executors=fakes,
    )
    stop = Once(plane.stop)
    try:
        _drive(run, plane, stop)
    finally:
        stop()


class Once:
    def __init__(self, fn):
        self.fn, self.done = fn, False

    def __call__(self):
        if not self.done:
            self.done = True
            self.fn()


def _drive(run, plane, stop):
    from armada_tpu.observe.xla import TELEMETRY
    from armada_tpu.services.grpc_api import connect

    config, traffic = run.cell.config, run.cell.traffic
    names = generate.queue_names(config)
    sched = plane.scheduler
    lo, hi = float(config["runtime_s"]["min"]), float(config["runtime_s"]["max"])
    gang_runtime = float((config["backlog"].get("gang") or {}).get("runtime_s", hi))
    fill = config.get("fill")
    fill_ids: set[str] = set()
    seed = run.seed
    client = connect(plane.address)
    record = Record(client)

    def runtime_for(job_id, seed=seed):
        """From the seed per job; a gang's members end together, and the
        fill runs its own runtime."""
        rec = record.jobs.get(job_id)
        if rec is not None and rec[5]:
            return gang_runtime
        if job_id in fill_ids:
            return float(fill["runtime_s"])
        u = zlib.crc32(f"{seed}:{job_id}".encode()) / 2**32
        return lo + (hi - lo) * u

    for executor in plane.executors:
        executor.runtime_for = runtime_for
    keeper = RoundKeeper(sched, config["pool"])
    annotate(plane)

    t0 = time.monotonic()
    for name, pf in zip(names, generate.priority_factors(config)):
        client.create_queue(name, priority_factor=pf)
    arr = traffic["arrivals"]
    if fill:
        for queue, jobs in fill_jobs(config, seed):
            for i in range(0, len(jobs), 2000):
                fill_ids.update(record.submit(queue, arr["jobset"], jobs[i:i + 2000]))
    for queue, jobset, jobs in backlog(config, seed):
        for i in range(0, len(jobs), 2000):
            record.submit(queue, jobset, jobs[i:i + 2000])
    arrival = {
        "priority": int(arr["priority"]),
        "priority_class": arr["priority_class"],
        "requests": {"cpu": arr["cpu"], "memory": arr["memory"]},
    }
    for name in names:
        record.submit(
            name, arr["jobset"], [arrival] * int(traffic["warmup_arrivals_per_queue"])
        )
    submit_s = time.monotonic() - t0

    watchers = [Watcher(plane.address, name, arr["jobset"], connect) for name in names]
    t0 = time.monotonic()
    plane.start()
    first = wait_solved(plane, keeper, timeout=1100.0)
    first_round_s = time.monotonic() - t0
    if fill:
        t0 = time.monotonic()
        fill_cpu = wait_filled(record, watchers, fill_ids, fleet_cpu(config),
                               float(fill["until_fraction"]))
        run.report.update(fill_s=round(time.monotonic() - t0, 3),
                          fill_leased_cpu=fill_cpu)
    t0 = time.monotonic()
    warmed = warm_resident(sched._resident.get(config["pool"]))
    warm_s = time.monotonic() - t0
    # The same open loop, untimed, so the round shapes it makes are warm.
    open_loop(record, names, arrival, arr, float(traffic["warmup_s"]), seed,
              stream=13)

    comp0 = TELEMETRY.snapshot()
    misses0 = len(run.cache_log.lookups)
    setup_s = time.monotonic() - T_START
    run.report.update(
        backlog_submit_s=round(submit_s, 3),
        first_round_s=round(first_round_s, 3),
        first_round_cycles=first,
        residency_programs_warmed=warmed,
        residency_warm_s=round(warm_s, 3),
        jobs=int(config["backlog"]["count"]),
        nodes=int(config["executors"]["count"]) * int(config["executors"]["nodes"]),
    )
    log_setup(run, setup_s)

    # ---- the window: an open loop --------------------------------------
    pool = config["pool"]
    keeper.on = True
    window_opened_s = keeper.since_first()
    run.start_trace()
    counters0 = read_counters(plane, pool)
    traced = float(traffic["trace_seconds"])

    def tick(elapsed):
        if run.recording and elapsed >= traced:
            run.counters.update(counter_delta(counters0, read_counters(plane, pool)))
            run.stop_trace()

    sent, late = open_loop(record, names, arrival, arr, run.seconds, seed,
                           stream=9, tick=tick)
    if run.recording:
        run.counters.update(counter_delta(counters0, read_counters(plane, pool)))
        run.stop_trace()
    window_compiles = TELEMETRY.delta_since(comp0)
    check_window_compiles(run, misses0, window_compiles)
    keeper.on = False

    # ---- drain: follow every arrival to its lease ------------------------
    ids = {jid for jid, _, _ in sent}
    t_drain = time.perf_counter() + float(traffic["drain_s"])
    while time.perf_counter() < t_drain:
        seen = leased_times(watchers)
        if ids.issubset(seen):
            break
        time.sleep(0.2)
    run.read_memory_peak()
    seen = leased_times(watchers)
    lat = np.asarray([seen[jid][0] - t for jid, t, _ in sent if jid in seen])
    unleased = len(ids) - len(lat)
    run.attempted = len(sent)
    run.failed = unleased
    run.e2e["setup_s"] = setup_s
    if len(lat):
        run.e2e["lease_p50_s"] = float(np.percentile(lat, 50))
        run.e2e["lease_p95_s"] = float(np.percentile(lat, 95))
    late = np.asarray(late)
    print(
        "window "
        + json.dumps({
            "arrivals": len(sent),
            "rate_per_s": float(arr["rate_per_s"]),
            "generator_late_p50_s": float(np.percentile(late, 50)) if len(late) else 0.0,
            "generator_late_max_s": float(late.max()) if len(late) else 0.0,
            "rounds_kept": len(keeper.rounds),
            "preempted": sum(preemptions(snap, out)[0] for snap, out in keeper.rounds),
            "window_opened_s": window_opened_s,
            "round_shapes": keeper.shapes,
            "unleased": unleased,
            "compiles": window_compiles["compiles"],
            "compiled_in_window": window_programs(run, misses0),
            "lease_p50_first_half_s": half_percentile(sent, seen, run.seconds, 0),
            "lease_p50_second_half_s": half_percentile(sent, seen, run.seconds, 1),
        }),
        flush=True,
    )
    for w in watchers:
        w.close()
    stop()
    run.reduce_trace()

    # ---- the check -------------------------------------------------------
    t0 = time.monotonic()
    rng = generate.rng_for(seed, 11)
    rounds = keeper.rounds
    k = min(int(traffic["compare_rounds"]), len(rounds))
    picks = sorted(rng.choice(len(rounds), size=k, replace=False)) if k else []
    cfg = generate.scheduling_config(config)
    divergences, gap, mismatches = 0, 0.0, 0
    preempted, preempting = 0, 0
    for i in picks:
        snap, out = rounds[i]
        numbers = check.compare_to_reference(
            snap, out, record.round_for(snap, cfg, config)
        )
        divergences += numbers["placement_divergences"]
        gap = max(gap, numbers["fair_share_gap"])
        mismatches += lease_mismatches(snap, out, seen)
        n, onto = preemptions(snap, out)
        preempted += n
        preempting += onto
    limits = run.cell.limits
    run.check["placement_divergences"] = (divergences, limits["placement_divergences"])
    run.check["fair_share_gap"] = (gap, limits["fair_share_gap"])
    run.check["lease_mismatches"] = (mismatches, limits["lease_mismatches"])
    run.check["unleased"] = (unleased, limits["unleased"])
    run.check["rounds_compared_short"] = (int(traffic["compare_rounds"]) - k, 0)
    run.check["failovers"] = (failovers(plane), 0)
    print("reference " + json.dumps({"rounds": [int(i) for i in picks],
                                      "preempted": preempted,
                                      "preempting_leases": preempting,
                                      "seconds": time.monotonic() - t0}),
          flush=True)


class Record:
    """What the benchmark submitted, by job id: queue, priority class,
    job priority, requests, gang, and the sequence number of its
    SubmitJobs call (the server stamps a call's jobs with one submit
    time, so call order is submit-time order). The reference's round is
    built from it."""

    def __init__(self, client):
        self.client = client
        self.calls = 0
        self.jobs: dict[str, tuple] = {}

    def submit(self, queue, jobset, jobs) -> list[str]:
        ids = self.client.submit_jobs(queue, jobset, [dict(j) for j in jobs])
        self.calls += 1
        for jid, job in zip(ids, jobs):
            gang = job.get("gang") or {}
            self.jobs[jid] = (
                queue, job["priority_class"], int(job["priority"]),
                job["requests"], self.calls, gang.get("id", ""),
                int(gang.get("cardinality", 1)),
            )
        return ids

    def round_for(self, snap, cfg, config):
        """The reference's input for one round the program solved. Which
        jobs the round held, which of them ran and where, and the
        rate-limit tokens (wall-clock state) come from the program's
        round; every job's attributes, the node capacities, the queue
        weights and all that is derived from them come from this record
        and the deployment file. A job the record does not know is left
        out, and counts as a divergence."""
        from benchmark.reference.inputs import build_round

        ex = config["executors"]
        res = {"cpu": ex["cpu"], "memory": ex["memory"]}
        classes = cfg.priority_classes
        J = snap.num_jobs
        ids = [str(x) for x in list(snap.job_ids)[:J]]
        running = np.asarray(snap.job_is_running)[:J]
        node = np.asarray(snap.job_node)[:J]
        cols = {k: [] for k in ("id", "queue", "priority_class", "priority",
                                "submitted", "requests", "node",
                                "running_priority", "gang_id", "gang_card")}
        for j, jid in enumerate(ids):
            rec = self.jobs.get(jid)
            if rec is None:
                continue
            queue, pc, prio, req, call, gang_id, card = rec
            cols["id"].append(jid)
            cols["queue"].append(queue)
            cols["priority_class"].append(pc)
            cols["priority"].append(prio)
            cols["submitted"].append(call)
            cols["requests"].append(req)
            cols["node"].append(snap.node_ids[int(node[j])] if running[j] else "")
            cols["running_priority"].append(classes[pc].priority)
            cols["gang_id"].append(gang_id)
            cols["gang_card"].append(card)
        return build_round(
            cfg, config["pool"], [(n, res) for n in snap.node_ids],
            generate.queue_weights(config), cols,
            global_rate_tokens=snap.global_rate_tokens,
            queue_rate_tokens=snap.queue_rate_tokens,
        )


def open_loop(record, names, arrival, arr, seconds, seed, stream, tick=None):
    """Submit single jobs at the mix's Poisson gaps for `seconds`, each
    into a queue of an even round in an order drawn from the seed.
    Returns [(job id, due time, queue)] and how late each submit began."""
    gaps = generate.arrival_gaps(float(arr["rate_per_s"]), seconds, seed + stream)
    due = np.cumsum(gaps)
    due = due[due < seconds]
    qorder = generate.shuffled_labels(len(due), [1.0] * len(names),
                                      generate.rng_for(seed, stream))
    sent, late = [], []
    t_start = time.perf_counter()
    for i, d in enumerate(due):
        if tick is not None:
            tick(time.perf_counter() - t_start)
        t_due = t_start + d
        wait = t_due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        late.append(time.perf_counter() - t_due)
        queue = names[qorder[i]]
        (jid,) = record.submit(queue, arr["jobset"], [arrival])
        sent.append((jid, t_due, queue))
    rest = t_start + seconds - time.perf_counter()
    if rest > 0:
        time.sleep(rest)
    return sent, late


def backlog(config, seed):
    """(queue, jobset, [job dicts]) per queue: exact shares of the sizes,
    in an order drawn from the seed; the gang goes to its queue first."""
    spec = config["backlog"]
    names = generate.queue_names(config)
    n = int(spec["count"])
    gang = spec.get("gang")
    prio = int(spec["priority"])
    per_queue = generate.exact_counts(n, [1.0] * len(names))
    rng = generate.rng_for(seed, 1)
    for q, name in enumerate(names):
        jobs = []
        count = int(per_queue[q])
        if gang and int(gang["queue"]) == q:
            card = int(gang["cardinality"])
            jobs += [
                {"priority": prio, "priority_class": gang["priority_class"],
                 "requests": {"cpu": gang["cpu"], "memory": gang["memory"]},
                 "gang": {"id": f"gang-{card}", "cardinality": card}}
            ] * card
            count -= card
        jobs += sized_jobs(count, spec["sizes"], prio, spec.get("priority_class"), rng)
        yield name, f"{name}-backlog", jobs


def fill_jobs(config, seed):
    """(queue, [job dicts]) per queue of the fill: its `count` jobs spread
    evenly over the queues, at the backlog's job priority."""
    spec = config["fill"]
    names = generate.queue_names(config)
    prio = int(config["backlog"]["priority"])
    per_queue = generate.exact_counts(int(spec["count"]), [1.0] * len(names))
    rng = generate.rng_for(seed, 17)
    for q, name in enumerate(names):
        yield name, sized_jobs(
            int(per_queue[q]), spec["sizes"], prio, spec["priority_class"], rng
        )


def sized_jobs(count, sizes, prio, priority_class, rng):
    """`count` job dicts in the exact shares of `sizes`, in an order drawn
    from rng; a size without a priority class takes `priority_class`."""
    labels = generate.shuffled_labels(count, [s["share"] for s in sizes], rng)
    kinds = [
        {"priority": prio, "priority_class": s.get("priority_class", priority_class),
         "requests": {"cpu": s["cpu"], "memory": s["memory"]}}
        for s in sizes
    ]
    return [kinds[i] for i in labels]


def fleet_cpu(config) -> float:
    ex = config["executors"]
    return float(int(ex["count"]) * int(ex["nodes"]) * quantity(ex["cpu"]))


def wait_filled(record, watchers, fill_ids, fleet, fraction) -> float:
    """Wait until the fill's leases its watchers have seen hold `fraction`
    of the fleet's `fleet` CPU; returns the CPU they hold. Fails the run
    after FILL_WAIT_S seconds."""
    deadline = time.monotonic() + FILL_WAIT_S
    while True:
        leased = leased_times(watchers)
        cpu = float(sum(quantity(record.jobs[j][3]["cpu"])
                          for j in fill_ids if j in leased))
        if cpu >= fraction * fleet:
            return cpu
        if time.monotonic() >= deadline:
            raise RuntimeError(
                f"set-up: the fill leased {cpu:g} of the fleet's {fleet:g} CPU in "
                f"{FILL_WAIT_S:g} s; its until_fraction asks for {fraction:g}"
            )
        time.sleep(0.2)


def preemptions(snap, out) -> tuple[int, int]:
    """The jobs a round preempted, and its leases onto a node on which it
    preempted one."""
    J = snap.num_jobs
    pre = np.flatnonzero(np.asarray(out["preempted_mask"])[:J])
    if not len(pre):
        return 0, 0
    nodes = np.unique(np.asarray(snap.job_node)[pre])
    sched = np.flatnonzero(np.asarray(out["scheduled_mask"])[:J])
    onto = np.isin(np.asarray(out["assigned_node"])[sched], nodes)
    return len(pre), int(onto.sum())


class RoundKeeper:
    """Wraps the scheduler's `_solve`: counts the rounds solved, notes
    each padded round shape the solve meets first (seconds since the
    first round, and the shape), and while `on` copies each round's
    snapshot and keeps it with its decisions."""

    KEYS = ("assigned_node", "scheduled_mask", "preempted_mask",
            "scheduled_priority", "fair_share", "demand_capped_fair_share",
            "uncapped_fair_share")

    def __init__(self, sched, pool):
        self.on = False
        self.solved = 0
        self.rounds = []
        self.shapes: list = []
        self.t_first = None
        solve = sched._solve

        def kept(snap, *args, **kwargs):
            out = solve(snap, *args, **kwargs)
            self.solved += out is not None
            shape = round_shape(sched._resident.get(pool))
            if shape is not None and all(shape != s for _, s in self.shapes):
                self.t_first = self.t_first or time.monotonic()
                self.shapes.append((self.since_first(), shape))
            if self.on and out is not None and snap.num_jobs:
                self.rounds.append(
                    (frozen(snap), {k: np.array(out[k]) for k in self.KEYS})
                )
            return out

        sched._solve = kept

    def since_first(self) -> float:
        return round(time.monotonic() - (self.t_first or time.monotonic()), 1)


def round_shape(resident):
    """(jobs, nodes, slots, slot members, queues, key groups) of the
    padded device round the residency sync holds, or None."""
    host = resident.host_round() if resident is not None else None
    if host is None:
        return None
    return (host.job_req.shape[0], host.node_total.shape[0],
            *host.slot_members.shape, host.queue_weight.shape[0],
            int(host.num_key_groups))


def frozen(snap):
    """A copy of a snapshot that later deltas cannot change."""
    fields = {}
    for f in dataclasses.fields(snap):
        v = getattr(snap, f.name)
        if isinstance(v, np.ndarray):
            fields[f.name] = v.copy()
        elif isinstance(v, (list, dict)):
            fields[f.name] = type(v)(v)
    return dataclasses.replace(snap, **fields)


def annotate(plane):
    """Host spans on the profiler's clock around the plane's phases, so
    the trace can say what the host did while the device idled."""
    import jax

    def wrap(obj, attr, name):
        fn = getattr(obj, attr)

        def wrapped(*a, **k):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)

        setattr(obj, attr, wrapped)

    sched = plane.scheduler
    wrap(sched, "cycle", "bench.cycle")
    wrap(sched, "_schedule_pool", "bench.round")
    wrap(sched, "_solve", "bench.solve")
    wrap(sched.ingester, "sync", "bench.ingest")
    wrap(plane.lookout_store, "sync", "bench.lookout")
    for ex in plane.executors:
        wrap(ex, "tick", "bench.executor")
    wrap(plane._stop, "wait", "bench.cycle_wait")


class Watcher:
    """One WatchJobSet stream on its own channel and thread: the time
    each job's JobRunLeased event arrived, and its node."""

    def __init__(self, address, queue, jobset, connect):
        self.client = connect(address)
        self.leased: dict[str, tuple[float, str]] = {}
        self.lock = threading.Lock()
        self.thread = threading.Thread(
            target=self._follow, args=(queue, jobset), daemon=True
        )
        self.thread.start()

    def _follow(self, queue, jobset):
        try:
            for ev in self.client.watch_jobset(queue, jobset, watch=True):
                if ev.get("type") == "JobRunLeased":
                    t = time.perf_counter()
                    with self.lock:
                        self.leased.setdefault(ev["job_id"], (t, ev.get("node_id", "")))
        except Exception:  # noqa: BLE001 - the stream ends when closed
            return

    def close(self):
        self.client.channel.close()
        self.thread.join(timeout=10)


def leased_times(watchers) -> dict:
    out = {}
    for w in watchers:
        with w.lock:
            out.update(w.leased)
    return out


def lease_mismatches(snap, out, seen) -> int:
    """Leases a watcher saw from this round that are not its decision."""
    sched = np.flatnonzero(np.asarray(out["scheduled_mask"])[: snap.num_jobs])
    bad = 0
    for j in sched:
        jid = str(snap.job_ids[j])
        if jid in seen:
            node = snap.node_ids[int(out["assigned_node"][j])]
            bad += seen[jid][1] != node
    return bad


def half_percentile(sent, seen, seconds, half):
    """Median lease latency of the arrivals due in one half of the
    window: flat across halves means the load is sustained."""
    lat = [
        seen[jid][0] - t
        for jid, t, _ in sent
        if jid in seen and (t - sent[0][1] >= seconds / 2) == bool(half)
    ]
    return float(np.percentile(lat, 50)) if lat else None


def read_counters(plane, pool_name) -> dict:
    reg = plane.metrics.registry
    if reg is None:
        return {}
    get = reg.get_sample_value
    pool = {"pool": pool_name}
    return {
        "scheduler_cycle_seconds_sum": get("scheduler_cycle_seconds_sum") or 0.0,
        "scheduler_cycle_seconds_count": get("scheduler_cycle_seconds_count") or 0.0,
        "scheduler_solve_seconds_sum": get("scheduler_solve_seconds_sum", pool) or 0.0,
        "scheduler_solve_seconds_count": get("scheduler_solve_seconds_count", pool) or 0.0,
    }


def counter_delta(before, after) -> dict:
    return {k: after[k] - before.get(k, 0.0) for k in after}


def wait_solved(plane, keeper, timeout):
    """Wait for the first solved round (its compile or cache load);
    returns the cycles it took."""
    start = plane.scheduler.cycle_count
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if keeper.solved:
            return plane.scheduler.cycle_count - start
        time.sleep(0.1)
    raise TimeoutError("the control plane solved no round")


def failovers(plane) -> int:
    n = sum(
        s.value
        for fam in plane.metrics.solver_failover.collect()
        for s in fam.samples
        if s.name.endswith("_total")
    )
    sched = plane.scheduler
    return int(n) + len(sched.recent_failovers) + len(sched.recent_rejections)
