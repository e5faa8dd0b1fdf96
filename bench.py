"""Benchmark: full scheduling rounds on the device (TPU when available).

The headline metric is the WARM END-TO-END CYCLE on the flagship config —
what a production scheduler pays per round at steady state: apply last
round's leases + fresh submissions to the resident `IncrementalRound`
state, assemble the snapshot, prep the device tensors (PrepCache), upload,
and solve. This is the number to compare against the reference's 5s
`maxSchedulingDuration` guard (config/scheduler/config.yaml:83); the
round-4 headline measured the solve alone and hid a 5.5s host rebuild.

Configs (one JSON line printed, flagship as the headline):

  1. tracking: 100k jobs x 5k nodes  — like-for-like vs earlier rounds,
     reported under extra.tracking_100k.
  2. flagship: 1M jobs x 50k nodes   — the north-star config
     (BASELINE.json: one round < 1s on v5e-8). vs_baseline = 5.0 / value.
  3. burst_50k: flagship with the scheduling burst raised to 50k jobs per
     round — the regime where batched fast-fill and while_loop trip
     counts actually matter (reference operating point:
     config/scheduler/config.yaml:101-108). Under extra.burst_50k.

The platform the numbers were measured on is part of the metric string
(JAX's configured platform; nothing falls back to another one).

Env overrides: BENCH_JOBS/BENCH_NODES/BENCH_QUEUES/BENCH_RUNNING pick a
single custom config instead (BENCH_BURST raises its per-round
scheduling burst — the forced-rewindow regime at custom scale); BENCH_FLAGSHIP=0 skips the 1M x 50k runs;
BENCH_BURST50K=0 skips the burst run; BENCH_FAST_FILL=0 runs the serial
parity-mode fill; BENCH_WARM_CYCLES sets the warm-sample count (>=2,
default 5); BENCH_ROUND_BUDGET_S runs every solve through the
budget-aware chunked driver (maxSchedulingDuration) and reports
truncation — the burst_50k config with BENCH_ROUND_BUDGET_S=5 is the
round-deadline acceptance scenario; BENCH_HOT_WINDOW sets the per-queue
hot-window compaction size (0 disables; default: 2x the fill window);
BENCH_FILL_WINDOW sets batch_fill_window (wide windows amortize the
per-group candidate sort, the dominant per-loop cost at 50k nodes);
ARMADA_TPU_KERNEL_PATH picks the solve kernel path (default here:
"blocked" — the fused scoring body + radix-threshold selection from
armada_tpu/ops/pallas_kernels.py; =lax reproduces the pre-kernel bench
for the A/B, =pallas runs the pallas kernels (Mosaic on a TPU,
interpreted elsewhere), =native adds the ICI ring winner exchange on a
TPU mesh) and the resolved path lands under extra.kernels;
BENCH_TUNED=<tuned.json> applies the tools/autotune.py profile matching
this host's target signature (hot window + budgeted chunk stride) to
every config — the A/B against the static defaults is just the same
bench run with and without the variable; the effective (possibly tuned)
parameters are always recorded under extra.params so artifacts are
self-describing either way.

The LAST stdout line is always one JSON object with an "ok" flag — on
any failure it carries ok=false and the error instead of silently dying
mid-run, so artifact parsers (tools/bench_trend.py, tools/bench_gate.py)
never see a half-written result.
"""

import json
import os
import time

# XLA's C++ INFO/WARNING log lines flood bench tails; errors still print.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")

N_QUEUES = int(os.environ.get("BENCH_QUEUES", 10))
# Running preemptible jobs (exercises eviction + fair preemption paths).
N_RUNNING = int(os.environ.get("BENCH_RUNNING", 0))


def resolve_fill_window(fill_window=None) -> int:
    """The effective batch_fill_window: BENCH_FILL_WINDOW env override,
    else the per-config value, else 2048. One resolution shared by
    build_inputs and run_config's hot-window sizing so the '~2x the fill
    window' invariant cannot drift between the two sites."""
    return int(os.environ.get("BENCH_FILL_WINDOW", fill_window or 2048))


def tuned_params():
    """The BENCH_TUNED profile entry matching this host's target
    signature, as a TunedParams, or None (no profile / no match).
    Resolved once per process."""
    global _TUNED
    if _TUNED is not _UNSET:
        return _TUNED
    _TUNED = None
    path = os.environ.get("BENCH_TUNED")
    if path:
        from armada_tpu.autotune import TunedParams, TuningStore, current_target

        store = TuningStore()
        store.merge_json(path)
        entry = store.lookup(current_target(), "default")
        if entry is None:
            print(f"# BENCH_TUNED: no entry in {path} matches this target; "
                  "running static defaults")
        else:
            _TUNED = TunedParams.from_dict(entry["params"])
            print(f"# BENCH_TUNED: applying {entry['params']} "
                  f"(source={entry.get('source')})")
    return _TUNED


_UNSET = object()
_TUNED = _UNSET


def build_inputs(n_jobs, n_nodes, burst=None, fill_window=None):
    import numpy as np

    from armada_tpu.core.config import (
        PriorityClass,
        RateLimits,
        SchedulingConfig,
    )
    from armada_tpu.core.types import JobSpec, NodeSpec, QueueSpec, RunningJob

    kw = {}
    if burst:
        kw["rate_limits"] = RateLimits(
            maximum_scheduling_rate=float(burst),
            maximum_scheduling_burst=burst,
            maximum_per_queue_scheduling_burst=burst,
        )
    cfg = SchedulingConfig(
        priority_classes={
            "high": PriorityClass("high", 30000, preemptible=False),
            "low": PriorityClass("low", 1000, preemptible=True),
        },
        default_priority_class="low",
        protected_fraction_of_fair_share=0.5 if N_RUNNING else 1.0,
        # Fast mode: batch the multi-queue sweep (set-exact vs the serial
        # loop when everything fits; see SchedulingConfig.enable_fast_fill).
        enable_fast_fill=os.environ.get("BENCH_FAST_FILL", "1") == "1",
        # Wide fill windows amortize the per-group best-fit candidate
        # sort (the dominant per-loop cost at 50k+ nodes) over more
        # placements per loop; burst drains in ~3 merged loops at 2048.
        # The tracking config keeps the historical 512 (like-for-like).
        batch_fill_window=resolve_fill_window(fill_window),
        **kw,
    )
    rng = np.random.default_rng(0)
    nodes = [
        NodeSpec(
            id=f"node-{i:05d}",
            pool="default",
            total_resources={"cpu": "32", "memory": "256Gi"},
        )
        for i in range(n_nodes)
    ]
    queues = [QueueSpec(f"queue-{i:02d}", 1.0) for i in range(N_QUEUES)]
    cpus = rng.choice([1, 2, 4, 8], size=n_jobs)
    qidx = rng.integers(0, N_QUEUES, size=n_jobs)
    queued = [
        JobSpec(
            id=f"job-{i:07d}",
            queue=f"queue-{qidx[i]:02d}",
            priority_class="low",
            requests={"cpu": str(int(cpus[i])), "memory": f"{int(cpus[i]) * 2}Gi"},
            submitted_ts=float(i),
        )
        for i in range(n_jobs)
    ]
    # Running jobs all in one hog queue (over fair share -> evicted and
    # mostly rescheduled, driving the eviction + fair-preemption machinery).
    running = [
        RunningJob(
            job=JobSpec(
                id=f"run-{i:07d}",
                queue="queue-00",
                priority_class="low",
                requests={"cpu": "2", "memory": "4Gi"},
                submitted_ts=float(-N_RUNNING + i),
            ),
            node_id=f"node-{i % n_nodes:05d}",
            scheduled_at_priority=1000,
        )
        for i in range(N_RUNNING)
    ]
    return cfg, "default", nodes, queues, running, queued


def _put(dev):
    import jax
    import numpy as np

    from armada_tpu.observe import note_up

    # Transfer ledger: the explicit warm-cycle upload — what a
    # device-resident round (ROADMAP 1) would mostly eliminate.
    note_up(dev, site="bench.put")
    out = jax.tree_util.tree_map(
        lambda x: jax.device_put(x) if isinstance(x, np.ndarray) else x, dev
    )
    jax.block_until_ready(
        [
            x
            for x in jax.tree_util.tree_leaves(out)
            if hasattr(x, "block_until_ready")
        ]
    )
    return out


def run_config(n_jobs, n_nodes, burst=None, mesh=None, fill_window=None,
               hot_window=None, trace_path=None):
    """Cold build, one shape-settling warm cycle, then >=5 measured warm
    cycles (BENCH_WARM_CYCLES): the headline is the MEDIAN cycle with its
    spread (min/max + IQR), not a single sample — a single warm cycle can
    land on a GC pause or a padded-shape recompile and misreport by 2x."""
    import numpy as np

    from armada_tpu.core.types import JobSpec
    from armada_tpu.snapshot.incremental import IncrementalRound
    from armada_tpu.solver.kernel import solve_round as _single_solve
    from armada_tpu.solver.kernel_prep import pad_device_round

    budget_s = float(os.environ.get("BENCH_ROUND_BUDGET_S", 0) or 0) or None
    raw_window = os.environ.get("BENCH_HOT_WINDOW")
    tuned = tuned_params()
    chunk_loops = 1
    # Historical bench behavior: no engagement floor (window choice is
    # per bench config). A tuned profile overrides the WHOLE vector,
    # floor included — the A/B must measure exactly what production
    # would run, not a floor-stripped variant of it.
    window_min_slots = 0
    applied_tuned = False
    if raw_window is not None:
        hot_window = int(raw_window)
    elif hot_window is None:
        if tuned is not None:
            # BENCH_TUNED profile — only for configs that don't pin
            # their own window (tracking keeps its historical fixed
            # parameters for like-for-like comparability).
            hot_window = tuned.hot_window_slots
            window_min_slots = tuned.hot_window_min_slots
            chunk_loops = tuned.chunk_loops
            applied_tuned = True
        else:
            # 2x the fill window: one gather covers ~two merged fill loops.
            hot_window = 2 * resolve_fill_window(fill_window)
    sharded = None
    if mesh:
        # mesh is a spec: int (1D chip count) or "HxC" (two-level
        # hosts x chips hierarchy, parallel/multihost.py). The sharded
        # solve is one fused program (no hot-window chunking — the
        # tracked sharded-round-budget gap).
        from armada_tpu.parallel.mesh import pad_nodes
        from armada_tpu.parallel.multihost import resolve_solver

        from armada_tpu.ops import pallas_kernels as _pk

        sharded = resolve_solver(mesh, kernel_path=_pk.resolve_kernel_path())

        def solve_round(dev, rows=None):
            return sharded(pad_nodes(dev, sharded.n_shards))
    else:
        # Single-device driver: hot-window compaction when the round is
        # big enough to pay (solver/hotwindow.py), the budget-aware
        # chunked pass 1 when BENCH_ROUND_BUDGET_S is set, the fused
        # program otherwise — all in solver/kernel.solve_round. The
        # min-slots floor is 0 (window choice is per bench config)
        # UNLESS a BENCH_TUNED profile supplied the full vector, floor
        # included — the A/B must measure what production would run.
        def solve_round(dev, rows=None):
            # rows (the live-job count) trims the warm-cycle readback to
            # the unpadded decision prefix; bench_gate holds the booked
            # bytes_down under its transfer budget.
            return _single_solve(
                dev, budget_s=budget_s, chunk_loops=chunk_loops,
                window=hot_window or None,
                window_min_slots=window_min_slots,
                readback_rows=rows,
            )

    t_setup = time.time()
    inputs = build_inputs(n_jobs, n_nodes, burst=burst, fill_window=fill_window)
    inc = IncrementalRound(*inputs)
    setup_s = time.time() - t_setup

    # Device-resident round state (armada_tpu/snapshot/residency.py):
    # the default warm cycle keeps the padded DeviceRound on device and
    # delta-syncs it, the way the scheduler's "resident" snapshot mode
    # runs. BENCH_RESIDENT=0 restores the legacy re-upload-every-cycle
    # path (the before/after axis for the transfer ledger). The sharded
    # solve re-pads and re-places the node axis per round, so mesh runs
    # always re-upload.
    resident = None
    if sharded is None and os.environ.get("BENCH_RESIDENT", "1") not in ("0", "false"):
        from armada_tpu.snapshot.residency import ResidentRound

        resident = ResidentRound()

    t0 = time.time()
    if resident is not None:
        dev = resident.device_round(inc)  # full reset upload, cold
    else:
        dev = _put(pad_device_round(inc.device_round()))
    h2d_cold_s = time.time() - t0
    t0 = time.time()
    out = solve_round(dev)  # compile + run on the padded flagship shape
    compile_s = time.time() - t0

    next_id = 0

    def warm_cycle(out):
        """One steady-state cycle: lease last round's decisions, take new
        submissions, re-solve. Returns (timings, out)."""
        nonlocal next_id
        snap = inc.snapshot()
        J = snap.num_jobs
        sched = np.flatnonzero(np.asarray(out["scheduled_mask"])[:J])
        assigned = np.asarray(out["assigned_node"])[:J]
        prio = np.asarray(out["scheduled_priority"])[:J]
        leases = [
            (
                str(snap.job_ids[j]),
                snap.node_ids[int(assigned[j])],
                int(prio[j]),
                1.0,
            )
            for j in sched
        ]
        new_jobs = [
            JobSpec(
                id=f"cycle-{next_id + i:08d}",
                queue=f"queue-{i % N_QUEUES:02d}",
                priority_class="low",
                requests={"cpu": "2", "memory": "4Gi"},
                submitted_ts=3e6 + next_id + i,
            )
            for i in range(len(leases))
        ]
        next_id += len(leases)
        # Round observatory (armada_tpu/observe): one transfer ledger +
        # compile-telemetry delta per warm cycle, so every artifact
        # carries extra.transfer — bytes up/down, donated buffers, and
        # the warm-cycle compile count (which must be ZERO: a compile
        # here is the silent-warm-recompile failure mode).
        from armada_tpu.observe import TELEMETRY, round_ledger

        comp0 = TELEMETRY.snapshot()
        with round_ledger() as led:
            t0 = time.time()
            inc.bind(leases)
            inc.add_jobs(new_jobs)
            delta_s = time.time() - t0
            t0 = time.time()
            if resident is not None:
                # Delta sync into the persistent device buffers: prep
                # (inc.device_round), diff against the host mirror, and
                # the scatter upload are one fused step, booked as h2d.
                dev = resident.device_round(inc)
                dev_h = resident.host_round()
                prep_s = 0.0
                h2d_s = time.time() - t0
            else:
                dev = inc.device_round()
                prep_s = time.time() - t0
                t0 = time.time()
                dev_h = pad_device_round(dev)
                dev = _put(dev_h)
                h2d_s = time.time() - t0
            t0 = time.time()
            out = solve_round(dev, rows=snap.num_jobs + len(new_jobs))
            solve_s = time.time() - t0
        # Round admission firewall (armada_tpu/solver/validate.py): time
        # the host-side invariant sweep the scheduler runs before every
        # commit. Measured OUTSIDE the cycle window (it overlaps the next
        # round's delta phase in production) but reported so bench_gate
        # can hold its cost under 5% of solve time.
        from armada_tpu.solver.validate import validate_round

        t0 = time.time()
        try:
            validate_round(
                {k: np.asarray(v) for k, v in out.items()
                 if k not in ("profile", "truncated")},
                dev=dev_h,
            )
        except Exception:  # noqa: BLE001 - bench measures cost, not verdicts
            pass
        validate_s = time.time() - t0
        timings = {
            "delta_s": round(delta_s, 3),
            "prep_s": round(prep_s, 3),
            "h2d_s": round(h2d_s, 3),
            "solve_s": round(solve_s, 3),
            "validate_s": round(validate_s, 4),
            "cycle_s": round(delta_s + prep_s + h2d_s + solve_s, 4),
            "scheduled_jobs": int(np.asarray(out["scheduled_mask"]).sum()),
            "loops": int(out["num_loops"]),
            "transfer": {
                **led.as_dict(),
                "compiles": TELEMETRY.delta_since(comp0),
            },
        }
        if "truncated" in out:
            timings["round_truncated"] = bool(out["truncated"])
        if "profile" in out:
            # Per-segment solve profile (setup / pass-1 / gather /
            # finish wall clock + loop mix) from the host-driven driver.
            timings["segments"] = out["profile"]
        return timings, out

    first, out = warm_cycle(out)  # may pay a shape-change compile once
    n_warm = max(2, int(os.environ.get("BENCH_WARM_CYCLES", 5)))
    samples = []
    for _ in range(n_warm):
        warm, out = warm_cycle(out)
        samples.append(warm)

    # Fairness observatory (armada_tpu/observe/fairness.py): the last
    # measured cycle's share ledger — Jain index + max regret land in
    # extra.fairness so tools/bench_trend.py tracks fairness alongside
    # speed. Computed OUTSIDE the measured window and outside any
    # transfer ledger (the O(J) result readback must not book into
    # extra.transfer or the cycle time).
    fairness_extra = {}
    try:
        from armada_tpu.observe.fairness import ledger_from_device_round

        snap_f = inc.snapshot()
        block = ledger_from_device_round(
            pad_device_round(inc.device_round()),
            {k: np.asarray(v) for k, v in out.items()
             if k not in ("profile", "truncated")},
            snap_f.num_jobs,
            snap_f.num_queues,
        )
        fairness_extra["fairness"] = {
            "jain": block["ledger"]["jain"],
            "max_regret": block["ledger"]["max_regret"],
            "preemptions_attributed": len(block["preemptions"]),
            "policy": block["ledger"].get("policy", "drf"),
        }
    except Exception as e:  # noqa: BLE001 - advisory, never fails the bench
        fairness_extra["fairness"] = {"error": f"{e.__class__.__name__}: {e}"}

    import statistics

    times = sorted(s["cycle_s"] for s in samples)
    median = statistics.median(times)
    q1, _, q3 = statistics.quantiles(times, n=4, method="inclusive")
    # The reported component breakdown comes from the median-cycle sample
    # (closest to the reported headline), spread from all samples.
    rep = min(samples, key=lambda s: abs(s["cycle_s"] - median))
    residency_extra = {}
    if resident is not None and resident.last_sync:
        # Self-describing artifact: which snapshot path produced the
        # headline (resident delta vs full reset) and the warm upload it
        # booked — tools/bench_trend.py shows this as the residency
        # column, tools/bench_gate.py holds bytes_up under the budget.
        residency_extra["residency"] = {
            "mode": str(resident.last_sync.get("mode")),
            "bytes_up": (rep.get("transfer") or {}).get("bytes_up"),
            "permuted": bool(resident.last_sync.get("permuted")),
        }
    mesh_extra = {}
    if sharded is not None:
        shape = sharded.mesh_shape
        hosts, chips = shape if len(shape) == 2 else (1, shape[0])
        mesh_extra["mesh"] = {
            "hosts": hosts,
            "chips": chips,
            # Trace-time accounting of the executed program's collectives
            # (solver/dist.CollectiveStats): sites + bytes per execution
            # by fabric level; multiply by `loops` for per-cycle totals.
            "collectives": (
                (sharded.last_stats or sharded.stats).as_dict()
                if sharded.stats
                else None
            ),
        }
    trace_extra = {}
    if trace_path:
        # Flight recorder (armada_tpu/trace): one extra, UNMEASURED warm
        # cycle appended to the .atrace bundle — the recorded round is
        # exactly the steady-state solve the headline median describes,
        # replayable forever by tools/replay_gate.py. The recorder
        # replaces any stale bundle at the path.
        from armada_tpu.trace import TraceRecorder

        snap = inc.snapshot()
        dev_np = pad_device_round(inc.device_round())
        out_rec = solve_round(_put(dev_np))
        solver_info = {"backend": "kernel", "mesh": str(mesh) if mesh else None,
                       "window": hot_window or 0, "budget": bool(budget_s),
                       "resident": resident is not None}
        with TraceRecorder(
            trace_path, source="bench", config=inputs[0],
            seeds={"workload_seed": 0},
            meta={"n_jobs": n_jobs, "n_nodes": n_nodes, "burst": burst},
        ) as rec:
            rec.record_round(
                pool="default", dev=dev_np,
                decisions={k: np.asarray(v) for k, v in out_rec.items()
                           if k not in ("profile", "truncated")},
                num_jobs=snap.num_jobs, num_queues=snap.num_queues,
                config=inputs[0], solver=solver_info,
                truncated=bool(out_rec.get("truncated", False)),
                profile=out_rec.get("profile"),
            )
        # Marker consumed by tools/bench_trend.py — set ONLY when THIS
        # run recorded the bundle (a stale file from an earlier revision
        # must not be advertised as this artifact's trace).
        trace_extra["trace_path"] = os.path.basename(trace_path)
    params_extra = {}
    if sharded is None:
        # The EFFECTIVE solver parameters this config ran with (possibly
        # tuned via BENCH_TUNED) — artifacts are self-describing, and
        # tools/bench_trend.py shows the vector across rounds. Mesh runs
        # record none: the sharded solve takes no window/chunk vector,
        # so claiming one was in effect would make the artifact lie.
        params_extra["params"] = {
            "hot_window_slots": int(hot_window or 0),
            "hot_window_min_slots": int(window_min_slots),
            "chunk_loops": int(chunk_loops),
            "fill_window": resolve_fill_window(fill_window),
            "tuned": applied_tuned,
        }
    return {
        **mesh_extra,
        **trace_extra,
        **params_extra,
        **fairness_extra,
        **residency_extra,
        "cycle_s": round(median, 4),
        **{k: v for k, v in rep.items() if k != "cycle_s"},
        "warm_cycles_measured": len(times),
        "cycle_s_min": round(times[0], 4),
        "cycle_s_max": round(times[-1], 4),
        "cycle_s_iqr": round(q3 - q1, 4),
        "cycle_s_samples": [round(x, 4) for x in times],
        "compile_s": round(compile_s, 1),
        "cold_build_s": round(setup_s, 1),
        "cold_h2d_s": round(h2d_cold_s, 3),
        "first_warm_cycle_s": first["cycle_s"],
        "decisions_sha256": _decisions_digest(out),
    }


def _decisions_digest(out) -> str:
    """sha256 over the last solve's decision arrays: two runs of the same
    inputs (e.g. two kernel paths) must agree on it."""
    import hashlib

    import numpy as np

    h = hashlib.sha256()
    for key in sorted(out):
        if key not in ("profile", "truncated"):
            h.update(key.encode())
            h.update(np.ascontiguousarray(np.asarray(out[key])).tobytes())
    return h.hexdigest()


def main():
    """Run the bench matrix; ALWAYS prints one final JSON line.

    Success: the full result with ok=true. Any exception: ok=false with
    the error and whatever sub-results completed, so downstream parsers
    get a parseable (if partial) artifact instead of a truncated tail."""
    partial = {}
    try:
        result = _run_matrix(partial)
        result["ok"] = True
    # KeyboardInterrupt/SystemExit propagate: a deliberate cancellation
    # is not a bench failure and must not mint an ok=false artifact.
    except Exception as e:  # noqa: BLE001 - the artifact IS the report
        import traceback

        result = {
            "metric": "warm_cycle_end_to_end",
            "value": None,
            "unit": "s",
            "ok": False,
            "error": f"{e.__class__.__name__}: {e}",
            "traceback": traceback.format_exc().splitlines()[-6:],
            # Sub-results that completed before the failure (e.g. the
            # tracking run when the burst config OOMs) stay usable by
            # tools/bench_trend.py / bench_gate.py.
            "extra": partial,
        }
    print(json.dumps(result), flush=True)
    if not result["ok"]:
        raise SystemExit(1)


def _run_matrix(partial=None):
    # BENCH_MESH spellings: "8" (1D, 8 chips on one host) or "2x4"
    # (two-level hosts x chips hierarchy, parallel/multihost.py).
    raw_mesh = os.environ.get("BENCH_MESH", "0").lower()
    if "x" in raw_mesh:
        hosts, chips = (int(t) for t in raw_mesh.lower().split("x", 1))
        mesh, n_mesh_devices = raw_mesh, hosts * chips
    else:
        n_mesh_devices = int(raw_mesh or 0)
        mesh = n_mesh_devices or None
    if mesh and os.environ.get("JAX_PLATFORMS") == "cpu":
        # Virtual multi-device mesh on the CPU platform: must be set
        # before the first jax import. On a TPU host the sharded path
        # uses the real chips.
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_mesh_devices}"
        )

    from armada_tpu.core.resources import ensure_native
    from armada_tpu.utils.platform import setup_jax

    ensure_native()  # C++ quantity parser (one-time build on fresh checkouts)
    platform = setup_jax()

    # Solve-kernel path (armada_tpu/ops/pallas_kernels.py): the bench
    # defaults to the blocked path — the fused scoring body plus the
    # radix-threshold top-B selection that replaces the per-fill-loop
    # lexsort, the measured CPU win. ARMADA_TPU_KERNEL_PATH is the A/B
    # lever: =lax reproduces the pre-kernel bench exactly, =pallas runs
    # the same body under pl.pallas_call (interpret mode off-TPU),
    # =native adds the ICI ring winner exchange on real hardware.
    from armada_tpu.ops import pallas_kernels as _pk

    os.environ.setdefault(_pk.PATH_ENV, "blocked")
    kernel_path = _pk.resolve_kernel_path("blocked")

    custom = any(
        k in os.environ
        for k in ("BENCH_JOBS", "BENCH_NODES", "BENCH_QUEUES", "BENCH_RUNNING")
    )
    if partial is None:
        partial = {}
    # Flight recorder (off by default): BENCH_TRACE=<path> (or =1 for
    # BENCH_trace.atrace next to the BENCH_r*.json artifacts) records the
    # flagship/custom config's warm cycle to an .atrace bundle.
    trace_path = os.environ.get("BENCH_TRACE") or None
    if trace_path == "1":
        trace_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_trace.atrace"
        )
    tracking = burst50k = None
    if custom:
        n_jobs = int(os.environ.get("BENCH_JOBS", 100_000))
        n_nodes = int(os.environ.get("BENCH_NODES", 5000))
        # BENCH_BURST raises the per-round scheduling burst on the
        # custom config (the burst_50k regime at custom scale — the
        # autotune A/B's forced-rewindow scenario).
        burst = int(os.environ.get("BENCH_BURST", 0) or 0) or None
        flag = run_config(n_jobs, n_nodes, burst=burst, mesh=mesh,
                          trace_path=trace_path)
    else:
        n_jobs, n_nodes = 1_000_000, 50_000
        # Like-for-like vs earlier rounds: the historical 512 fill
        # window, no hot-window compaction (a 100k round cannot
        # amortize the host-driven driver's fixed overhead).
        tracking = run_config(
            100_000, 5000, mesh=mesh, fill_window=512, hot_window=0,
        )
        partial["tracking_100k"] = tracking
        if os.environ.get("BENCH_FLAGSHIP", "1") == "1":
            flag = run_config(n_jobs, n_nodes, mesh=mesh, trace_path=trace_path)
            partial["flagship"] = flag
            if os.environ.get("BENCH_BURST50K", "1") == "1":
                burst50k = run_config(n_jobs, n_nodes, burst=50_000, mesh=mesh)
                partial["burst_50k"] = burst50k
        else:
            flag, (n_jobs, n_nodes) = tracking, (100_000, 5000)
            tracking = None
    extra = dict(flag)
    cycle_s = extra.pop("cycle_s")
    extra["platform"] = platform
    # Which kernel path produced the headline (artifacts are self-
    # describing): resolved path + the block geometry the pallas path
    # would run with at the headline node count. tools/bench_trend.py
    # shows this as the kernels column.
    extra["kernels"] = _pk.kernel_info(kernel_path, n_nodes)
    if mesh:
        extra["mesh_devices"] = n_mesh_devices
    if tracking is not None:
        extra["tracking_100k"] = tracking
    if burst50k is not None:
        extra["burst_50k"] = burst50k
    return {
        "metric": (
            f"warm_cycle_end_to_end({n_jobs} jobs x {n_nodes} nodes, "
            f"{N_QUEUES} queues, burst-limited, {platform})"
        ),
        "value": cycle_s,
        "unit": "s",
        "vs_baseline": round(5.0 / cycle_s, 2),
        "extra": extra,
    }


if __name__ == "__main__":
    main()
