"""Chip smoke: the scheduling round's main path, once, on one TPU.

    python chip_smoke.py              # one chip: round, served, kernels
    python chip_smoke.py --chips 4    # the node-sharded solve on 4 chips
    python chip_smoke.py --rehearse   # the same phases, tiny, on the CPU

Phases, each printing one `PHASE {json}` line:

- round: the flagship round (bench.build_inputs: 1M queued x 50k nodes x
  10 queues) on the shipped `lax` path and the bench's `blocked` path,
  through bench.run_config (one cold cycle, then warm ones). Both paths
  must reach the same decisions.
- served: ControlPlane(backend="kernel") in the exact (x64)
  configuration: 10 fake executors x 500 nodes of 8 CPU / 128Gi, a
  backlog of 100k jobs over 10 queues (two priority classes, one 128-way
  gang) submitted through the gRPC client API. Cycles run until leases
  flow. Any solver failover or non-primary rung fails the phase. The
  first round is recorded by the flight recorder; its placements must
  equal the oracle's (solver/reference.py) on the same snapshot, and a
  replay of the recording on the device must reproduce it.
- kernels: the pallas scoring and winner kernels compiled natively
  (Mosaic), bit-exact against the blocked path on the round's snapshot.
- mesh (--chips 4 only): the node-sharded solve (tracking config, 100k x
  5k) on a 1-D `4` mesh and a `2x2` host mesh (on a TPU the `native`
  path: pallas kernels plus the ICI ring winner exchange), bit-exact
  with the one-chip solve in the same process.

Everything runs in this one process, which alone holds the chip; the
control plane's gRPC clients are threads of it. The last stdout line is
one JSON object: `{"ok": true, "device": {...}}` only when every phase
passed on a TPU. Any failure, a non-TPU device or an interpret-mode
kernel prints `"ok": false` and exits 1. --rehearse runs on the CPU and
so never reports a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

# (jobs, nodes) per phase: full size on the chip, tiny for --rehearse.
FULL = {"round": (1_000_000, 50_000), "served": (100_000, 10), "mesh": (100_000, 5000)}
TINY = {"round": (3000, 1000), "served": (2000, 2), "mesh": (2000, 128)}
SERVED_NODES_PER_EXECUTOR = {False: 500, True: 20}
GANG = 128


def emit(phase: str, **fields) -> None:
    print(f"PHASE {json.dumps({'phase': phase, **fields})}", flush=True)


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# round
# ---------------------------------------------------------------------------


def phase_round(size, device) -> None:
    import bench
    from armada_tpu.ops.pallas_kernels import PATH_ENV

    # The bench's own levers: the kernel path and the warm-cycle count.
    os.environ["BENCH_WARM_CYCLES"] = "2"
    digests = {}
    for path in ("lax", "blocked"):
        os.environ[PATH_ENV] = path
        r = bench.run_config(*size)
        digests[path] = r["decisions_sha256"]
        if r["scheduled_jobs"] <= 0:
            raise AssertionError(f"round[{path}]: nothing scheduled")
        emit(
            "round",
            path=path,
            jobs=size[0],
            nodes=size[1],
            compile_s=r["compile_s"],
            first_warm_cycle_s=r["first_warm_cycle_s"],
            warm_cycle_s=r["cycle_s_samples"],
            solve_s=r["solve_s"],
            scheduled_jobs=r["scheduled_jobs"],
            loops=r["loops"],
            peak_bytes_in_use=peak_bytes(device),
            device_kind=device.device_kind,
        )
    if digests["lax"] != digests["blocked"]:
        raise AssertionError(f"round: lax and blocked decisions differ {digests}")


# ---------------------------------------------------------------------------
# served
# ---------------------------------------------------------------------------

PLACEMENT_KEYS = (
    "assigned_node",
    "scheduled_mask",
    "preempted_mask",
    "scheduled_priority",
)


def _backlog_yaml(path: str, n_jobs: int, queue: str, gang: bool) -> None:
    """One queue's share of the backlog in the `examples/gang-job.yaml`
    format; the first queue also carries the 128-way gang."""
    import yaml

    jobs = []
    if gang:
        jobs.append(
            {
                "priorityClassName": "high",
                "count": GANG,
                "requests": {"cpu": "4", "memory": "8Gi"},
                "gang": {"id": "trainer-128", "cardinality": GANG},
            }
        )
        n_jobs -= GANG
    jobs += [
        {
            "priorityClassName": "high",
            "count": n_jobs // 5,
            "requests": {"cpu": "2", "memory": "8Gi"},
        },
        {
            "priorityClassName": "low",
            "count": n_jobs - n_jobs // 5,
            "requests": {"cpu": "1", "memory": "4Gi"},
        },
    ]
    with open(path, "w") as f:
        yaml.safe_dump({"queue": queue, "jobSetId": f"{queue}-set", "jobs": jobs}, f)


def phase_served(size, rehearse: bool) -> None:
    import numpy as np

    from armada_tpu.clients.cli import _jobs_from_yaml
    from armada_tpu.core.config import PriorityClass, SchedulingConfig
    from armada_tpu.services.grpc_api import connect
    from armada_tpu.services.server import ControlPlane
    from armada_tpu.solver.reference import ReferenceSolver
    from armada_tpu.trace import TraceRecorder, load_trace
    from armada_tpu.trace.replayer import compare_round, replay_trace

    n_jobs, n_exec = size
    n_queues = 10
    config = SchedulingConfig(
        priority_classes={
            "high": PriorityClass("high", 30000, preemptible=False),
            "low": PriorityClass("low", 1000, preemptible=True),
        },
        default_priority_class="low",
    )
    fakes = [
        {
            "name": f"executor-{i:02d}",
            "nodes": SERVED_NODES_PER_EXECUTOR[rehearse],
            "cpu": "8",
            "memory": "128Gi",
        }
        for i in range(n_exec)
    ]
    with tempfile.TemporaryDirectory() as tmp:
        plane = ControlPlane(
            config, backend="kernel", cycle_period=0.2, fake_executors=fakes
        )
        sched = plane.scheduler
        trace_path = os.path.join(tmp, "served.atrace")
        sched.attach_trace_recorder(
            TraceRecorder(trace_path, source="chip_smoke", config=config,
                          max_rounds=1)
        )
        # The oracle solves the first round's own snapshot on the round
        # thread, before any later cycle can move it.
        first = {}
        solve = sched._solve

        def solve_and_keep_first(snap, *args, **kwargs):
            out = solve(snap, *args, **kwargs)
            if not first and out is not None and snap.num_jobs:
                first["oracle"] = ReferenceSolver(snap).solve()
                first["num_jobs"] = snap.num_jobs
            return out

        sched._solve = solve_and_keep_first
        try:
            client = connect(plane.address)
            t0 = time.monotonic()
            for q in range(n_queues):
                name = f"queue-{q:02d}"
                client.create_queue(name, priority_factor=1.0 + q % 2)
                path = os.path.join(tmp, f"{name}.yaml")
                _backlog_yaml(path, n_jobs // n_queues, name, gang=q == 0)
                queue, jobset, jobs = _jobs_from_yaml(path)
                for i in range(0, len(jobs), 2000):
                    client.submit_jobs(queue, jobset, jobs[i:i + 2000])
            submit_s = time.monotonic() - t0
            plane.start()
            t0 = time.monotonic()
            while time.monotonic() - t0 < 900:
                time.sleep(0.5)
                if first and _leases(plane) > 0:
                    break
            leases = _leases(plane)
            cycles = sched.cycle_count
        finally:
            plane.stop()
        if not first:
            raise AssertionError("served: no round was solved")
        if leases <= 0:
            raise AssertionError("served: no leases flowed")
        if sched.recent_failovers or sched.recent_rejections:
            raise AssertionError(
                f"served: failovers {list(sched.recent_failovers)} "
                f"rejections {list(sched.recent_rejections)}"
            )
        failovers = sum(
            s.value
            for fam in plane.metrics.solver_failover.collect()
            for s in fam.samples
            if s.name.endswith("_total")
        )
        if failovers:
            raise AssertionError(f"served: {failovers} solver failovers")
        trace = load_trace(trace_path)
        rec = trace.rounds[0]
        rung = rec.raw.get("solver", {}).get("rung")
        if rung != sched._rungs[0].label:
            raise AssertionError(f"served: round solved on rung {rung!r}")
        res = first["oracle"]
        oracle = {k: np.asarray(getattr(res, k)) for k in PLACEMENT_KEYS}
        divergences = compare_round(rec, oracle, compare_loops=False)
        if divergences:
            raise AssertionError(f"served: kernel != oracle: {divergences[:3]}")
        report = replay_trace(trace, solvers=("LOCAL",))
        if not report["ok"]:
            raise AssertionError(f"served: replay diverged: {report['divergences']}")
        decisions = rec.decisions()
        emit(
            "served",
            jobs=n_jobs,
            nodes=n_exec * SERVED_NODES_PER_EXECUTOR[rehearse],
            submit_s=round(submit_s, 3),
            cycles=cycles,
            leases=leases,
            first_round_jobs=first["num_jobs"],
            first_round_scheduled=int(
                np.asarray(decisions["scheduled_mask"])[: rec.num_jobs].sum()
            ),
            first_round_solve_s=rec.raw.get("solve_s"),
            rung=rung,
            failovers=int(failovers),
            oracle_match=True,
            replay_ok=True,
        )


def _leases(plane) -> int:
    """Jobs whose lease reached an executor (pending or later)."""
    from armada_tpu.jobdb.jobdb import JobState

    flowed = (JobState.PENDING, JobState.RUNNING, JobState.SUCCEEDED)
    txn = plane.scheduler.jobdb.read_txn()
    return sum(1 for j in txn.all_jobs() if j.state in flowed)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def phase_kernels(size, rehearse: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bench
    from armada_tpu.ops import pallas_kernels as pk
    from armada_tpu.ops.select import lex_argmin
    from armada_tpu.snapshot.incremental import IncrementalRound
    from armada_tpu.solver.dist import LocalDist
    from armada_tpu.solver.kernel_prep import pad_device_round

    if not rehearse and not pk.native_available():
        raise AssertionError("kernels: pallas would run in interpret mode")
    dev = pad_device_round(
        IncrementalRound(*bench.build_inputs(*size)).device_round()
    )
    dev = jax.tree_util.tree_map(
        lambda x: jnp.asarray(x) if isinstance(x, np.ndarray) else x, dev
    )
    bits = pk.pack_plan(dev, 1)
    dist = LocalDist()
    jobs =np.linspace(0, int(dev.job_req_fit.shape[0]) - 1, 8).astype(int)

    def score(path, dev, j):
        return pk.fill_score(dev, dist, dev.alloc0[0], j, path, bits)

    fn = {
        path: jax.jit(score, static_argnums=0)
        .lower(path, dev, jnp.int32(0))
        .compile()
        for path in ("pallas", "blocked")
    }
    native = "tpu_custom_call" in fn["pallas"].as_text()
    if not rehearse and not native:
        raise AssertionError("kernels: scoring kernel is not a Mosaic call")
    t0 = time.monotonic()
    for j in jobs:
        a = fn["pallas"](dev, jnp.int32(j))
        b = fn["blocked"](dev, jnp.int32(j))
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
            if not np.array_equal(np.asarray(x), np.asarray(y)):
                raise AssertionError(f"kernels: pallas != blocked at job {j}")
    score_s = time.monotonic() - t0

    rng = np.random.default_rng(0)
    winner = jax.jit(pk.winner_reduce).lower(
        [jnp.zeros(8, jnp.int32)] * 3, jnp.zeros(8, bool), jnp.zeros(8, jnp.int32)
    ).compile()
    if not rehearse and "tpu_custom_call" not in winner.as_text():
        raise AssertionError("kernels: winner kernel is not a Mosaic call")
    for _ in range(16):
        keys = [jnp.asarray(rng.integers(0, 4, 8), jnp.int32) for _ in range(2)]
        keys.append(jnp.asarray(rng.permutation(8), jnp.int32))
        found = jnp.asarray(rng.random(8) < 0.6)
        gids = jnp.asarray(rng.permutation(1000)[:8], jnp.int32)
        wgid, wfound = winner(keys, found, gids)
        idx, f = lex_argmin(keys, found)
        if bool(wfound) != bool(f) or (bool(f) and int(wgid) != int(gids[idx])):
            raise AssertionError("kernels: winner_reduce != lex_argmin")
    emit(
        "kernels",
        nodes=int(dev.node_gid.shape[0]),
        block_nodes=pk.BLOCK_NODES,
        jobs_checked=len(jobs),
        score_s=round(score_s, 4),
        native=native,
        interpret=not pk.native_available(),
    )


# ---------------------------------------------------------------------------
# mesh (--chips 4)
# ---------------------------------------------------------------------------


def phase_mesh(size, native: bool) -> None:
    import dataclasses
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import numpy as np

    import bench
    from armada_tpu.parallel.mesh import pad_nodes
    from armada_tpu.parallel.multihost import resolve_solver
    from armada_tpu.snapshot.incremental import IncrementalRound
    from armada_tpu.solver.kernel import solve_round
    from armada_tpu.solver.kernel_prep import pad_device_round

    inc = IncrementalRound(*bench.build_inputs(*size, fill_window=512))
    dev = pad_device_round(inc.device_round())
    # The ring (native) runs the pallas kernels too, so it replaces the
    # plain pallas run where the chip allows it.
    runs = [("4", "lax"), ("2x2", "native" if native else "pallas")]
    solvers = []
    for spec, path in runs:
        run = resolve_solver(spec, kernel_path=path)
        d = dataclasses.replace(pad_nodes(dev, run.n_shards), kernel_path=path)
        solvers.append((spec, path, run, d))
    # Compile every program at once (XLA compiles off the GIL); the
    # one-chip reference solve runs meanwhile.
    t0 = time.monotonic()
    with ThreadPoolExecutor(len(solvers) + 1) as pool:
        single_f = pool.submit(solve_round, dev)
        compiles = [pool.submit(run.prepare, d) for _, _, run, d in solvers]
        single = single_f.result()
        for f in compiles:
            f.result()
    compile_s = time.monotonic() - t0
    for spec, path, run, d in solvers:
        t0 = time.monotonic()
        out = jax.block_until_ready(run(d))
        first_s = time.monotonic() - t0
        t0 = time.monotonic()
        out = jax.block_until_ready(run(d))
        warm_s = time.monotonic() - t0
        for key, val in single.items():
            if not np.array_equal(np.asarray(out[key]), np.asarray(val), equal_nan=True):
                raise AssertionError(f"mesh {spec}/{path}: {key} differs from one chip")
        stats = (run.last_stats or run.stats).as_dict() if run.stats else {}
        emit(
            "mesh",
            mesh=spec,
            path=path,
            jobs=size[0],
            nodes=size[1],
            devices=int(np.prod(run.mesh_shape)),
            compile_and_single_solve_s=round(compile_s, 3),
            first_s=round(first_s, 3),
            warm_s=round(warm_s, 3),
            scheduled=int(np.asarray(single["scheduled_mask"]).sum()),
            ring_steps=stats.get("ring_steps"),
            bit_exact=True,
        )


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU (interpret-mode kernels)")
    args = ap.parse_args(argv)
    device = None
    try:
        if args.rehearse:
            os.environ["JAX_PLATFORMS"] = "cpu"
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4"
            )
        sys.path.insert(0, HERE)
        from armada_tpu.core.resources import ensure_native
        from armada_tpu.ops.pallas_kernels import PATH_ENV
        from armada_tpu.utils.platform import setup_jax

        ensure_native()
        setup_jax(require=None if args.rehearse else "tpu")
        import jax

        devices = jax.devices()
        if len(devices) < args.chips:
            raise RuntimeError(f"need {args.chips} devices, have {len(devices)}")
        device = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        }
        sizes = TINY if args.rehearse else FULL
        if args.chips == 4:
            from armada_tpu.ops.pallas_kernels import native_available

            phase_mesh(sizes["mesh"], native=native_available())
        else:
            phase_round(sizes["round"], devices[0])
            os.environ.pop(PATH_ENV, None)
            phase_served(sizes["served"], args.rehearse)
            phase_kernels(sizes["round"], args.rehearse)
        from armada_tpu.observe.xla import TELEMETRY
        from armada_tpu.utils.platform import compile_cache_dir

        emit("compile", cache_dir=compile_cache_dir(), **TELEMETRY.snapshot())
        ok = True
    except Exception as e:  # noqa: BLE001 - the last line IS the report
        traceback.print_exc()
        ok, error = False, f"{e.__class__.__name__}: {e}"
    if ok:
        print(json.dumps({"ok": True, "device": device}), flush=True)
        return 0
    print(json.dumps({"ok": False, "error": error, "device": device}), flush=True)
    return 1


if __name__ == "__main__":
    # Non-daemon threads a phase left behind must not hold the exit.
    code = main()
    sys.stdout.flush()
    os._exit(code)
