"""Multi-process mesh coordinator: the DCN dryrun harness.

Boots N real OS processes — one per mesh HOST row — joined through
jax.distributed with gloo CPU collectives, so the host axis of the
(hosts, chips) mesh crosses actual process boundaries: every
cross-host collective the HierarchicalDist seam issues is genuine
inter-process traffic, not a virtual-device shuffle. Each process runs
the SAME `solve_round` body over its mesh row via
multihost.hierarchical_sharded_solve, computes its own single-device
reference locally, and asserts **bit-exact parity** between the two on
the mixed-fleet scenario set (away pools, a market pool, mixed gangs —
parallel/scenarios.py).

This is the CPU stand-in for a v5e pod: process = host, local virtual
devices = chips on its slice, gloo = DCN. The compiled program and the
collective schedule are identical to what the same mesh shape runs on
real hardware; only the fabric underneath differs.

Entry points:
  - `launch(...)` (coordinator): spawns workers with a hard timeout,
    collects one JSON report per worker, merges them. Used by
    tools/dcn_dryrun.py and the slow-marked test.
  - `python -m armada_tpu.parallel.launcher --process-id I ...`
    (worker): joins the mesh and prints `DCN_WORKER {json}`.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import time
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[2]
_MARK = "DCN_WORKER "


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# Worker side
# ---------------------------------------------------------------------------


def _rendezvous_collectives(mesh):
    """Force every gloo clique the solve will use to connect NOW.

    XLA's gloo contexts initialize lazily at the first collective
    EXECUTION, with a hard ~30s rendezvous timeout on the distributed KV
    store. Each worker spends minutes in per-process compiles before its
    first collective, and on a small shared box the workers' compile
    wall clocks can skew past that window — one side publishes its pair
    address and times out connecting while the other is still compiling.
    jax.distributed.initialize IS a synchronization point (the
    coordinator waits for every process), so running one tiny program
    with the solve's collective patterns (all_gather + psum over both
    axes) right after init rendezvouses all cliques while skew is
    seconds; contexts are cached per clique, so the big programs never
    pay the 30s window again."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from .multihost import CHIP_AXIS, HOST_AXIS

    def body(x):
        g = jax.lax.all_gather(x, CHIP_AXIS)
        g = jax.lax.all_gather(g, HOST_AXIS)
        s = jax.lax.psum(jax.lax.psum(x, CHIP_AXIS), HOST_AXIS)
        return g.sum() + s

    f = jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
    )
    out = jax.block_until_ready(f(jnp.float32(1.0)))
    world = mesh.devices.size
    assert float(out) == 2.0 * world, f"collective warm-up: {out}"


def _sync(name: str, timeout_s: float = 1800.0) -> None:
    """Cross-process barrier on the jax.distributed coordination service
    (KV store, no gloo). Every EXECUTABLE gets its own gloo communicator
    whose first execution opens the ~30s rendezvous window, so the
    harness compiles each round's program AOT (runner.prepare), syncs
    here with a timeout sized for multi-minute compile skew, then
    executes — all processes enter the rendezvous together."""
    from jax._src import distributed

    distributed.global_state.client.wait_at_barrier(
        name, int(timeout_s * 1000)
    )


def run_worker(
    process_id: int,
    num_processes: int,
    coordinator: str,
    chips: int,
    n_nodes: int,
    n_jobs: int,
) -> dict:
    """Join the distributed mesh, run the mixed-fleet rounds, return the
    parity/timing report (also printed as a DCN_WORKER line by main)."""
    # Order matters: distributed.initialize must precede the first jax
    # computation (setup_jax asks for the backend), and the gloo
    # collectives config must precede backend creation. The coordinator
    # pinned JAX_PLATFORMS=cpu in our env.
    import jax

    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
        cluster_detection_method="deactivate",
    )

    from armada_tpu.utils.platform import setup_jax

    setup_jax()

    import numpy as np

    from ..solver.kernel import solve_round
    from ..solver.kernel_prep import pad_device_round, prep_device_round
    from .mesh import pad_nodes
    from .multihost import hierarchical_sharded_solve, make_host_mesh
    from .scenarios import mixed_fleet_rounds

    assert jax.local_device_count() == chips, (
        f"worker {process_id}: {jax.local_device_count()} local devices, "
        f"expected {chips}"
    )
    mesh = make_host_mesh(num_processes, chips)
    _rendezvous_collectives(mesh)
    runner = hierarchical_sharded_solve(mesh)

    rounds = []
    ok = True
    for label, snap in mixed_fleet_rounds(n_nodes, n_jobs):
        dev = pad_nodes(
            pad_device_round(prep_device_round(snap)), runner.n_shards
        )
        t0 = time.monotonic()
        single = solve_round(dev)
        t1 = time.monotonic()
        runner.prepare(dev)
        _sync(f"exec-{label}")
        t1x = time.monotonic()
        multi = runner(dev)
        jax.block_until_ready(multi)
        t2 = time.monotonic()
        multi = {k: np.asarray(v) for k, v in multi.items()}
        mismatch = [
            k
            for k, v in single.items()
            if not np.array_equal(multi[k], np.asarray(v), equal_nan=True)
        ]
        ok = ok and not mismatch
        rounds.append(
            {
                "round": label,
                "mismatch": mismatch,
                "scheduled": int(np.asarray(single["scheduled_mask"]).sum()),
                "loops": int(single["num_loops"]),
                "single_solve_s": round(t1 - t0, 3),
                # Per-shard (this host's) wall clock: compile (AOT,
                # before the exec barrier) and execution separately.
                "shard_compile_s": round(t1x - t1, 3),
                "shard_solve_s": round(t2 - t1x, 3),
                # The program THIS round executed (per-cache-key
                # snapshot, not the most recently traced one).
                "collectives": (runner.last_stats or runner.stats).as_dict(),
            }
        )
    return {
        "process_id": process_id,
        "hosts": num_processes,
        "chips": chips,
        "n_nodes": n_nodes,
        "n_jobs": n_jobs,
        "ok": ok,
        "rounds": rounds,
        "collectives": (runner.last_stats or runner.stats).as_dict(),
    }


# ---------------------------------------------------------------------------
# Coordinator side
# ---------------------------------------------------------------------------


def launch(
    n_hosts: int = 2,
    n_chips: int = 4,
    n_nodes: int = 512,
    n_jobs: int = 2048,
    timeout_s: float = 900.0,
) -> dict:
    """Spawn n_hosts worker processes, hard-kill past timeout_s, merge
    their reports. Returns a dict with "ok" true only when every worker
    exited 0 AND reported bit-exact parity on every round."""
    coordinator = f"127.0.0.1:{_free_port()}"
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        JAX_ENABLE_X64="1",
        XLA_FLAGS=f"--xla_force_host_platform_device_count={n_chips}",
        JAX_NUM_CPU_DEVICES=str(n_chips),
    )
    procs = []
    logs = []
    for i in range(n_hosts):
        # Each worker streams to its own temp file, never a PIPE: the
        # workers advance in lockstep through collectives, so one worker
        # blocked on a full 64K pipe buffer (XLA/gloo log noise) while
        # the coordinator drains a DIFFERENT worker's pipe would wedge
        # the whole fleet until the hard timeout.
        logs.append(
            tempfile.TemporaryFile(
                mode="w+", prefix=f"dcn-worker-{i}-", encoding="utf-8"
            )
        )
        procs.append(
            subprocess.Popen(
                [
                    sys.executable,
                    # Unbuffered: a fatal C++ abort (coordination-service
                    # error poll) must not swallow the Python traceback
                    # that caused it.
                    "-u",
                    "-m",
                    "armada_tpu.parallel.launcher",
                    "--process-id",
                    str(i),
                    "--num-processes",
                    str(n_hosts),
                    "--coordinator",
                    coordinator,
                    "--chips",
                    str(n_chips),
                    "--nodes",
                    str(n_nodes),
                    "--jobs",
                    str(n_jobs),
                ],
                cwd=REPO_ROOT,
                env=env,
                stdout=logs[i],
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    deadline = time.monotonic() + timeout_s
    timed_out = False
    for p in procs:
        try:
            p.wait(timeout=max(0.1, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            timed_out = True
    if timed_out:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    outputs: list[str] = []
    for f in logs:
        f.seek(0)
        outputs.append(f.read())
        f.close()
    reports = []
    for out in outputs:
        report = None
        for line in out.splitlines():
            if line.startswith(_MARK):
                report = json.loads(line[len(_MARK):])
        reports.append(report)
    ok = (
        not timed_out
        and all(p.returncode == 0 for p in procs)
        and all(r is not None and r["ok"] for r in reports)
    )
    result = {
        "ok": ok,
        "timed_out": timed_out,
        "hosts": n_hosts,
        "chips": n_chips,
        "n_nodes": n_nodes,
        "n_jobs": n_jobs,
        "returncodes": [p.returncode for p in procs],
        "workers": reports,
    }
    if reports and reports[0] is not None:
        result["collectives"] = reports[0]["collectives"]
        result["rounds"] = reports[0]["rounds"]
    if not ok:
        # Last 8k chars of each worker's output — enough to keep the
        # Python traceback that preceded the coordination-service abort
        # noise without flooding the report.
        result["tails"] = [out[-8000:] for out in outputs]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--process-id", type=int, required=True)
    ap.add_argument("--num-processes", type=int, required=True)
    ap.add_argument("--coordinator", required=True)
    ap.add_argument("--chips", type=int, required=True)
    ap.add_argument("--nodes", type=int, default=512)
    ap.add_argument("--jobs", type=int, default=2048)
    args = ap.parse_args(argv)
    report = run_worker(
        args.process_id,
        args.num_processes,
        args.coordinator,
        args.chips,
        args.nodes,
        args.jobs,
    )
    print(_MARK + json.dumps(report), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
