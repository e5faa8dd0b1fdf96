"""Tiny cells of the benchmark for CPU tests: `write_tiny_root` writes a
root that holds a BENCHMARK.json, the configuration and traffic files of
two tiny cells cut down from the real ones, and copies of the
benchmark's runners, readers and limits, where the harness looks for
them in a checkout."""

import json
import os
import shutil
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _load(rel):
    with open(os.path.join(REPO, rel)) as f:
        return json.load(f)


def tiny_configs():
    """The two tiny deployments and the tiny served mix."""
    flag = _load("benchmark/configs/flagship-1m-50k.json")
    flag["name"] = "tiny-flagship"
    flag["nodes"]["count"] = 64
    flag["backlog"]["count"] = 3000
    flag["running"]["count"] = 128
    flag["rate_limits"].update(global_burst=200, queue_burst=100)
    fleet = _load("benchmark/configs/fleet-10x500.json")
    fleet["name"] = "tiny-fleet"
    fleet["executors"].update(count=2, nodes=20, cpu="128", memory="2048Gi")
    fleet["backlog"]["count"] = 1500
    fleet["backlog"]["gang"]["cardinality"] = 16
    fleet["cycle_period_s"] = 0.3
    served = _load("benchmark/traffic/served.json")
    served.update(warmup_s=2.0, drain_s=20.0, trace_seconds=1.0, compare_rounds=2)
    served["arrivals"]["rate_per_s"] = 20.0
    return {"tiny-flagship": flag, "tiny-fleet": fleet, "tiny-served": served}


def write_tiny_root(root):
    """A root with cells `tiny.steady` and `tiny.served`."""
    bdir = os.path.join(root, "benchmark")
    for sub in ("runners", "metrics"):
        shutil.copytree(os.path.join(REPO, "benchmark", sub), os.path.join(bdir, sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "benchmark", "limits.json"), bdir)
    os.makedirs(os.path.join(bdir, "configs"), exist_ok=True)
    os.makedirs(os.path.join(bdir, "traffic"), exist_ok=True)
    tiny = tiny_configs()
    files = {
        "benchmark/configs/tiny-flagship.json": tiny["tiny-flagship"],
        "benchmark/configs/tiny-fleet.json": tiny["tiny-fleet"],
        "benchmark/traffic/tiny-served.json": tiny["tiny-served"],
        "benchmark/traffic/steady.json": _load("benchmark/traffic/steady.json"),
    }
    for rel, doc in files.items():
        with open(os.path.join(root, rel), "w") as f:
            json.dump(doc, f)
    bench = _load("BENCHMARK.json")
    bench["configs"] = [
        {"name": "tiny-flagship", "source": "test", "reduced": [], "why": "test",
         "file": "benchmark/configs/tiny-flagship.json"},
        {"name": "tiny-fleet", "source": "test", "reduced": [], "why": "test",
         "file": "benchmark/configs/tiny-fleet.json"},
    ]
    bench["workloads"] = [
        {"name": "tiny.steady", "config": "tiny-flagship", "traffic": "steady",
         "chips": 1, "why": "test"},
        {"name": "tiny.served", "config": "tiny-fleet", "traffic": "tiny-served",
         "chips": 1, "why": "test"},
    ]
    # Cells of the real benchmark other than these two have no tiny
    # counterpart: they drop out of every metric's list, and a metric
    # left with no cell drops out of the tiny benchmark.
    rename = {"flagship.steady": "tiny.steady", "fleet.served": "tiny.served"}
    for group in ("end_to_end", "per_layer"):
        kept = []
        for m in bench[group]:
            if "workloads" in m:
                m["workloads"] = [rename[w] for w in m["workloads"] if w in rename]
                if not m["workloads"]:
                    continue
            kept.append(m)
        bench[group] = kept
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return str(root)


def add_cell(root, cell, config, mix, reports):
    """Add cell `cell` to a tiny root as a later change adds one, by files
    and entries alone: the configuration's file (named by its "name"),
    the mix's file where `mix` is a dict (else the name of a mix the root
    has), an entry for each, and the cell's name appended to the
    `workloads` of the end-to-end metrics it `reports`. Returns the mix's
    name."""
    bdir = os.path.join(root, "benchmark")
    name = config["name"]
    with open(os.path.join(bdir, "configs", f"{name}.json"), "w") as f:
        json.dump(config, f)
    if isinstance(mix, dict):
        mix_name = f"{cell}-mix"
        with open(os.path.join(bdir, "traffic", f"{mix_name}.json"), "w") as f:
            json.dump(mix, f)
    else:
        mix_name = mix
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": name, "source": "test", "reduced": [],
                             "why": "test", "file": f"benchmark/configs/{name}.json"})
    bench["workloads"].append({"name": cell, "config": name, "traffic": mix_name,
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in reports:
            m["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return mix_name


# Faults planted under the timed path: the solve's answer, broken where
# it is produced. A cell on one chip has no exchange between chips.

def _unchanged(out):
    """A step that returns its state unchanged: nothing placed."""
    out["scheduled_mask"] = np.zeros_like(out["scheduled_mask"])
    out["assigned_node"] = np.where(
        np.asarray(out["preempted_mask"]), -1, out["assigned_node"]
    )


def _half_batch(out):
    """Half of the batch left out: every other placement dropped."""
    sched = np.flatnonzero(out["scheduled_mask"])
    drop = sched[::2]
    out["scheduled_mask"] = out["scheduled_mask"].copy()
    out["scheduled_mask"][drop] = False
    out["assigned_node"] = out["assigned_node"].copy()
    out["assigned_node"][drop] = -1


def _altered(out):
    """An answer altered where it is produced: one job on another node."""
    j = int(np.flatnonzero(out["scheduled_mask"])[0])
    out["assigned_node"] = out["assigned_node"].copy()
    out["assigned_node"][j] = (out["assigned_node"][j] + 1) % 2


FAULTS = {
    "state_unchanged": _unchanged,
    "half_batch": _half_batch,
    "answer_altered": _altered,
}


def plant_fault(monkeypatch, name):
    """Break `solver/kernel.solve_round` for the rest of a test."""
    from armada_tpu.solver import kernel

    solve = kernel.solve_round
    fault = FAULTS[name]

    def broken(dev, **kwargs):
        out = dict(solve(dev, **kwargs))
        if np.asarray(out["scheduled_mask"]).any():
            fault(out)
        return out

    monkeypatch.setattr(kernel, "solve_round", broken)


def plant_snapshot_fault(monkeypatch):
    """Queue weights lost between the queue records and the program's
    round state (`IncrementalRound`): every queue weighs 1."""
    from armada_tpu.core.types import QueueSpec
    from armada_tpu.snapshot.incremental import IncrementalRound

    init = IncrementalRound.__init__

    def faulty(self, config, pool, nodes, queues, *args, **kwargs):
        queues = [QueueSpec(q.name, 1.0) for q in queues]
        init(self, config, pool, nodes, queues, *args, **kwargs)

    monkeypatch.setattr(IncrementalRound, "__init__", faulty)


def plant_window_compile(monkeypatch):
    """A program that compiles in every round: one new shape per solve."""
    import jax
    import jax.numpy as jnp

    from armada_tpu.solver import kernel

    solve = kernel.solve_round
    calls = [0]

    def compiling(dev, **kwargs):
        calls[0] += 1
        jax.block_until_ready(jax.jit(jnp.cumsum)(jnp.zeros(calls[0] + 7)))
        return solve(dev, **kwargs)

    monkeypatch.setattr(kernel, "solve_round", compiling)
