"""The round-loop runner on a tiny cell, on the CPU: a sound run is
correct, and each fault the cell can have, planted under the timed path,
makes it not correct; so does the float32 control."""

import json
import os
import subprocess
import sys

import pytest

from benchmark_tiny import (
    FAULTS, REPO, plant_fault, plant_window_compile, write_tiny_root,
)

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


def test_benchmark_round_loop_is_correct(tiny_run):
    out, lines = tiny_run("tiny.steady", seed=2**33 + 5)
    assert out["correct"], out["check"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "cycle_s"}
    assert out["metrics"]["cycle_s"]["value"] > 0
    assert list(out)[-1] == "check"
    assert out["check"]["placement_divergences"]["limit"] == 0
    assert lines and all(line.startswith("check ") for line in lines)


def test_benchmark_round_loop_traced_reads_its_layers(tiny_run):
    out, _ = tiny_run("tiny.steady", trace=True)
    assert out["correct"]
    assert {"delta_ms.round", "sync_ms.round", "solve_ms.round",
            "solve_syncs.round"} <= set(out["metrics"])
    units = {m["name"]: m["unit"] for m in BENCH["per_layer"]}
    for name, m in out["metrics"].items():
        assert m["unit"] == units[name], name
    assert out["metrics"]["solve_syncs.round"]["value"] >= 1


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_benchmark_round_loop_fault_is_not_correct(tiny_run, monkeypatch, fault):
    plant_fault(monkeypatch, fault)
    out, _ = tiny_run("tiny.steady")
    assert not out["correct"]
    assert out["check"]["placement_divergences"]["value"] > 0


def test_benchmark_round_loop_window_compile_is_not_correct(tiny_run, monkeypatch):
    plant_window_compile(monkeypatch)
    out, _ = tiny_run("tiny.steady")
    assert not out["correct"]
    assert out["check"]["window_compiles"]["value"] >= 1
    assert out["check"]["placement_divergences"]["value"] == 0


def test_benchmark_warm_resident_covers_every_bucket():
    """After `warm_resident`, a delta sync of any batch size compiles
    nothing."""
    import jax

    from armada_tpu.observe.xla import TELEMETRY
    from armada_tpu.snapshot import residency
    from armada_tpu.snapshot.incremental import IncrementalRound
    from armada_tpu.snapshot.residency import ResidentRound
    from benchmark import generate
    from benchmark.harness import warm_resident
    from benchmark_tiny import tiny_configs

    config = tiny_configs()["tiny-flagship"]
    cfg = generate.scheduling_config(config)
    nodes = generate.nodes(config)
    running = generate.running_jobs(config, nodes, 5)
    stream = generate.JobStream(config["backlog"], config, 5, 1, "job-")
    queued = stream.take(4000)
    inc = IncrementalRound(cfg, config["pool"], nodes, generate.queues(config),
                           running, queued)
    resident = ResidentRound()
    resident.device_round(inc)
    assert warm_resident(resident) > 0
    TELEMETRY.install()
    before = TELEMETRY.snapshot()["compiles"]
    k = 0
    for n in (1, 70, 300, 1100):  # one batch of leases in each bucket
        inc.bind([(j.id, nodes[(k + i) % len(nodes)].id, 1000, 0.0)
                  for i, j in enumerate(queued[k:k + n])])
        k += n
        resident.device_round(inc)
        assert resident.last_sync["mode"] == "delta"
    jax.block_until_ready(resident._dev.job_node)
    assert TELEMETRY.snapshot()["compiles"] == before
    assert residency._jit_for("s0")._cache_size() > 0


def test_benchmark_round_loop_float32_control_is_not_correct(tmp_path):
    """The control: the program's own float32 cost path
    (ARMADA_TPU_X64=0), in a process of its own."""
    root = write_tiny_root(tmp_path / "root")
    code = (
        "import json, sys; sys.path.insert(0, %r)\n"
        "from armada_tpu.core import resources\n"
        "resources.ensure_native = lambda *a, **k: False\n"
        "from benchmark.harness import run_cell\n"
        "out = run_cell(%r, 'tiny.steady', 77, 1.0, False,"
        " require_accelerator=False, out_dir=%r, log=lambda m: None)\n"
        "print(json.dumps(out))\n"
    ) % (REPO, root, str(tmp_path / "out"))
    env = dict(os.environ, ARMADA_TPU_X64="0", JAX_PLATFORMS="cpu",
               JAX_ENABLE_X64="0")
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    gap = out["check"]["fair_share_gap"]
    assert gap["value"] > gap["limit"]
    assert not out["correct"]


def test_benchmark_round_loop_follows_preemption(tiny_root, tiny_run, capsys):
    """A full fleet of small preemptible jobs and a backlog with large
    urgent ones: rounds preempt, the runner ends the preempted jobs as
    the scheduler service does, and the reference, rebuilt from the
    benchmark's own record, agrees with the last round."""
    from benchmark_tiny import add_cell, tiny_configs

    config = tiny_configs()["tiny-flagship"]
    config["name"] = "tiny-preempt"
    config["running"].update(count=2048, priority_class="low", cpu="1", memory="2Gi")
    config["backlog"]["sizes"] = [
        {"share": 1, "priority_class": "high", "cpu": "4", "memory": "8Gi"},
        {"share": 1, "cpu": "1", "memory": "2Gi"},
    ]
    mix = {"runner": "round_loop", "warmup_rounds": 4, "trace_rounds": 2}
    add_cell(tiny_root, "tiny.preempt", config, mix, ["cycle_s"])
    out, _ = tiny_run("tiny.preempt", seed=2**32 + 11, seconds=2.0)
    window = next(json.loads(line[len("window "):])
                  for line in capsys.readouterr().out.splitlines()
                  if line.startswith("window "))
    assert window["preempted"] > 0
    assert out["correct"], out["check"]
