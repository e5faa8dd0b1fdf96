"""BENCHMARK.json against the benchmark's contract; every cell, mix,
configuration and reader found by name; a new one added by files alone;
the last-line contract of `benchmark/run.py`."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark_tiny import REPO, add_cell, write_tiny_root
from benchmark.harness import Cell, Run, result_line

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_benchmark_json_top_level():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= len(BENCH["paths"]) <= 16
    for path in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", path)
        assert os.path.isdir(os.path.join(REPO, path))
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024


def test_benchmark_json_full_check_fits_with_24_cells():
    runs = 2 + 14 * 24
    total = runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_benchmark_config_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert entry["file"].startswith("benchmark/")
    with open(os.path.join(REPO, entry["file"])) as f:
        doc = json.load(f)
    assert doc["name"] == entry["name"] and doc["source"] == entry["source"]
    assert doc["reduced"] == entry["reduced"] and len(entry["reduced"]) <= 16
    assert doc["chips"] in (1, 4) and "assumed" in doc and "guarantees" in doc
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])
    assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]


@pytest.mark.parametrize("cell", CELLS)
def test_benchmark_cell_reports_what_the_contract_asks(cell):
    w = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    c = Cell(REPO, cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    assert hasattr(c.runner(), "run")
    for m in c.per_layer:
        assert callable(c.reader(m["name"]).read)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_benchmark_metric_entry(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    for w in metric.get("workloads", []):
        assert w in CELLS
    if metric in BENCH["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer", "moves",
                               "workloads"}
        moved = next(m for m in BENCH["end_to_end"] if m["name"] == metric["moves"])
        for w in metric["workloads"]:
            assert w in moved.get("workloads", CELLS)
        assert os.path.exists(
            os.path.join(REPO, "benchmark", "metrics", metric["name"] + ".py")
        )


def test_benchmark_json_names_are_unique():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


# A throwaway cell of each runner: the deployment it copies, its mix,
# the end-to-end metrics it reports and a reader of its own.
THROWAWAY = {
    "round_loop": {
        "config": "tiny-flagship", "e2e": ["cycle_s"], "moves": "cycle_s",
        "mix": {"runner": "round_loop", "warmup_rounds": 1, "trace_rounds": 2},
        "reader": "def read(run):\n    return len(run.spans.get('round', [])) or None\n",
    },
    "served": {
        "config": "tiny-fleet", "e2e": ["lease_p50_s", "lease_p95_s"],
        "moves": "lease_p95_s", "mix": "tiny-served",
        "reader": ("def read(run):\n"
                   "    return run.counters.get('scheduler_cycle_seconds_count') or None\n"),
    },
}


@pytest.mark.parametrize("runner", sorted(THROWAWAY))
def test_benchmark_adds_a_cell_by_files_alone(tmp_path, monkeypatch, runner):
    """A throwaway configuration, mix, cell and per-layer metric: new
    files and new entries, and no file of the benchmark edited. The
    real BENCHMARK.json already holds a cell that has no tiny
    counterpart, as it does once a later change adds one."""
    import benchmark_tiny
    from armada_tpu.core import resources

    monkeypatch.setattr(resources, "ensure_native", lambda *a, **k: False)
    spec = THROWAWAY[runner]
    real = json.loads(json.dumps(BENCH))
    real["workloads"].append({"name": "later.cell", "config": real["configs"][0]["name"],
                              "traffic": "later-mix", "chips": 1, "why": "test"})
    for m in real["end_to_end"]:
        if m["name"] in spec["e2e"]:
            m["workloads"].append("later.cell")
    real["per_layer"].append({"name": "later_ms.later", "unit": "ms", "better": "lower",
                              "source": "host_clock", "layer": "harness",
                              "moves": spec["moves"], "workloads": ["later.cell"]})
    load = benchmark_tiny._load
    monkeypatch.setattr(benchmark_tiny, "_load",
                        lambda rel: real if rel == "BENCHMARK.json" else load(rel))
    root = write_tiny_root(tmp_path)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [w["name"] for w in bench["workloads"]] == ["tiny.steady", "tiny.served"]
    assert "later_ms.later" not in {m["name"] for m in bench["per_layer"]}

    bdir = os.path.join(root, "benchmark")
    with open(os.path.join(bdir, "configs", spec["config"] + ".json")) as f:
        cfg = json.load(f)
    cfg.update(name="throwaway")
    add_cell(root, "throwaway.cell", cfg, spec["mix"], spec["e2e"])
    with open(os.path.join(bdir, "metrics", "throwaway_seen.cell.py"), "w") as f:
        f.write(spec["reader"])
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["per_layer"].append({"name": "throwaway_seen.cell", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "harness", "moves": spec["moves"],
                               "workloads": ["throwaway.cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    from benchmark.harness import run_cell

    out = run_cell(root, "throwaway.cell", 9, 2.0 if runner == "served" else 0.5, True,
                   require_accelerator=False, out_dir=str(tmp_path / "out"),
                   log=lambda m: None)
    assert out["correct"], out["check"]
    assert out["metrics"]["throwaway_seen.cell"]["value"] >= 1
    assert out["metrics"]["throwaway_seen.cell"]["unit"] == "count"


class _Dev:
    platform, device_kind = "tpu", "TPU v5 lite"


def _fake_run(tmp_path, trace):
    cell = Cell(REPO, "flagship.steady")
    run = Run(cell, 1, 1.0, trace, str(tmp_path))
    run.devices = [_Dev()]
    run.memory_peak_bytes = 123
    run.e2e.update(setup_s=50.0, cycle_s=2.0, lease_p50_s=9.0)
    run.spans.update(deltas=[0.01, 0.03], sync=[0.2], solve=[1.0, 3.0])
    run.trace_summary = {"busy_s": 1.0, "window_s": 4.0, "idle_share": 0.75,
                         "device_ops": [["a", 1.0]], "idle_gaps": [["b", 3.0]]}
    run.check.update(placement_divergences=(0, 0), fair_share_gap=(1e-16, 1e-9))
    run.attempted = 5
    return run


def test_benchmark_last_line_untraced(tmp_path):
    lines = []
    out = result_line(_fake_run(tmp_path, False), lines.append)
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device", "check"]
    assert out["correct"] is True
    assert set(out["metrics"]) == {"setup_s", "cycle_s"}
    assert out["metrics"]["cycle_s"] == {"value": 2.0, "unit": "s"}
    assert out["device"] == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                             "memory_peak_bytes": 123}
    assert lines == ["check placement_divergences = 0.0 limit 0.0",
                     "check fair_share_gap = 1e-16 limit 1e-09"]
    json.dumps(out)


def test_benchmark_last_line_traced(tmp_path):
    out = result_line(_fake_run(tmp_path, True), lambda m: None)
    assert list(out)[-2:] == ["breakdown", "check"]
    assert out["device"]["busy_s"] == 1.0 and out["device"]["window_s"] == 4.0
    assert out["metrics"]["delta_ms.round"]["value"] == pytest.approx(20.0)
    assert out["metrics"]["solve_ms.round"]["value"] == pytest.approx(2000.0)
    assert out["metrics"]["device_idle_pct.round"]["value"] == pytest.approx(75.0)
    assert "cycle_s" not in out["metrics"]


def test_benchmark_incorrect_when_a_number_passes_its_limit(tmp_path):
    run = _fake_run(tmp_path, False)
    run.check["placement_divergences"] = (3, 0)
    assert result_line(run, lambda m: None)["correct"] is False


def _run_py(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "flagship.steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_benchmark_run_refuses_without_an_accelerator():
    proc = _run_py(REPO, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_benchmark_run_needs_the_program(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's
    paths has no system to measure: no result."""
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(REPO, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    proc = _run_py(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_benchmark_tiny_root_is_the_same_with_a_cell_it_does_not_know(tmp_path, monkeypatch):
    """The tiny root's BENCHMARK.json comes out the same whether or not
    the real one holds a cell the tiny root has no copy of."""
    import benchmark_tiny

    with open(os.path.join(write_tiny_root(tmp_path / "a"), "BENCHMARK.json")) as f:
        plain = json.load(f)
    real = json.loads(json.dumps(BENCH))
    real["workloads"].append({"name": "later.cell", "config": real["configs"][0]["name"],
                              "traffic": "later-mix", "chips": 1, "why": "test"})
    for m in real["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("later.cell")
    real["end_to_end"].append({"name": "later_s", "unit": "s", "better": "lower",
                               "bound": 0.1, "source": "host_clock",
                               "workloads": ["later.cell"]})
    real["per_layer"].append({"name": "later_ms.later", "unit": "ms", "better": "lower",
                              "source": "host_clock", "layer": "harness",
                              "moves": "later_s", "workloads": ["later.cell"]})
    load = benchmark_tiny._load
    monkeypatch.setattr(benchmark_tiny, "_load",
                        lambda rel: real if rel == "BENCHMARK.json" else load(rel))
    with open(os.path.join(write_tiny_root(tmp_path / "b"), "BENCHMARK.json")) as f:
        assert json.load(f) == plain
