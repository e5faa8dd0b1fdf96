"""The readers of the program's own spans and GC counter
(benchmark/program_spans.py): a traced run of each tiny cell on the CPU
gives every one a finite value, the inside measurement of the residency
sync fits inside the outside one, and a program without the span API or
the counter gives none of them (as the benchmark runs over an older
program)."""

import json
import math
import os

import pytest

from benchmark_tiny import REPO
from benchmark.harness import Cell, Run, result_line

ROUND = ["sync_prep_ms.round", "sync_diff_ms.round", "pass1_ms.round",
         "gc_ms.round"]
SERVED = ["sync_ms.served", "solve_round_ms.served", "checks_ms.served",
          "snapshot_ms.served", "gc_pct.served"]


@pytest.mark.parametrize(
    "cell,names,seconds",
    [("tiny.steady", ROUND, 2.0), ("tiny.served", SERVED, 2.0)],
    ids=["round_loop", "served"],
)
def test_benchmark_traced_run_reads_program_spans(tiny_run, cell, names, seconds):
    out, _ = tiny_run(cell, seconds=seconds, trace=True)
    assert out["correct"], out["check"]
    metrics = out["metrics"]
    for name in names:
        value = metrics[name]["value"]
        assert math.isfinite(value) and value >= 0, name
    if cell == "tiny.steady":
        inside = metrics["sync_prep_ms.round"]["value"] + metrics["sync_diff_ms.round"]["value"]
        assert 0 < inside <= metrics["sync_ms.round"]["value"]
        assert metrics["pass1_ms.round"]["value"] <= metrics["solve_ms.round"]["value"]
    else:
        assert metrics["solve_round_ms.served"]["value"] > 0
        assert metrics["snapshot_ms.served"]["value"] > 0
        assert metrics["gc_pct.served"]["value"] < 100


class _Dev:
    platform, device_kind = "tpu", "TPU v5 lite"


class _OldTracer:
    """A tracer as the program had it before the read API."""

    finished: list = []


with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_benchmark_program_readers_silent_without_the_api(tmp_path, monkeypatch, cell):
    """Every reader of the program's spans and counters gives nothing
    where the program has neither."""
    from armada_tpu.utils import tracing

    monkeypatch.setattr(tracing, "TRACER", _OldTracer())
    monkeypatch.delattr(tracing, "GC_PAUSES")
    run = Run(Cell(REPO, cell), 1, 51.0, True, str(tmp_path))
    run.devices = [_Dev()]
    run.e2e.update({m["name"]: 1.0 for m in run.cell.end_to_end})
    run.attempted = 10
    run.check.update(placement_divergences=(0, 0))
    out = result_line(run, lambda m: None)
    program = {m["name"] for m in run.cell.per_layer
               if m["source"] in ("program_span", "program_counter")}
    assert not program & set(out["metrics"])
