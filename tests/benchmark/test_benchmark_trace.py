"""The trace reduction, on a small trace recorded on a TPU v5e chip
(benchmark/testdata/v5e_small.xplane.pb: three runs of one jitted
program inside `bench.window`, each in `bench.solve`, with 5 ms sleeps
in `bench.deltas` between them), and on hand-made intervals."""

import json
import os

import pytest

from benchmark_tiny import REPO
from benchmark import trace_reduce

TRACE = os.path.join(REPO, "benchmark", "testdata", "v5e_small.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce_trace(TRACE)


def test_benchmark_trace_busy_and_window(summary):
    assert summary["devices"] == 1
    assert 0.015 < summary["window_s"] < 0.03
    assert 0 < summary["busy_s"] < summary["window_s"]
    assert summary["idle_share"] == pytest.approx(
        1 - summary["busy_s"] / summary["window_s"]
    )


def test_benchmark_trace_top_ops_are_named_by_program(summary):
    ops = summary["device_ops"]
    assert 0 < len(ops) <= trace_reduce.TOP
    assert ops[0][0] == "jit__lambda/fusion"
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    assert sum(s for _, s in ops) == pytest.approx(summary["busy_s"], rel=1e-6)


def test_benchmark_trace_idle_gaps_by_host_span(summary):
    gaps = dict(summary["idle_gaps"])
    assert "bench.deltas" in gaps
    assert sum(gaps.values()) == pytest.approx(
        summary["window_s"] - summary["busy_s"], rel=1e-6
    )


def test_benchmark_trace_cli_prints_the_summary(capsys, summary):
    assert trace_reduce.main([TRACE]) == 0
    assert json.loads(capsys.readouterr().out) == json.loads(json.dumps(summary))


@pytest.mark.parametrize(
    "intervals,merged",
    [
        ([(0, 1), (2, 3)], [[0, 1], [2, 3]]),
        ([(0, 2), (1, 3)], [[0, 3]]),
        ([(1, 3), (0, 2), (5, 6), (2, 2.5)], [[0, 3], [5, 6]]),
        ([(0, 1), (1, 2)], [[0, 2]]),
    ],
)
def test_benchmark_trace_union(intervals, merged):
    assert trace_reduce._union(intervals) == merged


@pytest.mark.parametrize(
    "text,name",
    [
        ("%fusion.12 = bf16[] fusion(bf16[8] %x), kind=kLoop", "fusion.12"),
        ("%copy-start = (bf16[8]) copy-start(bf16[8] %x)", "copy-start"),
        ("while.3", "while.3"),
    ],
)
def test_benchmark_trace_op_names(text, name):
    assert trace_reduce.op_name(text) == name


def test_benchmark_peaks_for_v5e():
    peaks = trace_reduce.device_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in peaks["source"]


def test_benchmark_peaks_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        trace_reduce.device_peaks("cpu")
