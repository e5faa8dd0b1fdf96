"""Seeded traffic: a seed gives the same inputs every time; two seeds
give the same multiset of sizes, queues and gaps in another order."""

import collections

import numpy as np
import pytest

from benchmark_tiny import _load
from benchmark import generate

BIG = 2**33 + 17  # beyond 32 signed bits


@pytest.fixture(scope="module")
def flagship():
    cfg = _load("benchmark/configs/flagship-1m-50k.json")
    cfg["nodes"]["count"] = 16
    cfg["running"]["count"] = 40
    return cfg


def _take(cfg, seed, n=400):
    jobs = generate.JobStream(cfg["backlog"], cfg, seed, 1, "job-").take(n)
    return [(j.id, j.queue, j.requests["cpu"], j.requests["memory"]) for j in jobs]


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_benchmark_generate_same_seed_same_jobs(flagship, seed):
    assert _take(flagship, seed) == _take(flagship, seed)


def test_benchmark_generate_seeds_reorder_one_multiset(flagship):
    a, b = _take(flagship, 1), _take(flagship, BIG)
    assert a != b
    count = lambda jobs, k: collections.Counter(j[k] for j in jobs)  # noqa: E731
    for k in (1, 2, 3):
        assert count(a, k) == count(b, k)
    assert count(a, 2) == {"1": 100, "2": 100, "4": 100, "8": 100}
    assert set(count(a, 1).values()) == {40}


def test_benchmark_generate_arrival_gaps_are_one_multiset():
    a = generate.arrival_gaps(80.0, 30.0, 3)
    b = generate.arrival_gaps(80.0, 30.0, BIG)
    assert len(a) == len(b) == 2400
    assert not np.array_equal(a, b)
    assert np.array_equal(np.sort(a), np.sort(b))
    assert np.array_equal(a, generate.arrival_gaps(80.0, 30.0, 3))
    assert a.mean() == pytest.approx(1 / 80.0, rel=0.1)


@pytest.mark.parametrize("n,shares", [(10, [1, 1, 1]), (1000, [1, 4]), (7, [3, 1, 1, 2])])
def test_benchmark_generate_exact_counts(n, shares):
    counts = generate.exact_counts(n, shares)
    assert counts.sum() == n
    assert np.all(np.abs(counts - n * np.asarray(shares) / sum(shares)) < 1)


def test_benchmark_generate_running_population_is_seeded(flagship):
    nodes = generate.nodes(flagship)
    a = generate.running_jobs(flagship, nodes, 5)
    b = generate.running_jobs(flagship, nodes, 5)
    assert [(r.job.queue, r.node_id) for r in a] == [(r.job.queue, r.node_id) for r in b]
    assert len(a) == 40 and {r.node_id for r in a} == {n.id for n in nodes}
