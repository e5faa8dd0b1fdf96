"""Fixtures for the benchmark's CPU tests: `tiny_root` is a directory
with two tiny cells (benchmark_tiny.py), `tiny_run` runs one of them in
this process, on the CPU."""

import pytest

from benchmark_tiny import write_tiny_root


@pytest.fixture
def tiny_root(tmp_path):
    return write_tiny_root(tmp_path)


@pytest.fixture
def tiny_run(tiny_root, tmp_path, monkeypatch):
    """Run a tiny cell here on the CPU; the native parser is not built
    (other tests look for it)."""
    from armada_tpu.core import resources
    from benchmark.harness import run_cell

    monkeypatch.setattr(resources, "ensure_native", lambda *a, **k: False)

    def go(workload, seed=1234, seconds=2.0, trace=False):
        lines = []
        out = run_cell(
            tiny_root, workload, seed, seconds, trace,
            require_accelerator=False, out_dir=str(tmp_path / "out"),
            log=lines.append,
        )
        return out, lines

    return go
