"""Run one cell of the benchmark once and print its result line.

`BENCHMARK.json` names the cell. The cell names a configuration, whose
file (`benchmark/configs/<config>.json`) holds the deployment, and a
traffic mix, whose file (`benchmark/traffic/<mix>.json`) holds the
parameters of the load and names the runner
(`benchmark/runners/<runner>.py`) that runs it. A per-layer metric is
read by `benchmark/metrics/<metric>.py`. All of them are found by name,
so a new configuration, mix, cell or metric is a new file and a new
entry, never an edit.

A runner receives a `Run` and does the cell's work in three phases:
set-up (build the inputs, warm every shape the window uses), the window
(`--seconds` of timed work, no compiles), and the check (the window's
answers against the plain reference in `benchmark/reference/`). It
records end-to-end values, host spans, program counters and the numbers
it compared; the harness turns them into the result line.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import logging
import os
import shutil
import sys
import time
from collections import defaultdict

T_START = time.monotonic()


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One `workloads` entry of BENCHMARK.json with what it names."""

    def __init__(self, root: str, workload: str):
        self.root = root
        bench = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
        entry = cells[workload]
        self.name = workload
        self.chips = int(entry["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_name = entry["config"]
        self.config = load_json(os.path.join(root, configs[self.config_name]["file"]))
        self.traffic_name = entry["traffic"]
        self.traffic = load_json(self.find("traffic", f"{self.traffic_name}.json"))
        self.limits = load_json(self.find(".", "limits.json"))

        def mine(metric):
            return workload in metric.get("workloads", [workload])

        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            m
            for m in bench["per_layer"]
            if mine(m) and m["moves"] in reported
        ]

    def find(self, kind: str, filename: str) -> str:
        """`<root>/benchmark/<kind>/<filename>`."""
        path = os.path.normpath(os.path.join(self.root, "benchmark", kind, filename))
        if not os.path.exists(path):
            raise FileNotFoundError(f"benchmark/{kind}/{filename}")
        return path

    def runner(self):
        name = self.traffic["runner"]
        return load_module(
            self.find("runners", f"{name}.py"), f"benchmark_runner_{name}"
        )

    def reader(self, metric: str):
        return load_module(
            self.find("metrics", f"{metric}.py"),
            f"benchmark_metric_{metric.replace('.', '_').replace('-', '_')}",
        )


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


class CacheLog(logging.Handler):
    """Persistent compile-cache hits and misses by program name, from
    the messages JAX's compiler logs for each lookup."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.hits: list[str] = []
        self.misses: list[str] = []
        self.lookups: list[str] = []  # both, in order

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Persistent compilation cache hit for '"):
            self.hits.append(msg.split("'")[1])
            self.lookups.append(self.hits[-1])
        elif msg.startswith("PERSISTENT COMPILATION CACHE MISS for '"):
            self.misses.append(msg.split("'")[1])
            self.lookups.append(self.misses[-1])

    def install(self):
        logger = logging.getLogger("jax._src.compiler")
        logger.setLevel(logging.DEBUG)
        logger.propagate = False
        logger.addHandler(self)


class Run:
    """What a runner reads (the cell, the seed, the window) and fills in."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 out_dir: str):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.out_dir = out_dir
        self.e2e: dict[str, float] = {}
        self.spans: dict[str, list[float]] = defaultdict(list)
        self.counters: dict[str, float] = {}
        self.check: dict[str, tuple[float, float]] = {}
        self.attempted = 0
        self.failed = 0
        self.report: dict = {}
        self.trace_summary: dict | None = None
        self.memory_peak_bytes: int | None = None
        self.devices = []
        self.recording = False
        self._trace_dir = None
        self._window_cm = None
        self.cache_log = CacheLog()

    # -- spans ---------------------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span on the profiler's clock (`bench.<name>`), kept in
        `spans` while `recording` is on."""
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.{name}"):
            yield
        if self.recording:
            self.spans[name].append(time.perf_counter() - t0)

    # -- tracing -------------------------------------------------------

    def start_trace(self):
        """Start the profiler (only in a `--trace 1` run) and record
        spans from here on."""
        import jax

        self.recording = True
        if not self.trace:
            return
        self._trace_dir = os.path.join(
            self.out_dir, "trace", f"{self.cell.name}-{self.seed}"
        )
        jax.profiler.start_trace(self._trace_dir)
        self._window_cm = jax.profiler.TraceAnnotation("bench.window")
        self._window_cm.__enter__()

    def stop_trace(self):
        import jax

        self.recording = False
        if self._window_cm is None:
            return
        self._window_cm.__exit__(None, None, None)
        self._window_cm = None
        jax.profiler.stop_trace()

    def reduce_trace(self):
        if self._trace_dir is None:
            return
        from benchmark.trace_reduce import find_xplane, reduce_trace

        path = find_xplane(self._trace_dir)
        if path is not None:
            self.trace_summary = reduce_trace(path)
        shutil.rmtree(self._trace_dir, ignore_errors=True)

    # -- device --------------------------------------------------------

    def read_memory_peak(self):
        peaks = [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in self.devices
        ]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak_bytes = max(peaks) if peaks else None


def init_jax(cell: Cell, require_accelerator: bool):
    """The program's JAX set-up (exact costs, the persistent compile
    cache at its fixed in-checkout path) and the device check. Every
    compiled program is cached, so only a checkout's first run
    compiles."""
    from armada_tpu.core import resources
    from armada_tpu.utils.platform import setup_jax

    setup_jax()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if require_accelerator and devices[0].platform == "cpu":
        raise NoAccelerator("JAX found no accelerator")
    if len(devices) < cell.chips:
        raise NoAccelerator(
            f"the cell needs {cell.chips} chips; JAX found {len(devices)}"
        )
    resources.ensure_native()  # the C++ quantity parser, built in the checkout
    return devices[: cell.chips]


def run_cell(root: str, workload: str, seed: int, seconds: float, trace: bool,
             *, require_accelerator: bool = True, out_dir: str | None = None,
             log=None):
    """Run one cell once; returns the result dict (the last line)."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    cell = Cell(root, workload)
    run = Run(cell, seed, seconds, trace, out_dir or os.path.join(root, ".bench"))
    run.cache_log.install()
    run.devices = init_jax(cell, require_accelerator)
    cell.runner().run(run)
    return result_line(run, log)


def result_line(run: Run, log) -> dict:
    cell = run.cell
    d0 = run.devices[0]
    device = {
        "platform": d0.platform,
        "kind": d0.device_kind,
        "count": len(run.devices),
        "memory_peak_bytes": run.memory_peak_bytes,
    }
    metrics = {}
    breakdown = None
    if run.trace:
        summary = run.trace_summary
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            breakdown = {
                "device_ops": summary["device_ops"],
                "idle_gaps": summary["idle_gaps"],
            }
        for m in cell.per_layer:
            value = cell.reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            if m["name"] in run.e2e:
                metrics[m["name"]] = {
                    "value": float(run.e2e[m["name"]]),
                    "unit": m["unit"],
                }
    check = {
        name: {"value": float(v), "limit": float(lim)}
        for name, (v, lim) in run.check.items()
    }
    correct = bool(check) and all(
        c["value"] <= c["limit"] for c in check.values()
    )
    out = {
        "correct": correct,
        "attempted": int(run.attempted),
        "failed": int(run.failed),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = check
    for name, c in check.items():
        log(f"check {name} = {c['value']!r} limit {c['limit']!r}")
    return out


def json_line(**fields):
    return json.dumps(fields)


def log_setup(run, setup_s):
    """The set-up report: compile-cache hits and misses (with the
    programs that missed), input-build seconds, peak device memory
    against the chip's memory."""
    import jax

    d = run.devices[0]
    stats = d.memory_stats() or {}
    fields = dict(run.report)
    fields.update(
        setup_s=round(setup_s, 3),
        cache_hits=len(run.cache_log.hits),
        cache_misses=len(run.cache_log.misses),
        missed=sorted(set(run.cache_log.misses)),
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        bytes_limit=stats.get("bytes_limit"),
        x64=bool(jax.config.jax_enable_x64),
    )
    print("setup " + json_line(**fields), flush=True)


def window_programs(run, since: int) -> list[str]:
    """Programs looked up in the compile cache since `since` lookups:
    a warm window looks up none."""
    return sorted(set(run.cache_log.lookups[since:]))


def check_window_compiles(run, since: int, telemetry: dict):
    """A window compiles nothing: every program it runs was compiled or
    loaded in set-up. The count is the larger of the compile-cache
    lookups (any thread) and the program's own compile telemetry since
    the window opened; it is a compared number with the limit 0."""
    n = max(len(run.cache_log.lookups) - since, int(telemetry["compiles"]))
    run.check["window_compiles"] = (n, run.cell.limits["window_compiles"])


def warm_resident(resident) -> int:
    """Compile in set-up every delta program the residency sync
    (`snapshot/residency.py`) can dispatch for this round's shapes: the
    row scatter of each field at each batch bucket below the size at
    which it replaces the field whole, and the slot gather. A bucket
    seen first in the window would otherwise compile there. Each is run
    twice, once on an uploaded buffer and once on a program's output, as
    the sync meets both. Returns the number of programs warmed; 0 where
    the program has no such sync."""
    import jax
    import numpy as np

    try:
        from armada_tpu.snapshot import residency as rs

        host = resident.host_round()
        fields, axis1, slots = rs._DATA_FIELDS, rs._AXIS1_FIELDS, rs._SLOT_FIELDS
        bucket, jit_for = rs._bucket, rs._jit_for
    except AttributeError:
        return 0
    if host is None:
        return 0
    done = set()

    def twice(fn, cur, *args):
        out = fn(jax.device_put(np.zeros_like(cur)), *args)
        jax.block_until_ready(fn(out, *args))

    for name in fields:
        cur = getattr(host, name)
        if not (isinstance(cur, np.ndarray) and cur.ndim >= 1 and cur.size):
            continue
        axis = 1 if name in axis1 else 0
        row_bytes = max(1, cur.nbytes // cur.shape[axis])
        kb = bucket(1)
        while kb * (4 + row_bytes) < cur.nbytes:
            key = ("s", axis, cur.shape, cur.dtype.str, kb)
            if key not in done:
                done.add(key)
                idx = np.zeros(kb, dtype=np.int32)
                vals = np.ascontiguousarray(np.take(cur, idx, axis=axis))
                twice(jit_for("s1" if axis == 1 else "s0"), cur, idx, vals)
            kb *= 4
        if name in slots:
            key = ("g", cur.shape, cur.dtype.str)
            if key not in done:
                done.add(key)
                perm = jax.device_put(np.arange(cur.shape[0], dtype=np.int32))
                twice(jit_for("g0"), cur, perm)
    return len(done)
