"""Tracing + profiling hooks.

Plays the role of /root/reference/internal/common/observability/ (OTel
init, wired at schedulerapp.go:63-70) and internal/common/profiling/ (the
pprof HTTP endpoint): lightweight in-process spans with structured-log
export (no OTel collector exists in this environment; the span API is
OTel-shaped so an exporter can be dropped in), plus a cProfile-based
profile capture equivalent to pprof's CPU profile endpoint.

Cross-process propagation is W3C Trace Context: `Span.traceparent`
formats the header, `Tracer.span(remote_parent=...)` adopts one, and
services/grpc_api.py injects/extracts it on every unary RPC — so one
trace id follows a job submit -> ingest -> round -> lease -> run-report
(see docs/operations.md "Tracing a stuck job"). Export to Perfetto via
OtlpJsonFileExporter + tools/trace2perfetto.py.

Every span is also a `jax.profiler.TraceAnnotation` for its duration
(once jax is imported), so a profiler trace holds the program's spans on
the same host clock as the device's ops. Library code opens spans with
the module-level `span()`, which nests under whatever span this thread
has open. `GC_PAUSES` counts the garbage collector's pauses (installed
by utils/platform.setup_jax).
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import gc
import itertools
import re
import secrets
import sys
import threading
import time
from dataclasses import dataclass, field

# W3C Trace Context (https://www.w3.org/TR/trace-context/): the header
# key and the version-00 `traceparent` shape. Carried over gRPC metadata
# (services/grpc_api.py) and stamped onto EventSequences, so one trace id
# spans submit -> ingest -> round -> lease -> run-report across
# processes.
TRACEPARENT_HEADER = "traceparent"
_TRACEPARENT_RE = re.compile(
    r"^[0-9a-f]{2}-([0-9a-f]{32})-([0-9a-f]{16})-[0-9a-f]{2}$"
)


def format_traceparent(trace_id: str, span_id: str) -> str:
    """version 00, sampled flag set (we record everything we trace)."""
    return f"00-{trace_id}-{span_id}-01"


def parse_traceparent(value: str | None) -> tuple[str, str] | None:
    """(trace_id, parent_span_id) from a traceparent header, or None on
    anything malformed — a bad header must start a fresh trace, never
    crash the RPC carrying it."""
    if not value:
        return None
    m = _TRACEPARENT_RE.match(value.strip().lower())
    if m is None:
        return None
    trace_id, span_id = m.group(1), m.group(2)
    # All-zero ids are explicitly invalid per the spec.
    if set(trace_id) == {"0"} or set(span_id) == {"0"}:
        return None
    return trace_id, span_id


# Per-thread registry of OPEN spans across every Tracer instance: the
# logging layer (utils/logging.py) stamps the current trace id on every
# record, and a process may run several tracers at once (the process
# default plus an exporter-attached one in the scheduler) — log
# correlation must not care which instance opened the active span.
_ACTIVE_SPANS = threading.local()


def _active_stack() -> list:
    stack = getattr(_ACTIVE_SPANS, "stack", None)
    if stack is None:
        stack = _ACTIVE_SPANS.stack = []
    return stack


def current_trace_id() -> str:
    """Trace id of this thread's innermost open span, whichever Tracer
    opened it ("" outside any span) — what the JSON log formatter
    stamps on every record so log lines join the job-journey trace."""
    stack = _active_stack()
    return stack[-1].trace_id if stack else ""


def current_span_id() -> str:
    """Span id of this thread's innermost open span ("" outside)."""
    stack = _active_stack()
    return stack[-1].span_id if stack else ""


def _profiler_annotation(name: str):
    """A `jax.profiler.TraceAnnotation(name)` for the caller to enter
    while a profiler session records, else None (the inactive check is
    all it costs then). jax is never imported here: a process that has
    not imported it has no profiler session either."""
    prof = sys.modules.get("jax.profiler")
    cls = getattr(prof, "TraceAnnotation", None)
    if cls is None or not cls.is_enabled():
        return None
    return cls(name)


@dataclass
class Span:
    name: str
    # time.monotonic() at start and end.
    start: float
    attrs: dict = field(default_factory=dict)
    end: float | None = None
    parent: str = ""
    # Wall-clock epoch ns at start (exporters need absolute time; the
    # monotonic pair above is for durations).
    start_unix_ns: int = 0
    span_id: str = ""
    parent_id: str = ""
    trace_id: str = ""
    # Finish order within its tracer, and the tracer that opened it (the
    # module-level `span()` nests library spans into that tracer).
    seq: int = field(default=0, repr=False, compare=False)
    owner: Tracer | None = field(default=None, repr=False, compare=False)

    @property
    def duration_s(self) -> float:
        return (self.end or time.monotonic()) - self.start

    @property
    def traceparent(self) -> str:
        """This span's context as a W3C traceparent header value."""
        return format_traceparent(self.trace_id, self.span_id)


class OtlpJsonFileExporter:
    """Span exporter writing the OTLP/JSON `resourceSpans` shape, one
    export batch per line — the drop-in the in-proc tracer was missing
    (the reference initializes a real OTel exporter at
    common/observability; no collector runs in this environment, so the
    sink is a file any OTLP file-receiver or post-processor ingests)."""

    def __init__(self, path: str, service_name: str = "armada-tpu"):
        self.path = path
        self.service_name = service_name
        self._lock = threading.Lock()

    def export(self, spans: list[Span]) -> None:
        if not spans:
            return
        import json

        payload = {
            "resourceSpans": [
                {
                    "resource": {
                        "attributes": [
                            {
                                "key": "service.name",
                                "value": {"stringValue": self.service_name},
                            }
                        ]
                    },
                    "scopeSpans": [
                        {
                            "scope": {"name": "armada_tpu.utils.tracing"},
                            "spans": [
                                {
                                    "traceId": s.trace_id,
                                    "spanId": s.span_id,
                                    "parentSpanId": s.parent_id,
                                    "name": s.name,
                                    "kind": 1,  # SPAN_KIND_INTERNAL
                                    "startTimeUnixNano": str(s.start_unix_ns),
                                    "endTimeUnixNano": str(
                                        s.start_unix_ns
                                        + int(s.duration_s * 1e9)
                                    ),
                                    "attributes": [
                                        {
                                            "key": k,
                                            "value": {"stringValue": str(v)},
                                        }
                                        for k, v in s.attrs.items()
                                    ],
                                }
                                for s in spans
                            ],
                        }
                    ],
                }
            ]
        }
        line = json.dumps(payload) + "\n"
        with self._lock:
            with open(self.path, "a") as f:
                f.write(line)


class Tracer:
    """Per-process tracer: span stack per thread, a ring buffer of
    finished spans per span name (`keep` each, so a flood of one name —
    the gRPC spans of a busy server — never evicts another), optional
    logger export, optional OTLP exporter (batched; flushed every
    `export_every` finished spans or on flush()). Span names are the
    program's fixed vocabulary, so the rings are bounded too."""

    def __init__(self, logger=None, keep: int = 1024, exporter=None,
                 export_every: int = 64, export_interval_s: float = 10.0,
                 max_pending: int | None = None):
        self.logger = logger
        self.keep = keep
        self.exporter = exporter
        self.export_every = export_every
        # Time-based flush: low-traffic processes must not hold spans
        # hostage to the batch size (and atexit drains the final batch).
        self.export_interval_s = export_interval_s
        # A raising exporter must not grow _pending without bound while
        # it stays down: failed batches are retried on later flushes but
        # capped here (oldest dropped first; the `finished` ring buffer
        # stays the authoritative in-process record either way).
        self.max_pending = (
            max_pending if max_pending is not None else max(keep, 8 * export_every)
        )
        self._export_warned = False
        self.export_failures = 0
        self._last_flush = time.monotonic()
        self._rings: dict[str, collections.deque] = {}
        self._seq = itertools.count(1)
        self._pending: list[Span] = []
        self._local = threading.local()
        # Re-entrant: the GC hook finishes a `gc` span from inside
        # whatever allocation triggered the collection, possibly while
        # this thread already holds the lock.
        self._lock = threading.RLock()
        if exporter is not None:
            import atexit

            atexit.register(self.flush)

    def _stack(self):
        if not hasattr(self._local, "stack"):
            self._local.stack = []
        return self._local.stack

    def current_span(self) -> Span | None:
        """This thread's innermost open span, or None."""
        stack = self._stack()
        return stack[-1] if stack else None

    def current_traceparent(self) -> str:
        """W3C traceparent of the current span ("" outside any span) —
        what gRPC clients inject into call metadata."""
        s = self.current_span()
        return s.traceparent if s is not None else ""

    @contextlib.contextmanager
    def span(self, name: str, remote_parent: str | None = None, **attrs):
        """Open a span. `remote_parent` is a W3C traceparent header value
        from the wire: when there is no local parent span, the new span
        joins that remote trace instead of opening a fresh one (the
        server-side half of context propagation). A local parent always
        wins — nesting inside this process is already one trace. While
        a profiler session records, the span is also a
        `jax.profiler.TraceAnnotation` of the same name."""
        stack = self._stack()
        parent = stack[-1] if stack else None
        trace_id = parent.trace_id if parent else ""
        parent_id = parent.span_id if parent else ""
        if parent is None:
            remote = parse_traceparent(remote_parent)
            if remote is not None:
                trace_id, parent_id = remote
        s = self._new_span(name, time.monotonic(), attrs, parent, parent_id,
                           trace_id)
        stack.append(s)
        _active_stack().append(s)
        annotation = _profiler_annotation(name)
        if annotation is not None:
            annotation.__enter__()
        try:
            yield s
        finally:
            if annotation is not None:
                annotation.__exit__(None, None, None)
            s.end = time.monotonic()
            stack.pop()
            _active_stack().pop()
            self._finish(s)
            if self.logger is not None:
                self.logger.with_fields(
                    span=name, parent=s.parent,
                    duration_ms=round(s.duration_s * 1e3, 2),
                    **attrs,
                ).debug("span finished")

    def _new_span(self, name, start, attrs, parent, parent_id, trace_id):
        return Span(
            name=name,
            start=start,
            attrs=attrs,
            parent=parent.name if parent else "",
            start_unix_ns=time.time_ns() - int((time.monotonic() - start) * 1e9),
            span_id=secrets.token_hex(8),
            parent_id=parent_id,
            # Root spans open a new trace; children inherit it.
            trace_id=trace_id or secrets.token_hex(16),
            owner=self,
        )

    def _finish_measured(self, name: str, start: float, end: float,
                         **attrs) -> None:
        """A span whose start and end (time.monotonic()) were read as
        the work began and ended, by code that cannot hold a context
        manager open across it (the GC hook); a child of this thread's
        innermost open span."""
        active = _active_stack()
        parent = active[-1] if active else None
        s = self._new_span(name, start, attrs, parent,
                           parent.span_id if parent else "",
                           parent.trace_id if parent else "")
        s.end = end
        self._finish(s)

    def _finish(self, s: Span) -> None:
        with self._lock:
            s.seq = next(self._seq)
            ring = self._rings.get(s.name)
            if ring is None:
                ring = self._rings[s.name] = collections.deque(maxlen=self.keep)
            ring.append(s)
            flush_now = False
            if self.exporter is not None:
                self._pending.append(s)
                flush_now = (
                    len(self._pending) >= self.export_every
                    or time.monotonic() - self._last_flush
                    >= self.export_interval_s
                )
        if flush_now:
            self.flush()

    def flush(self) -> None:
        """Export pending spans (batch-size/interval triggers, atexit).
        Exporter failures never propagate to the traced code path: the
        batch is re-queued for a later flush, bounded by max_pending."""
        if self.exporter is None:
            return
        with self._lock:
            batch, self._pending = self._pending, []
            self._last_flush = time.monotonic()
        if not batch:
            return
        try:
            self.exporter.export(batch)
        except Exception as e:  # noqa: BLE001 - observability must not fail work
            self.export_failures += 1
            with self._lock:
                requeued = batch + self._pending
                self._pending = requeued[-self.max_pending:]
            if not self._export_warned:
                self._export_warned = True
                import logging

                logging.getLogger("armada_tpu.tracing").warning(
                    "span exporter failed (%r); retrying on later flushes, "
                    "pending capped at %d spans (ring buffer unaffected). "
                    "Further failures are silent.",
                    e,
                    self.max_pending,
                )

    def spans(self, name: str | None = None) -> list[Span]:
        """The retained finished spans named `name` (of every name when
        None), in the order they finished. Each has `start` and `end` on
        time.monotonic(), `span_id`, `parent_id` and `attrs`."""
        with self._lock:
            if name is not None:
                return list(self._rings.get(name, ()))
            spans = [s for ring in self._rings.values() for s in ring]
        spans.sort(key=lambda s: s.seq)
        return spans

    @property
    def finished(self) -> list[Span]:
        """Every retained finished span, in the order they finished."""
        return self.spans()

    def summary(self) -> dict:
        """Aggregate durations by span name (count, total, max)."""
        out: dict[str, dict] = {}
        for s in self.spans():
            bucket = out.setdefault(
                s.name, {"count": 0, "total_s": 0.0, "max_s": 0.0}
            )
            bucket["count"] += 1
            bucket["total_s"] += s.duration_s
            bucket["max_s"] = max(bucket["max_s"], s.duration_s)
        return out


# Process-wide default tracer (observability.Init analogue).
TRACER = Tracer()


def span(name: str, **attrs):
    """A span for library code that holds no tracer: a child of this
    thread's innermost open span, in the tracer that opened it (so the
    solve's spans nest under `scheduler.round` in whichever tracer the
    scheduler has attached), else a root span in `TRACER`."""
    active = _active_stack()
    tracer = active[-1].owner if active else None
    return (tracer or TRACER).span(name, **attrs)


class GcPauses:
    """Process counter of the garbage collector's pauses, fed by a
    `gc.callbacks` hook: seconds, collections and objects collected per
    generation, plus a timeline coarse enough to stay small (the running
    total at the end of each `grain_s` of wall time, the last `keep` of
    them) so a reader can ask for the pauses inside a window after the
    fact. Each generation-2 collection is also a `gc` span in `tracer`;
    while a profiler session records, every collection is a `gc`
    annotation in its trace. The hook allocates a few floats per
    collection (generation 0 runs every few hundred allocations)."""

    def __init__(self, tracer: Tracer, grain_s: float = 0.1, keep: int = 8192):
        self.tracer = tracer
        self.seconds = [0.0, 0.0, 0.0]
        self.count = [0, 0, 0]
        self.collected = [0, 0, 0]
        self.installed = False
        self._grain = grain_s
        self._keep = keep
        self._mark_grain = [0] * keep
        self._mark_total = [0.0] * keep
        self._marks = 0
        self._total = 0.0
        self._t0 = 0.0
        self._annotation = None

    def install(self) -> None:
        """Hook the collector (idempotent)."""
        if not self.installed:
            gc.callbacks.append(self._callback)
            self.installed = True

    def _callback(self, phase, info):
        if phase == "start":
            self._annotation = _profiler_annotation("gc")
            if self._annotation is not None:
                self._annotation.__enter__()
            self._t0 = time.monotonic()
            return
        t1 = time.monotonic()
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
        self._note(info["generation"], info["collected"], self._t0, t1)

    def _note(self, generation: int, collected: int, t0: float, t1: float):
        """Count one collection that paused from t0 to t1."""
        pause = t1 - t0
        gen = min(int(generation), 2)
        self.seconds[gen] += pause
        self.count[gen] += 1
        self.collected[gen] += collected
        self._total += pause
        g = int(t1 / self._grain)
        last = (self._marks - 1) % self._keep
        if self._marks and self._mark_grain[last] == g:
            self._mark_total[last] = self._total
        else:
            at = self._marks % self._keep
            self._mark_grain[at] = g
            self._mark_total[at] = self._total
            self._marks += 1
        if gen == 2:
            self.tracer._finish_measured(
                "gc", t0, t1, generation=gen, collected=collected
            )

    def _total_at(self, t: float) -> float | None:
        """Pause seconds of the collections that ended by the end of
        `t`'s grain; None when the timeline no longer reaches back."""
        n = min(self._marks, self._keep)
        start = self._marks - n
        order = [(start + i) % self._keep for i in range(n)]
        grains = [self._mark_grain[i] for i in order]
        g = int(t / self._grain)
        k = bisect.bisect_right(grains, g)
        if k == 0:
            return 0.0 if self._marks <= self._keep else None
        return self._mark_total[order[k - 1]]

    def pause_s_between(self, t0: float, t1: float) -> float | None:
        """Seconds the collector paused between time.monotonic() `t0`
        and `t1`, to the grain at each end; None before the hook was
        installed or where the timeline no longer reaches back to t0."""
        if not self.installed:
            return None
        lo, hi = self._total_at(t0), self._total_at(t1)
        if lo is None or hi is None:
            return None
        return hi - lo


# The process's GC counter; utils/platform.setup_jax installs it.
GC_PAUSES = GcPauses(TRACER)


@contextlib.contextmanager
def profile_cpu(path: str):
    """Capture a CPU profile to `path` (pprof StartCPUProfile analogue);
    readable with pstats / snakeviz."""
    import cProfile

    profiler = cProfile.Profile()
    profiler.enable()
    try:
        yield profiler
    finally:
        profiler.disable()
        profiler.dump_stats(path)
