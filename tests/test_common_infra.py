"""Common infrastructure: health endpoints, payload compression, tracing,
config loading (common/{health,compress,observability,config} analogues)."""

import json
import time
import urllib.request

from armada_tpu.core.config import SchedulingConfig, load_config, validate_config
from armada_tpu.services.health import (
    FuncChecker,
    HeartbeatChecker,
    MultiChecker,
    StartupCompleteChecker,
    serve_health,
)
from armada_tpu.utils.compress import compress_obj, decompress_obj
from armada_tpu.utils.tracing import Tracer, profile_cpu


def test_health_endpoint_and_checkers():
    startup = StartupCompleteChecker()
    hb = HeartbeatChecker("cycle", timeout_s=60.0)
    multi = MultiChecker(startup, hb, FuncChecker("log", lambda: (True, "ok")))
    server, port = serve_health(multi, startup)
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/health/startup") as r:
            assert r.status == 503  # not started yet... urllib raises on 503
    except urllib.error.HTTPError as e:
        assert e.code == 503
    startup.mark_complete()
    hb.beat()
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/health") as r:
        body = json.loads(r.read())
        assert r.status == 200 and body["ok"]
        assert set(body["checks"]) == {"startup", "cycle", "log"}
    server.shutdown()


def test_heartbeat_checker_times_out():
    hb = HeartbeatChecker("cycle", timeout_s=0.01)
    time.sleep(0.05)
    ok, detail = hb.check()
    assert not ok and "last beat" in detail


def test_solver_ladder_checker_is_advisory():
    """A degraded ladder or recent rejections stay HEALTHY (restarting
    would discard the breaker state routing around the fault) but name
    the degraded rungs and point at `armadactl doctor`."""
    from armada_tpu.services.health import SolverLadderChecker

    class Degraded:
        def doctor_report(self):
            return {
                "ladder": [
                    {"rung": "LOCAL", "state": "closed"},
                    {"rung": "hotwindow:64", "state": "half-open"},
                ],
                "rejections": [{"cycle": 3}],
            }

    ok, detail = SolverLadderChecker(Degraded()).check()
    assert ok
    assert "hotwindow:64=half-open" in detail and "LOCAL" not in detail
    assert "1 recent round rejection" in detail and "doctor" in detail

    class Healthy:
        def doctor_report(self):
            return {"ladder": [{"rung": "oracle", "state": "closed"}],
                    "rejections": []}

    ok, detail = SolverLadderChecker(Healthy()).check()
    assert ok and "all solver rungs closed" in detail

    class NoLadder:
        doctor_report = None

    ok, detail = SolverLadderChecker(NoLadder()).check()
    assert ok and "no solve ladder" in detail


def test_compress_roundtrip_and_threshold():
    small = {"id": "x"}
    assert compress_obj(small) == small  # below threshold: unchanged
    big = {"data": "y" * 10_000}
    packed = compress_obj(big)
    assert "__zlib__" in packed
    assert len(json.dumps(packed)) < len(json.dumps(big)) // 5
    assert decompress_obj(packed) == big
    assert decompress_obj(small) == small


def test_tracer_spans_and_summary():
    tracer = Tracer()
    with tracer.span("cycle", pool="default"):
        with tracer.span("solve"):
            pass
    summary = tracer.summary()
    assert summary["cycle"]["count"] == 1
    assert summary["solve"]["count"] == 1
    assert tracer.finished[-1].name == "cycle"
    assert tracer.finished[0].parent == "cycle"


def test_profile_cpu(tmp_path):
    out = tmp_path / "profile.pstats"
    with profile_cpu(str(out)):
        sum(range(1000))
    import pstats

    stats = pstats.Stats(str(out))
    assert stats.total_calls >= 1


def test_load_config_env_override_and_validation(tmp_path):
    cfg_file = tmp_path / "config.yaml"
    cfg_file.write_text(
        "scheduling:\n  maxQueueLookback: 1234\n  enableFastFill: false\n"
    )
    cfg = load_config(
        str(cfg_file),
        env={"ARMADA__enableFastFill": "true", "IGNORED": "x"},
    )
    assert cfg.max_queue_lookback == 1234
    assert cfg.enable_fast_fill is True
    validate_config(SchedulingConfig())
    try:
        load_config(env={"ARMADA__defaultPriorityClassName": "ghost"})
        assert False, "expected validation failure"
    except ValueError as e:
        assert "priority class" in str(e)


def test_otlp_json_file_exporter(tmp_path):
    """Spans export in the OTLP/JSON resourceSpans shape with trace/span
    id propagation — the exporter the in-proc tracer plugs into
    (common/observability's OTel init analogue)."""
    import json

    from armada_tpu.utils.tracing import OtlpJsonFileExporter, Tracer

    path = str(tmp_path / "spans.otlp.jsonl")
    tracer = Tracer(exporter=OtlpJsonFileExporter(path), export_every=100)
    with tracer.span("cycle", pool="default") as outer:
        with tracer.span("solve") as inner:
            pass
    tracer.flush()

    lines = open(path).read().strip().splitlines()
    assert len(lines) == 1
    batch = json.loads(lines[0])
    resource = batch["resourceSpans"][0]
    svc = resource["resource"]["attributes"][0]
    assert svc["key"] == "service.name"
    spans = resource["scopeSpans"][0]["spans"]
    by_name = {s["name"]: s for s in spans}
    assert set(by_name) == {"cycle", "solve"}
    # Child joins the parent's trace and points at its span id.
    assert by_name["solve"]["traceId"] == by_name["cycle"]["traceId"]
    assert by_name["solve"]["parentSpanId"] == by_name["cycle"]["spanId"]
    assert by_name["cycle"]["parentSpanId"] == ""
    assert int(by_name["cycle"]["endTimeUnixNano"]) >= int(
        by_name["cycle"]["startTimeUnixNano"]
    )
    assert by_name["cycle"]["attributes"][0] == {
        "key": "pool", "value": {"stringValue": "default"},
    }


class _CollectExporter:
    def __init__(self):
        self.batches = []

    def export(self, spans):
        self.batches.append(list(spans))


class _BoomExporter:
    def export(self, spans):
        raise RuntimeError("collector down")


def test_tracer_flush_on_interval():
    """A low-traffic process must not hold spans hostage to the batch
    size: once export_interval_s elapses, the next finished span
    triggers a flush even far below export_every."""
    import time as _t

    exporter = _CollectExporter()
    tracer = Tracer(exporter=exporter, export_every=1000,
                    export_interval_s=0.05)
    with tracer.span("early"):
        pass
    assert exporter.batches == []  # within the interval, batch too small
    _t.sleep(0.06)
    with tracer.span("late"):
        pass
    assert len(exporter.batches) == 1
    assert [s.name for s in exporter.batches[0]] == ["early", "late"]


def test_tracer_atexit_drains_final_batch(monkeypatch):
    """Building a Tracer with an exporter registers its flush with
    atexit, so the final sub-batch is not lost at process exit."""
    import atexit

    registered = []
    monkeypatch.setattr(atexit, "register", registered.append)
    exporter = _CollectExporter()
    tracer = Tracer(exporter=exporter, export_every=1000)
    with tracer.span("tail"):
        pass
    assert exporter.batches == []
    assert registered == [tracer.flush]
    registered[0]()  # what atexit runs at interpreter shutdown
    assert [s.name for s in exporter.batches[0]] == ["tail"]


def test_tracer_exporter_failure_caps_pending_and_recovers():
    """A raising exporter must not grow _pending without bound (capped,
    oldest dropped) nor lose the batch silently once it heals; the
    finished ring buffer stays authoritative throughout."""
    tracer = Tracer(exporter=_BoomExporter(), export_every=1, keep=100,
                    max_pending=3)
    for i in range(8):
        with tracer.span(f"s{i}"):
            pass
    assert tracer.export_failures >= 1
    assert len(tracer._pending) == 3  # capped, not 8
    assert len(tracer.finished) == 8  # ring buffer unaffected
    # Collector heals: the retained tail drains on the next flush.
    healed = _CollectExporter()
    tracer.exporter = healed
    tracer.flush()
    assert [s.name for s in healed.batches[0]] == ["s5", "s6", "s7"]
    assert tracer._pending == []


def test_traceparent_parse_and_format():
    from armada_tpu.utils.tracing import (
        format_traceparent,
        parse_traceparent,
    )

    tp = format_traceparent("ab" * 16, "cd" * 8)
    assert tp == f"00-{'ab' * 16}-{'cd' * 8}-01"
    assert parse_traceparent(tp) == ("ab" * 16, "cd" * 8)
    assert parse_traceparent("") is None
    assert parse_traceparent(None) is None
    assert parse_traceparent("00-short-bad-01") is None
    # all-zero ids are invalid per the W3C spec
    assert parse_traceparent(f"00-{'0' * 32}-{'cd' * 8}-01") is None
    # a remote parent is adopted only when there is no local parent
    tracer = Tracer()
    with tracer.span("root", remote_parent=tp) as root:
        assert root.trace_id == "ab" * 16
        assert root.parent_id == "cd" * 8
        with tracer.span("child", remote_parent=None) as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
    # malformed headers start a fresh trace instead of failing the RPC
    with tracer.span("fresh", remote_parent="garbage") as fresh:
        assert fresh.trace_id not in ("", "ab" * 16)


def test_background_task_manager():
    """common/task BackgroundTaskManager semantics: interval between
    RETURNS, panic containment per task, join-on-stop with straggler
    reporting."""
    import threading
    import time as _t

    from armada_tpu.utils.tasks import BackgroundTaskManager

    mgr = BackgroundTaskManager()
    runs = {"ok": 0}

    def ok():
        runs["ok"] += 1

    def bad():
        raise RuntimeError("boom")

    mgr.register(ok, 0.01, "ok")
    mgr.register(bad, 0.01, "bad")
    deadline = _t.time() + 5
    while _t.time() < deadline and (
        runs["ok"] < 3 or mgr.stats()["bad"]["failures"] < 3
    ):
        _t.sleep(0.01)
    stats = mgr.stats()
    assert stats["ok"]["runs"] >= 3
    assert stats["bad"]["failures"] >= 3  # contained, siblings unaffected
    assert mgr.stop_all(timeout=2.0) == []
    n = stats["ok"]["runs"]
    _t.sleep(0.05)
    assert mgr.stats()["ok"]["runs"] <= n + 1  # actually stopped

    # A straggler (blocked task) is reported, not hung on forever.
    mgr2 = BackgroundTaskManager()
    release = threading.Event()
    mgr2.register(lambda: release.wait(30), 0.01, "stuck")
    _t.sleep(0.05)
    assert mgr2.stop_all(timeout=0.2) == ["stuck"]
    release.set()


def test_compile_cache_dir_honours_env(monkeypatch, tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, the program uses it and
    sets no other directory."""
    import jax

    from armada_tpu.utils import platform as plat

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert plat.compile_cache_dir() == str(tmp_path)
    before = jax.config.jax_compilation_cache_dir
    plat.enable_persistent_compile_cache()
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_dir_default_is_fixed(monkeypatch):
    """Unset, the cache is `<repo>/.jax_cache` — the same path on every
    host and run, so a later process finds what an earlier one wrote."""
    import os

    from armada_tpu.utils import platform as plat

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert plat.compile_cache_dir() == os.path.join(repo, ".jax_cache")
    assert plat.compile_cache_dir() == plat.compile_cache_dir()
