"""Staleness guard for docs/architecture.md "Known gaps".

The gaps list rotted twice (it kept claiming a JSON-only executor wire
and a ~330-line UI long after both were obsolete), so the section is now
GENERATED from the tracked checklist docs/known_gaps.yaml
(tools/gen_known_gaps.py) and this suite makes the checklist itself
self-verifying:

  - the rendered section must match the doc byte-for-byte (no hand
    edits, no drift);
  - every OPEN gap carries a feature detector answering "does the
    claimed-missing feature exist now?" — a gap whose feature exists
    fails (stale claim), a gap with no detector fails (unguarded);
  - every OPEN gap names its future closer test; if that test already
    exists AND passes, the suite fails — flip the gap to closed;
  - every CLOSED gap's closer test must exist (the evidence that closed
    it cannot silently vanish).
"""

import os
import re
import subprocess
import sys

REPO = os.path.join(os.path.dirname(__file__), "..")
DOC = os.path.join(REPO, "docs", "architecture.md")

sys.path.insert(0, os.path.join(REPO, "tools"))
from gen_known_gaps import SECTION_RE, load_gaps, render  # noqa: E402

GAPS = load_gaps()


def _gaps_section() -> str:
    with open(DOC) as f:
        text = f.read()
    m = SECTION_RE.search(text)
    assert m, "docs/architecture.md lost its 'Known gaps' section"
    return m.group(0)


def _feature_exists_kubernetes() -> bool:
    # A kubelet/kube-api integration would import the kubernetes client.
    root = os.path.join(REPO, "armada_tpu")
    for dirpath, _, files in os.walk(root):
        for name in files:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(dirpath, name)) as f:
                if re.search(r"^\s*(import|from) kubernetes", f.read(), re.M):
                    return True
    return False


def _feature_exists_rich_lookout_ui() -> bool:
    # The gap claims "a fraction of the surface" of a 22.6k-line app:
    # consider it closed once the UI grows past a few thousand lines.
    path = os.path.join(REPO, "armada_tpu", "services", "lookout_ui.py")
    with open(path) as f:
        return sum(1 for _ in f) > 5000


def _feature_exists_cpp_grpc() -> bool:
    client_dir = os.path.join(REPO, "native", "client")
    if not os.path.isdir(client_dir):
        return False
    for dirpath, _, files in os.walk(client_dir):
        for name in files:
            if name.endswith((".cpp", ".cc", ".h", ".hpp")):
                with open(os.path.join(dirpath, name), errors="replace") as f:
                    if "grpc::" in f.read():
                        return True
    return False


def _feature_exists_scala_client() -> bool:
    return os.path.isdir(os.path.join(REPO, "client", "scala"))


def _feature_exists_sharded_budget() -> bool:
    # Closed once the mesh solve takes a budget (chunked pass 1).
    path = os.path.join(REPO, "armada_tpu", "parallel", "mesh.py")
    with open(path) as f:
        return "budget" in f.read()


def _feature_exists_window_autotune() -> bool:
    # Closed once something adapts the hot-window size at runtime: an
    # autotune hook in the solver or a config switch for it.
    solver = os.path.join(REPO, "armada_tpu", "solver")
    for name in os.listdir(solver):
        if name.endswith(".py"):
            with open(os.path.join(solver, name)) as f:
                if "autotune" in f.read().lower():
                    return True
    with open(os.path.join(REPO, "armada_tpu", "core", "config.py")) as f:
        return "autotune" in f.read().lower()


def _feature_exists_native_ring_test() -> bool:
    # Closed once the native ICI ring runs on real hardware in a test:
    # the promoted form of chip_smoke.py's mesh phase.
    path = os.path.join(REPO, "tests", "test_pallas_parity.py")
    if not os.path.exists(path):
        return False
    with open(path) as f:
        return "def test_native_ring_on_hardware(" in f.read()


DETECTORS = {
    "kubernetes": _feature_exists_kubernetes,
    "lookout-ui-surface": _feature_exists_rich_lookout_ui,
    "cpp-client-grpc": _feature_exists_cpp_grpc,
    "scala-client": _feature_exists_scala_client,
    "sharded-round-budget": _feature_exists_sharded_budget,
    "hot-window-autotune": _feature_exists_window_autotune,
    "pallas-ici-native": _feature_exists_native_ring_test,
}


def _closer_exists(closer: str) -> bool:
    """Does the pytest node id point at an existing test function?
    Handles both module-level ids (file::test) and class-based ones
    (file::Class::test — the def is indented, the class must exist)."""
    parts = closer.split("::")
    path, func = parts[0], parts[-1]
    full = os.path.join(REPO, path)
    if not os.path.exists(full):
        return False
    with open(full) as f:
        src = f.read()
    for cls in parts[1:-1]:
        if re.search(rf"^class {re.escape(cls)}\b", src, re.M) is None:
            return False
    return re.search(rf"^[ \t]*def {re.escape(func)}\(", src, re.M) is not None


def test_doc_matches_checklist():
    """The doc section is exactly the YAML rendering — regenerate with
    `python tools/gen_known_gaps.py --write` after editing the YAML."""
    assert _gaps_section().rstrip("\n") == render(GAPS), (
        "docs/architecture.md 'Known gaps' drifted from "
        "docs/known_gaps.yaml; rerun tools/gen_known_gaps.py --write"
    )


def test_every_open_gap_is_guarded_and_current():
    open_ids = [g["id"] for g in GAPS if g["status"] == "open"]
    assert open_ids, "no open gaps tracked — suspicious for this repo"
    unguarded = [i for i in open_ids if i not in DETECTORS]
    assert not unguarded, (
        f"open gaps {unguarded} have no staleness detector in "
        "test_docs_gaps.py; add one so the claim can't rot"
    )
    stale = [i for i in open_ids if DETECTORS[i]()]
    assert not stale, (
        f"gaps {stale} claim features that now exist — flip them to "
        "closed in docs/known_gaps.yaml"
    )


def test_closed_gaps_name_existing_tests():
    missing = [
        g["id"]
        for g in GAPS
        if g["status"] == "closed" and not _closer_exists(g["closer"])
    ]
    assert not missing, (
        f"closed gaps {missing} name closer tests that do not exist — "
        "the evidence that closed them has rotted"
    )


def test_open_gaps_closers_not_already_passing():
    """An open gap whose named closer test exists and PASSES is a rotted
    claim: the feature landed but the checklist wasn't flipped."""
    landed = [g for g in GAPS if g["status"] == "open" and _closer_exists(g["closer"])]
    for g in landed:
        proc = subprocess.run(
            [sys.executable, "-m", "pytest", g["closer"], "-q", "--no-header"],
            cwd=REPO,
            capture_output=True,
            text=True,
            timeout=600,
        )
        # Exit 0 alone is not "passes": a slow-marked closer is SKIPPED
        # by the nested run (conftest policy) and pytest still exits 0 —
        # only an actual "N passed" report proves the claim rotted.
        passed = proc.returncode == 0 and re.search(
            r"\b[1-9]\d* passed", proc.stdout
        )
        assert not passed, (
            f"open gap {g['id']}: closer {g['closer']} exists and passes — "
            "flip it to closed in docs/known_gaps.yaml"
        )


def test_obsolete_claims_stay_gone():
    """The two claims that rotted must not reappear."""
    section = _gaps_section().lower()
    assert not re.search(
        r"executor (wire|lease/heartbeat payloads).{0,60}json", section
    ), (
        "the executor wire has a protobuf schema (ProtoExecutorClient); "
        "a JSON-only executor-wire claim is stale"
    )
    assert "~330" not in section, "the stale UI line count is back"


def test_gap_markers_match_prose():
    """Every bullet in the gaps list carries a marker (no unmarked,
    therefore unguarded, claims sneak in)."""
    section = _gaps_section()
    bullets = [line for line in section.splitlines() if line.startswith("- ")]
    assert bullets, "Known gaps section lost its bullets"
    unmarked = [
        b
        for b in bullets
        if "<!-- gap:" not in b and "<!-- closed-gap:" not in b
    ]
    assert not unmarked, f"gap bullets without markers: {unmarked}"


def test_metrics_inventory_matches_registry():
    """docs/metrics.md is GENERATED from SchedulerMetrics
    (tools/gen_metrics_doc.py): every registered family must appear in
    the doc with its registered type/labels/help, and no documented
    family may outlive its registration — same anti-rot contract as the
    known-gaps section."""
    from gen_metrics_doc import DOC_PATH, render

    assert os.path.exists(DOC_PATH), (
        "docs/metrics.md missing; run tools/gen_metrics_doc.py --write"
    )
    with open(DOC_PATH) as f:
        current = f.read()
    assert current == render(), (
        "docs/metrics.md is stale vs services/metrics.SchedulerMetrics; "
        "run tools/gen_metrics_doc.py --write"
    )
