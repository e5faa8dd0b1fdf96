"""Test configuration: JAX on a virtual 8-device CPU platform.

Tests run on the CPU (the driver sets JAX_PLATFORMS=cpu); sharding tests
use a virtual 8-device CPU mesh. Must run before any test module imports
jax-dependent code. The TPU compile rehearsals (tests/test_tpu_compile.py)
describe a v5e topology inside their own fixture.
"""

import os
import sys

os.environ.setdefault("REPO_ROOT", os.path.dirname(os.path.dirname(__file__)))
sys.path.insert(0, os.environ["REPO_ROOT"])

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# x64 gives float64 cost arithmetic and int64 aggregates: exact parity with
# the host oracle — the production configuration (utils/platform.setup_jax).
os.environ["JAX_ENABLE_X64"] = "1"

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

# Persistent compile cache: the solver kernel recompiles per padded shape,
# which dominates suite wall-clock. Caching executables on disk makes
# repeat runs load instead of compile; clearing the in-process caches at
# module boundaries bounds the live JITed-code footprint.
from armada_tpu.utils.platform import compile_cache_dir  # noqa: E402

jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

import pytest  # noqa: E402


def pytest_sessionstart(session):
    """Fail fast on orphaned bytecode: a `__pycache__/mod.*.pyc` whose
    `mod.py` source is gone (a deleted or renamed module, e.g. the
    remnants of a discarded front-door attempt) still satisfies imports
    on this interpreter and can silently shadow the real tree. Delete
    the stale .pyc instead of exempting it here."""
    root = os.environ["REPO_ROOT"]
    orphans = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if not d.startswith(".")]
        if os.path.basename(dirpath) != "__pycache__":
            continue
        src_dir = os.path.dirname(dirpath)
        for name in filenames:
            if not name.endswith(".pyc"):
                continue
            module = name.split(".", 1)[0]
            if not os.path.exists(os.path.join(src_dir, module + ".py")):
                orphans.append(os.path.relpath(
                    os.path.join(dirpath, name), root
                ))
    if orphans:
        raise pytest.UsageError(
            "orphaned __pycache__ bytecode without a matching .py source "
            "(can shadow imports; delete them): " + ", ".join(sorted(orphans))
        )


@pytest.fixture(autouse=True, scope="module")
def _bound_jit_cache():
    yield
    jax.clear_caches()


def pytest_collection_modifyitems(config, items):
    """Exhaustive parity sweeps (random-scenario fleets, the full
    fast-fill matrix) run only with ARMADA_FULL_SUITE=1: the default
    suite keeps one representative per mechanism and finishes in
    minutes, the full sweep stays one env var away."""
    if os.environ.get("ARMADA_FULL_SUITE") == "1":
        return
    skip = pytest.mark.skip(reason="slow sweep; set ARMADA_FULL_SUITE=1")
    for item in items:
        if item.get_closest_marker("slow") is not None:
            item.add_marker(skip)
