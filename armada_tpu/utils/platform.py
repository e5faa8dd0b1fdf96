"""JAX set-up shared by every entry point.

`setup_jax` turns on the exact (x64) solver configuration, the
persistent compile cache and the process's GC pause counter, then takes
the platform JAX is configured
for (`JAX_PLATFORMS`). It never switches platforms: a process asked
for a TPU that finds none fails in JAX's own start-up, and a caller
that needs a specific platform passes `require=` to fail loudly too.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def xla_target_signature() -> str:
    """The jaxlib revision plus any xla_cpu codegen flags: what decides
    the code XLA emits for a recorded round (trace/recorder.py)."""
    import jaxlib

    flags = sorted(
        t
        for t in os.environ.get("XLA_FLAGS", "").split()
        if t.startswith("--xla_cpu")
    )
    return " ".join([f"jaxlib-{jaxlib.__version__}"] + flags)


def host_cpu_signature() -> str:
    """Stable hash of the host's CPU ISA features plus
    `xla_target_signature`: the flight recorder stamps it on a bundle so
    a replay on another host/toolchain is flagged (trace/replayer.py)."""
    import hashlib
    import platform as _platform

    parts = [_platform.machine(), xla_target_signature()]
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    parts.append(" ".join(sorted(line.split(":", 1)[1].split())))
                    break
    except OSError:
        parts.append(_platform.processor())
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]


def compile_cache_dir() -> str:
    """`JAX_COMPILATION_CACHE_DIR` when set, else `<repo>/.jax_cache`.
    The path is part of the cache key, so it is fixed: a directory that
    moves between runs never hits."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _REPO_ROOT, ".jax_cache"
    )


def enable_persistent_compile_cache():
    """Cache compiled executables on disk (the solver compiles for
    minutes per padded shape on TPU) and install the compile/retrace
    telemetry listeners (observe/xla.py). Where
    `JAX_COMPILATION_CACHE_DIR` is set JAX already reads it, and no
    other directory is set here. Idempotent."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    from ..observe.xla import install_compile_telemetry

    install_compile_telemetry()


def enable_exact_costs():
    """Enable x64 — the production solver configuration.

    Every large DeviceRound tensor is explicitly int32/uint32, so x64 only
    widens the Q-sized cost vectors (DRF costs, fair shares, budgets) to
    float64. In exchange the cost keys match the float64 oracle
    bit-for-bit: the whole x64 parity suite is the proof. Opt out with
    ARMADA_TPU_X64=0 (float32 costs; placement parity then becomes
    approximate — quantified by tools/float32_parity.py and docs/parity.md)."""
    if os.environ.get("ARMADA_TPU_X64", "1") == "0":
        return
    import jax

    jax.config.update("jax_enable_x64", True)


def setup_jax(require: str | None = None) -> str:
    """Exact costs + compile cache + the garbage collector's pause
    counter (utils/tracing.GC_PAUSES); returns the platform JAX runs on.
    With `require`, raises unless that is the platform."""
    from .tracing import GC_PAUSES

    GC_PAUSES.install()
    enable_exact_costs()
    enable_persistent_compile_cache()
    import jax

    platform = jax.default_backend()
    if require is not None and platform != require:
        raise RuntimeError(
            f"{require} requested but JAX runs on {platform!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r})"
        )
    return platform
