"""Pallas solve-kernel parity: interpret-mode kernels vs the lax path.

The armada_tpu/ops/pallas_kernels.py contract asserted on CPU:

- `fill_take` (radix-threshold top-B selection) is index-for-index equal
  to the stable single-key `jnp.lexsort` it replaces, masked sentinel
  tail included.
- `winner_reduce` (tree winner exchange) equals the host lexicographic
  argmin, first-index tie-break included.
- Full mixed-fleet rounds solve bit-exactly on every kernel path, under
  LOCAL, the hot-window driver, and the 2x4 two-level HierarchicalDist —
  and the hierarchical pallas run books its fabric cost model
  (pallas call/block/VMEM counts, winner-exchange steps + DMA bytes)
  into CollectiveStats so the ICI ring's cost is asserted where the
  hardware isn't.

Every pallas kernel here runs under interpret=True (no TPU attached in
tier-1); tests/test_tpu_compile.py compiles them for a described v5e and
chip_smoke.py runs them natively (docs/known_gaps.yaml: pallas-ici-native).
"""

import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from armada_tpu.ops import pallas_kernels as pk

N_NODES, N_JOBS = 48, 192

DECISION_KEYS = (
    "assigned_node", "scheduled_priority", "scheduled_mask",
    "preempted_mask", "fair_share", "demand_capped_fair_share",
    "uncapped_fair_share", "num_loops", "spot_price",
)


def _decisions(out):
    return {
        k: np.asarray(v) for k, v in out.items()
        if k not in ("profile", "truncated")
    }


def _assert_equal(name, got, want):
    for k, v in want.items():
        assert np.array_equal(np.asarray(got[k]), v, equal_nan=True), (
            f"{name}: {k} diverged"
        )


@pytest.fixture(scope="module")
def fleet():
    """(name, padded DeviceRound, lax decisions) per mixed-fleet round —
    the lax baseline is solved once and shared by every parity case."""
    from armada_tpu.parallel.scenarios import mixed_fleet_rounds
    from armada_tpu.solver.kernel import solve_round
    from armada_tpu.solver.kernel_prep import (
        pad_device_round,
        prep_device_round,
    )

    rounds = []
    for name, snap in mixed_fleet_rounds(N_NODES, N_JOBS):
        dev = pad_device_round(prep_device_round(snap))
        assert dev.kernel_path == "lax"
        rounds.append((name, dev, _decisions(solve_round(dev))))
    return rounds


# ---------------------------------------------------------------------------
# Primitive parity
# ---------------------------------------------------------------------------


def test_fill_take_matches_lexsort():
    """Radix-threshold selection == stable lexsort top-B, including the
    masked-sentinel tail when fewer than B candidates are valid."""
    rng = np.random.default_rng(3)
    for n, want, span in ((512, 64, 2**40), (1024, 256, 2**20), (64, 64, 8)):
        keys = rng.integers(0, span, size=n, dtype=np.int64)
        # Mask a random suffix-weighted subset to the int64 sentinel the
        # fill path uses for infeasible slots (duplicates included: span
        # 8 forces heavy key collisions through the stable-order path).
        dead = rng.random(n) < 0.4
        keys = np.where(dead, pk._I64_SENTINEL, keys)
        jk = jnp.asarray(keys)
        take, taken = pk.fill_take(jk, want, nbits=63)
        ref = jnp.lexsort((jk,))[:want]
        np.testing.assert_array_equal(np.asarray(take), np.asarray(ref))
        np.testing.assert_array_equal(
            np.asarray(taken), np.asarray(keys)[np.asarray(ref)]
        )


def test_winner_reduce_matches_host_argmin():
    """Tree winner exchange == host lexicographic argmin. The production
    contract makes the minimum unique: the LAST key is the globally
    unique node rank, so the reduction's association order can never
    matter — mirrored here with duplicate-heavy leading keys and a
    permutation as the final key."""
    rng = np.random.default_rng(5)
    for p, span in ((8, 1000), (16, 3), (5, 2), (1, 10)):
        keys = [jnp.asarray(rng.integers(0, span, size=p, dtype=np.int32))
                for _ in range(2)]
        keys.append(jnp.asarray(rng.permutation(p).astype(np.int32)))
        found = jnp.asarray(rng.random(p) < 0.6)
        gids = jnp.arange(p, dtype=jnp.int32) + 7
        wgid, wfound = pk.winner_reduce(keys, found, gids)
        rows = np.stack([np.asarray(k) for k in keys], axis=1)
        alive = np.flatnonzero(np.asarray(found))
        if alive.size == 0:
            assert not bool(wfound)
            continue
        # np.lexsort treats the LAST tuple entry as primary.
        order = np.lexsort(tuple(rows[alive].T[::-1]))
        assert bool(wfound)
        assert int(wgid) == int(np.asarray(gids)[alive[order[0]]])


def test_winner_reduce_none_found():
    keys = [jnp.zeros(4, jnp.int32)]
    wgid, wfound = pk.winner_reduce(
        keys, jnp.zeros(4, bool), jnp.arange(4, dtype=jnp.int32)
    )
    assert not bool(wfound)


# ---------------------------------------------------------------------------
# Path selection plumbing
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kinds", [("i64",), ("f64", "i32"), ("u64", "f32", "bool"), ("f64", "f64", "i64")])
def test_lexsort_32bit_words_match_jnp_lexsort(kinds):
    """ops/select.lexsort splits 64-bit keys into 32-bit words (XLA:TPU
    compiles 64-bit multi-key sorts for minutes); it must order exactly
    as jnp.lexsort, including NaN, -0.0, infinities and ties."""
    from armada_tpu.ops.select import lexsort

    rng = np.random.default_rng(len(kinds))
    n = 500
    special = [-np.inf, np.inf, np.nan, -0.0, 0.0, 1 / 3, 1 / 3 + 2**-52, -2.5]
    make = {
        "i64": lambda: rng.integers(-3, 3, n) * 2**40 + rng.integers(-2, 2, n),
        "u64": lambda: rng.integers(0, 2**63, n, dtype=np.uint64) | (
            rng.integers(0, 2, n, dtype=np.uint64) << np.uint64(63)
        ),
        "i32": lambda: rng.integers(-5, 5, n).astype(np.int32),
        "f32": lambda: rng.choice(special, n).astype(np.float32),
        "f64": lambda: np.where(
            rng.random(n) < 0.5,
            rng.choice(special, n),
            rng.random(n) * rng.choice([1e-20, 1.0, 1e20], n),
        ),
        "bool": lambda: rng.integers(0, 2, n).astype(bool),
    }
    keys = [jnp.asarray(make[k]()) for k in kinds]
    want = np.asarray(jnp.lexsort(tuple(reversed(keys))))
    np.testing.assert_array_equal(np.asarray(lexsort(keys)), want)


def test_resolve_kernel_path(monkeypatch):
    monkeypatch.delenv(pk.PATH_ENV, raising=False)
    assert pk.resolve_kernel_path("blocked") == "blocked"
    # A path that cannot run raises; nothing switches to another path.
    with pytest.raises(ValueError, match="tpuv9"):
        pk.resolve_kernel_path("tpuv9")
    # native needs a TPU (tier-1 runs on the CPU).
    with pytest.raises(RuntimeError, match="needs a TPU"):
        pk.resolve_kernel_path("native")
    # Env is the A/B lever and beats config.
    monkeypatch.setenv(pk.PATH_ENV, "pallas")
    assert pk.resolve_kernel_path("lax") == "pallas"
    monkeypatch.setenv(pk.PATH_ENV, "bogus")
    with pytest.raises(ValueError, match="bogus"):
        pk.resolve_kernel_path("blocked")


def test_config_rejects_unknown_kernel_path():
    from armada_tpu.core.config import SchedulingConfig, validate_config

    with pytest.raises(ValueError, match="solveKernelPath"):
        validate_config(SchedulingConfig(solve_kernel_path="fused9000"))


def test_failover_ladder_gets_kernel_rung():
    """A configured non-lax path is its own rung above plain LOCAL, so a
    poisoned pallas executable demotes to the lax graph like any other
    rung failure; a lax config keeps the historical ladder."""
    from armada_tpu.core.config import SchedulingConfig
    from armada_tpu.solver.failover import build_ladder

    labels = [r.label for r in build_ladder(
        "kernel", None, SchedulingConfig(solve_kernel_path="pallas")
    )]
    assert labels == ["local:pallas", "LOCAL", "hotwindow:64", "oracle"]
    labels = [r.label for r in build_ladder(
        "kernel", None, SchedulingConfig()
    )]
    assert labels == ["LOCAL", "hotwindow:64", "oracle"]


def test_trace_codec_defaults_kernel_path():
    """Pre-pallas .atrace bundles decode with kernel_path='lax' (every
    recorded round ran the lax graph)."""
    from armada_tpu.trace.codec import (
        decode_device_round,
        encode_device_round,
    )
    from armada_tpu.parallel.scenarios import home_away_round
    from armada_tpu.solver.kernel_prep import (
        pad_device_round,
        prep_device_round,
    )

    dev = pad_device_round(prep_device_round(home_away_round(16, 32)))
    doc = encode_device_round(dev)
    doc.pop("kernel_path")
    assert decode_device_round(doc).kernel_path == "lax"


# ---------------------------------------------------------------------------
# Round parity: LOCAL / hotwindow / hierarchical mesh
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("path", ["blocked", "pallas"])
def test_local_round_parity(fleet, path):
    """Every mixed-fleet round solves bit-exactly on the blocked and
    pallas-interpret paths under the LOCAL single-device driver."""
    from armada_tpu.solver.kernel import solve_round

    for name, dev, want in fleet:
        got = solve_round(dataclasses.replace(dev, kernel_path=path))
        _assert_equal(f"{name}/{path}", _decisions(got), want)


def test_hotwindow_round_parity(fleet):
    """The hot-window compacted driver takes the same kernel-path seam:
    pallas-interpret under a forced small window == the lax path under
    the same window."""
    from armada_tpu.solver.kernel import solve_round

    name, dev, _ = fleet[0]
    want = _decisions(solve_round(dev, window=4, window_min_slots=0))
    got = solve_round(
        dataclasses.replace(dev, kernel_path="pallas"),
        window=4, window_min_slots=0,
    )
    _assert_equal(f"{name}/hotwindow:4", _decisions(got), want)


def test_hierarchical_2x4_parity_and_fabric_stats(fleet):
    """The 2x4 two-level HierarchicalDist with the pallas winner
    exchange solves bit-exactly vs the single-device lax baseline, and
    the run books the fabric cost model: pallas call/block/VMEM counts
    and the winner exchange's step count + DMA bytes, alongside the
    existing per-level ici/dcn gather accounting."""
    from armada_tpu.parallel.mesh import pad_nodes
    from armada_tpu.parallel.multihost import resolve_solver

    run = resolve_solver("2x4", kernel_path="pallas")
    per_round = {}
    for name, dev, want in fleet:
        got = run(pad_nodes(
            dataclasses.replace(dev, kernel_path="pallas"), run.n_shards
        ))
        _assert_equal(f"{name}/2x4:pallas", _decisions(got), want)
        # last_stats describes the program THIS round executed (market
        # compiles a different program than home_away).
        per_round[name] = (run.last_stats or run.stats).as_dict()
    for name, stats in per_round.items():
        assert stats["selects"] > 0, name
        # Winner exchange: log2(pow2(hosts)) tree steps per select, each
        # moving (1 + n_keys + 1) int32 lanes of row payload.
        assert stats["pallas_calls"] > 0, name
        assert stats["ring_steps"] > 0, name
        assert stats["ring_bytes"] > 0, name
        # The pallas winner exchange replaces the host-level
        # all_gather+argmin sites; chip-level ICI gathers still book.
        assert stats["ici_bytes"] > 0, name
    # Fused scoring blocks ran as pallas calls with VMEM-resident blocks
    # wherever the round fills (market rounds run with batch_window=0 and
    # never enter the fill loop, so only home_away books score blocks).
    stats = per_round["home_away"]
    assert stats["pallas_blocks"] > 0
    assert stats["pallas_vmem_bytes"] > 0


# ---------------------------------------------------------------------------
# Round readback trim
# ---------------------------------------------------------------------------


def test_readback_trim_bit_exact(fleet):
    """solve_round(readback_rows=J) downloads only the decision prefix
    but re-expands to the padded shape with the exact pad fills, so
    every consumer sees bit-identical arrays to the full readback."""
    from armada_tpu.solver.kernel import solve_round

    name, dev, want = fleet[0]
    rows = int(np.flatnonzero(
        np.asarray(dev.job_queue) >= 0
    ).size) or dev.job_queue.shape[0]
    got = solve_round(dev, readback_rows=min(rows, 7))
    out = _decisions(got)
    for k in want:
        assert out[k].shape == want[k].shape, k
    _assert_equal(f"{name}/readback", out, want)
