"""Compile-only rehearsals for a TPU v5e: no chip needed.

The TPU compiler is installed here and compiles for a described (not
attached) `v5e:2x2` topology, refusing what the chip would refuse — a
scoped-VMEM overflow, an op Mosaic cannot lower, a tiling it rejects.
Nothing runs, so these say nothing about results or times; the
interpret-mode parity suites and `chip_smoke.py` cover those.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and every xdist
worker imports this file. Keep every TPU-compile test in this one file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from armada_tpu.ops import pallas_kernels as pk


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 - no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A TPU executable written to the persistent cache cannot be read
        # back without a chip: keep these compiles out of it.
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """The kernels ask the process backend (the CPU here) whether to
    interpret; compile them as the chip would."""
    monkeypatch.setattr(pk, "native_available", lambda: True)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_scoring_kernel_compiles_at_65536_nodes(one_chip, on_tpu):
    """The flagship's padded node count (50k -> 65,536), 4 resources."""
    n, r, k = 65536, 4, 3
    s = lambda shape, dt: _spec(shape, dt, one_chip)  # noqa: E731
    args = (
        s((n, r), jnp.int32), s((n, r), jnp.int32),
        s((n, 1), jnp.uint32), s((n, 1), jnp.uint32),
        s((n,), jnp.int32), s((n,), jnp.int32),
        s((n,), jnp.int32), s((n,), jnp.int32),
        s((1,), jnp.uint32), s((1,), jnp.uint32), s((r,), jnp.int32),
        s((k,), jnp.int32), s((1,), jnp.int32),
        s((r,), jnp.int32), s((r,), jnp.int32),
    )
    bits = (1, 9, 11, 1, 17)
    compiled = jax.jit(
        lambda *a: pk._pallas_score(a, bits, 2048)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_winner_reduce_compiles(one_chip, on_tpu):
    h = 4
    keys = [_spec((h,), jnp.int32, one_chip)] * 3
    compiled = jax.jit(pk.winner_reduce).lower(
        keys, _spec((h,), jnp.bool_, one_chip), _spec((h,), jnp.int32, one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ring_winner_exchange_compiles_on_4_device_mesh(topo):
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("x",))

    def ring(rows):
        return jax.shard_map(
            lambda r: pk.ring_winner_exchange(r[0], "x", 4)[None],
            mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False,
        )(rows)

    rows = jax.ShapeDtypeStruct(
        (4, 5), jnp.int32, sharding=NamedSharding(mesh, P("x"))
    )
    compiled = jax.jit(ring).lower(rows).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_exact_solve_compiles(one_chip):
    """The x64 solve at 64 nodes x 256 jobs. Guards the scoped-VMEM
    refusal of a 64-bit cumsum in fair preemption (kernel.py)."""
    from __graft_entry__ import _example_snapshot
    from armada_tpu.solver.kernel import solve_impl
    from armada_tpu.solver.kernel_prep import pad_device_round, prep_device_round

    assert jax.config.jax_enable_x64
    dev = pad_device_round(prep_device_round(_example_snapshot(64, 256)))
    args = jax.tree_util.tree_map(
        lambda x: _spec(x.shape, x.dtype, one_chip)
        if isinstance(x, np.ndarray)
        else x,
        dev,
    )
    compiled = jax.jit(solve_impl).lower(args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0
