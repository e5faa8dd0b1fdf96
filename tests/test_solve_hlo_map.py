"""tools/solve_hlo_map.py reads a compiled module's text: each op's
opcode, the computation it calls, and the program's own source line
behind it, resolved through the module's stack-frame tables."""

import jax
import jax.numpy as jnp

from armada_tpu.solver.dist import LOCAL
from tools.solve_hlo_map import fused_kinds, parse_hlo


def test_parse_hlo_names_the_scatter_and_its_source():
    contrib = jnp.ones((64, 2), jnp.int32)
    nodes = jnp.arange(64, dtype=jnp.int32) % 8
    text = (
        jax.jit(LOCAL.segment_to_nodes, static_argnums=2)
        .lower(contrib, nodes, 8).compile().as_text()
    )
    hlo = parse_hlo(text)
    scatters = [
        (name, ins) for name, ins in hlo["instrs"].items()
        if "scatter" in fused_kinds(hlo, name)
    ]
    assert scatters
    assert any(
        "dist.py" in ins["source"] and "LocalDist.segment_to_nodes" in ins["source"]
        for _, ins in scatters
    ), scatters
