"""The SpMV Pallas kernel vs a ``segment_sum`` oracle (interpret mode).

The container is CPU-only; ``interpret=True`` executes each kernel body in
Python with the same BlockSpec tiling the TPU backend would use, so tiling /
masking / accumulation logic is what is being validated.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.graph import from_edges
from repro.graph.generators import gnm_edges
from repro.graph.graph import inv_out_degree
from repro.kernels.spmv.ops import pagerank_push


# ------------------------------------------------------------------ spmv
@pytest.mark.parametrize("n,m,seed", [(300, 2000, 0), (1024, 6000, 1),
                                      (257, 900, 2)])
def test_spmv_matches_segment_sum(n, m, seed):
    src, dst = gnm_edges(n, m, seed=seed)
    n_cap = ((n + 255) // 256) * 256
    g = from_edges(src, dst, n_cap, m + 64)
    ranks = jnp.asarray(
        np.random.default_rng(seed).random(n_cap).astype(np.float32))
    out = pagerank_push(g, ranks, interpret=True)
    emit = ranks * inv_out_degree(g)
    contrib = jnp.where(g.edge_mask(), emit[g.src], 0.0)
    ref = jax.ops.segment_sum(contrib, g.dst, num_segments=n_cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_spmv_empty_graph():
    g = from_edges(np.zeros(0, np.int32), np.zeros(0, np.int32), 256, 64)
    out = pagerank_push(g, jnp.ones(256), interpret=True)
    assert float(jnp.abs(out).max()) == 0.0


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 1000), density=st.floats(0.001, 0.05))
def test_spmv_property_random_graphs(seed, density):
    rng = np.random.default_rng(seed)
    n = 256
    m = max(1, int(density * n * n))
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    g = from_edges(src, dst, n, m + 8)
    ranks = jnp.asarray(rng.random(n).astype(np.float32))
    out = pagerank_push(g, ranks, interpret=True)
    emit = ranks * inv_out_degree(g)
    contrib = jnp.where(g.edge_mask(), emit[g.src], 0.0)
    ref = jax.ops.segment_sum(contrib, g.dst, num_segments=n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)

