"""Semiring-generic propagation: algebra specs, backend parity, fallbacks.

The acceptance contract of the (⊕, ⊗) redesign:

- every registered semiring pushes identically on the pallas (interpret)
  and segment backends, including non-float dtypes and custom tile
  geometry;
- the unsorted ``push_coo`` fallback (the sharded dry-run's cost model) is
  pinned to the sorted ``push`` primitive across weight/mask combinations
  so the two cost models cannot drift;
- the new segment-min/max fallbacks match a pure-numpy reference on
  property-sampled random graphs;
- mis-matched layouts/semirings fail loudly at trace time.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro
from repro.core import backend as B
from repro.core.semiring import (MIN_MIN, MIN_PLUS, PLUS_TIMES, Semiring,
                                 available_semirings, resolve_semiring)
from repro.graph import from_edges
from repro.graph.csr import gather_push, sort_by_dst
from repro.graph.generators import gnm_edges
from repro.kernels.spmv.kernel import CHUNK

TOL = dict(rtol=1e-5, atol=1e-6)


def _graph(n=300, m=2000, seed=0, n_cap=None):
    src, dst = gnm_edges(n, m, seed=seed)
    return from_edges(src, dst, n_cap or n, m + 64)


def _values(s: Semiring, n: int, seed: int):
    rng = np.random.default_rng(seed)
    if np.issubdtype(s.np_dtype, np.floating):
        v = rng.random(n).astype(s.np_dtype)
        if s.name == "min_plus":  # distances: a few sources, rest +inf
            v = np.where(rng.random(n) < 0.1, v, np.inf).astype(s.np_dtype)
        return jnp.asarray(v)
    return jnp.asarray(rng.integers(0, n, n).astype(s.np_dtype))


def _numpy_push(s: Semiring, src, dst, w, values, n, mask=None):
    """Reference ⊕/⊗ over an explicit edge list."""
    out = np.full(n, s.zero, s.np_dtype)
    combine = {"times": lambda a, b: a * b,
               "plus": lambda a, b: a + b,
               "min": np.minimum}[s.mul]
    reduce_ = {"sum": lambda a, b: a + b, "min": np.minimum,
               "max": np.maximum}[s.add]
    for i, (a, b) in enumerate(zip(src, dst)):
        if mask is not None and not mask[i]:
            continue
        out[b] = reduce_(out[b], combine(values[a], w[i]))
    return out


# ----------------------------------------------------------- the algebra
def test_semiring_identities_and_registry():
    assert {"plus_times", "min_plus", "min_min",
            "max_times"} <= set(available_semirings())
    pt = resolve_semiring("plus_times")
    assert pt is PLUS_TIMES and pt.zero == 0.0 and pt.one == 1.0
    mp = resolve_semiring("min_plus")
    assert mp.zero == np.inf and mp.one == 0.0
    mm = resolve_semiring("min_min")
    assert mm.np_dtype == np.int32
    assert mm.zero == np.iinfo(np.int32).max  # int "+inf"
    assert mm.one == np.iinfo(np.int32).max   # ⊗=min's identity
    mt = resolve_semiring("max_times")
    assert mt.zero == -np.inf and mt.one == 1.0
    # instances resolve to themselves; None means plus_times
    assert resolve_semiring(mp) is mp
    assert resolve_semiring(None) is PLUS_TIMES
    with pytest.raises(KeyError):
        resolve_semiring("tropical-nonsense")
    with pytest.raises(ValueError):
        Semiring("bogus", "avg", "times")
    with pytest.raises(ValueError):
        Semiring("bogus", "sum", "divide")


@pytest.mark.parametrize("name", ["plus_times", "min_plus", "min_min",
                                  "max_times"])
def test_combine_matches_identity_laws(name):
    s = resolve_semiring(name)
    v = _values(s, 64, seed=3)
    one = jnp.full((64,), s.one, s.np_dtype)
    np.testing.assert_array_equal(np.asarray(s.combine(v, one)),
                                  np.asarray(v))


# --------------------------------------------------- backend parity: push
def _kronecker_edges(scale, m, seed, a=0.57, b=0.19, c=0.19):
    """Graph500's Kronecker draw at a test size: one quadrant per bit."""
    u = np.random.default_rng(seed).random((scale, m))
    bits = np.arange(scale)[:, None]
    src = ((u >= a + b).astype(np.int64) << bits).sum(0)
    dst = ((((u >= a) & (u < a + b)) | (u >= a + b + c)).astype(np.int64)
           << bits).sum(0)
    return src.astype(np.int32), dst.astype(np.int32)


def _shaped_graph(shape, n=257):
    """A graph over ``n`` vertices (a non-multiple of the tile width):

    - ``uniform``: G(n, m) edges;
    - ``hub``: uniform edges plus one vertex whose in-degree spans
      several kernel chunks;
    - ``kronecker``: the skewed degrees of graph500's generator;
    - ``sparse``: few edges into a quarter of the vertices, so most
      destination rows are empty.
    """
    rng = np.random.default_rng(1)
    if shape == "uniform":
        src, dst = gnm_edges(n, 1200, seed=1)
    elif shape == "hub":
        src, dst = gnm_edges(n, 600, seed=1)
        fan_in = rng.integers(0, n, 4 * CHUNK).astype(np.int32)
        src = np.concatenate([src, fan_in])
        dst = np.concatenate([dst, np.full(fan_in.size, 100, np.int32)])
    elif shape == "kronecker":
        src, dst = _kronecker_edges(8, 2048, seed=1)
    else:
        src = rng.integers(0, n, 80).astype(np.int32)
        dst = (4 * rng.integers(0, n // 4, 80)).astype(np.int32)
    return from_edges(src, dst, n, len(src) + 64)


@pytest.mark.parametrize("shape", ["uniform", "hub", "kronecker", "sparse"])
@pytest.mark.parametrize("name,weight", [
    ("plus_times", "inv_out"), ("plus_times", "unit"),
    ("min_plus", "length"), ("min_plus", "unit"),
    ("min_min", "unit"), ("max_times", "unit"),
])
def test_push_backend_parity_per_semiring(name, weight, shape):
    s = resolve_semiring(name)
    g = _shaped_graph(shape)
    layout = B.build_layout(g, weight=weight, semiring=name)
    v = _values(s, 257, seed=2)
    ref = B.push(v, layout, semiring=name, backend="segment_sum")
    out = B.push(v, layout, semiring=name, backend="pallas", interpret=True)
    if s.add == "sum":
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)
    else:  # min/max reduces are reassociation-exact
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # and against the edge-list oracle
    mask = np.asarray(g.edge_mask())
    w = np.full(mask.shape, s.one, s.np_dtype)
    if weight == "inv_out":
        from repro.graph.graph import inv_out_degree
        w = np.asarray(inv_out_degree(g))[np.asarray(g.src)]
    elif weight == "length":
        w = np.ones(mask.shape, s.np_dtype)
    oracle = _numpy_push(s, np.asarray(g.src), np.asarray(g.dst), w,
                         np.asarray(v), 257, mask=mask)
    np.testing.assert_allclose(np.asarray(ref), oracle, **TOL)


@pytest.mark.parametrize("name", ["min_plus", "min_min"])
def test_reduce_push_custom_tile_geometry(name):
    s = resolve_semiring(name)
    weight = "length" if name == "min_plus" else "unit"
    g = _graph(n=257, m=900, seed=2, n_cap=257)
    v = _values(s, 257, seed=5)
    for chunk in (256, 512):
        layout = B.build_layout(g, weight=weight, semiring=name, chunk=chunk)
        ref = B.push(v, layout, semiring=name, backend="segment_sum")
        for tile_n in (64, 128, 256):
            out = B.push(v, layout, semiring=name, backend="pallas",
                         tile_n=tile_n, chunk=chunk, interpret=True)
            np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("backend", ["segment_sum", "pallas"])
def test_reduce_push_empty_graph_gives_identity(backend):
    g = from_edges(np.zeros(0, np.int32), np.zeros(0, np.int32), 256, 64)
    layout = B.build_layout(g, weight="length", semiring="min_plus")
    out = B.push(jnp.zeros(256), layout, semiring="min_plus",
                 backend=backend, interpret=True)
    assert bool(jnp.all(jnp.isposinf(out)))  # ⊕-identity everywhere


def test_explicit_edge_lengths_flow_through_sort():
    """weight='length' with explicit per-slot lengths survives the dst sort."""
    g = _graph(n=64, m=400, seed=7, n_cap=64)
    rng = np.random.default_rng(8)
    lengths = jnp.asarray(rng.random(g.edge_capacity).astype(np.float32))
    layout = B.build_layout(g, weight="length", semiring="min_plus",
                            lengths=lengths)
    v = _values(MIN_PLUS, 64, seed=9)
    out = B.push(v, layout, semiring="min_plus", backend="segment_sum")
    mask = np.asarray(g.edge_mask())
    oracle = _numpy_push(MIN_PLUS, np.asarray(g.src), np.asarray(g.dst),
                         np.asarray(lengths), np.asarray(v), 64, mask=mask)
    np.testing.assert_array_equal(np.asarray(out), oracle)


# ------------------------------------- push_coo pinned to push (satellite)
@pytest.mark.parametrize("name,weight", [
    ("plus_times", "inv_out"), ("plus_times", "unit"),
    ("min_plus", "length"), ("min_min", "unit"), ("max_times", "unit"),
])
@pytest.mark.parametrize("masked", [False, True])
def test_push_coo_matches_push(name, weight, masked):
    """The unsorted fallback (sharded dry-run cost model) must agree with
    the sorted primitive for every weight/mask combination."""
    s = resolve_semiring(name)
    g = _graph(n=200, m=1500, seed=11, n_cap=200)
    layout = B.build_layout(g, weight=weight, semiring=name)
    v = _values(s, 200, seed=12)
    edge_mask = g.edge_mask()

    # the same per-edge operand in unsorted order
    if weight == "inv_out":
        from repro.graph.graph import inv_out_degree
        w_coo = inv_out_degree(g)[g.src]
    elif weight == "length":
        w_coo = jnp.ones((g.edge_capacity,), s.np_dtype)
    else:
        w_coo = jnp.full((g.edge_capacity,), s.one, s.np_dtype)

    if masked:
        # an E_B-style endpoint-defined mask, expressible in both orders
        hot = jnp.asarray(
            np.random.default_rng(13).random(200) < 0.5)
        coo_mask = edge_mask & (~hot[g.src]) & hot[g.dst]
        sorted_mask = (~hot[layout.src]) & hot[jnp.minimum(layout.dst, 199)]
    else:
        coo_mask = edge_mask
        sorted_mask = None

    ref = B.push(v, layout, semiring=name, mask=sorted_mask,
                 backend="segment_sum")
    out = B.push_coo(v, g.src, g.dst, 200, weight=w_coo, mask=coo_mask,
                     semiring=name)
    if s.add == "sum":
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)
    else:
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ------------------------------ property-based segment-min/max (satellite)
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), density=st.floats(0.002, 0.05),
       name=st.sampled_from(["min_plus", "min_min", "max_times"]))
def test_segment_reduce_fallback_property(seed, density, name):
    """gather_push's segment-min/max on sorted layouts == numpy loop."""
    s = resolve_semiring(name)
    rng = np.random.default_rng(seed)
    n = 128
    m = max(1, int(density * n * n))
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    g = from_edges(src, dst, n, m + 8)
    se = sort_by_dst(g)
    v = _values(s, n, seed=seed + 1)
    w = jnp.asarray(rng.random(se.src.shape[0]).astype(np.float32)) \
        if np.issubdtype(s.np_dtype, np.floating) else \
        jnp.asarray(rng.integers(0, n, se.src.shape[0]).astype(np.int32))
    out = gather_push(se, v, n, weight=w, semiring=s)
    oracle = _numpy_push(s, np.asarray(se.src), np.asarray(se.dst),
                         np.asarray(w), np.asarray(v), n,
                         mask=np.asarray(se.valid))
    np.testing.assert_allclose(np.asarray(out), oracle, **TOL)


# ----------------------------------------------------- trace-time guards
def test_custom_int_sum_semiring_parity_or_loud_failure():
    """A user-registered int32 sum semiring stays exact on the segment
    backend; the f32-matmul pallas path refuses instead of silently
    casting (dtype parity between backends, or a loud error)."""
    from repro.core.semiring import register_semiring
    s = register_semiring(Semiring("count_paths", "sum", "times", "int32"))
    g = _graph(n=64, m=300, seed=24, n_cap=64)
    layout = B.build_layout(g, weight="unit", semiring="count_paths")
    v = jnp.ones(64, jnp.int32)
    out = B.push(v, layout, semiring="count_paths", backend="segment_sum")
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(g.in_deg))  # unit counts = in-degree
    with pytest.raises(NotImplementedError, match="segment_sum"):
        B.push(v, layout, semiring="count_paths", backend="pallas",
               interpret=True)


def test_layout_semiring_mismatch_rejected():
    g = _graph(n=64, m=300, seed=20, n_cap=64)
    unit_mm = B.build_layout(g, weight="unit", semiring="min_min")
    with pytest.raises(ValueError, match="semiring"):
        B.push(jnp.ones(64), unit_mm)  # plus_times over a min_min layout
    with pytest.raises(ValueError, match="semiring"):
        B.push(jnp.ones(64), unit_mm, semiring="min_plus",
               backend="segment_sum")
    with pytest.raises(ValueError, match="inv_out"):
        B.build_layout(g, weight="inv_out", semiring="min_plus")
    with pytest.raises(ValueError, match="lengths"):
        B.build_layout(g, weight="unit", semiring="min_plus",
                       lengths=jnp.ones(g.edge_capacity))
    with pytest.raises(ValueError, match="weight mode"):
        B.build_layout(g, weight="distance", semiring="min_plus")
    with pytest.raises(ValueError, match="layout spec"):
        B.normalize_layout_spec(("unit",))


def test_build_summary_rejects_inv_out_on_min_semiring():
    from repro.core.pagerank import build_summary
    g = _graph(n=64, m=300, seed=21, n_cap=64)
    hot = jnp.ones(64, bool)
    with pytest.raises(ValueError, match="inv_out"):
        build_summary(g, jnp.ones(64), hot, hot_node_capacity=64,
                      hot_edge_capacity=512, semiring="min_plus")


def test_summary_layout_rejects_mismatched_semiring():
    """A plus_times consumer over +∞-baked min-semiring buffers would
    silently NaN — the summary records its algebra and the layout builder
    checks it at trace time."""
    from repro.core.pagerank import build_summary
    g = _graph(n=64, m=300, seed=23, n_cap=64)
    hot = jnp.ones(64, bool)
    s = build_summary(g, jnp.zeros(64), hot, hot_node_capacity=64,
                      hot_edge_capacity=512, weight="length",
                      semiring="min_plus")
    assert s.semiring == "min_plus" and s.weight_mode == "length"
    with pytest.raises(ValueError, match="baked for"):
        B.summary_layout(s)  # defaults to plus_times
    B.summary_layout(s, semiring="min_plus")  # matching algebra passes


# ------------------------------------------- session ingestion (satellite)
def test_add_edges_rejects_mismatched_shapes():
    src, dst = gnm_edges(50, 200, seed=22)
    with repro.session((src, dst), algorithm="pagerank") as s:
        with pytest.raises(ValueError, match="equal length"):
            s.add_edges([0, 1, 2], [3, 4])
        with pytest.raises(ValueError, match="1-D"):
            s.add_edges(np.zeros((2, 2), np.int32), np.zeros((2, 2), np.int32))
        with pytest.raises(ValueError, match="equal length"):
            s.remove_edges([0, 1], [2])
        # a valid call still goes through after the failed ones
        s.add_edges([0], [1])
        assert s.engine.pending_updates == 1
