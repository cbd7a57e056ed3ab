"""Multi-device parity suite for the sharded propagation backend.

Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
sharded job does) these tests drive the real ``shard_map`` path over an
8-device mesh; on a single device the same tests still run — the mesh
shrinks to the available devices and the mesh-less shard-loop reference
path keeps 8-way partitioning covered regardless.

The contract:

- sharded ``push`` == single-layout ``push`` for every registered semiring
  × weight mode — **bitwise** for the min-reduce semirings (min/pmin is
  reassociation-exact), to f32 summation order for sum/max-of-products;
- sharded ``build_summary`` (the distributed bucket sort) == the
  replicated construction: same counters, identical E_K edge multiset,
  same ``b_in`` boundary and the same summarized-sweep answers (bitwise
  for min semirings);
- sharded ``fused_query_step`` == the unsharded engine answer for every
  registered algorithm (bitwise for the min-semiring workloads at full
  hot-set coverage);
- a mesh-configured engine under a forced-imbalance stream *rebalances*
  (recuts its slot partition) and keeps answering identically to the
  single-device engine;
- the sharded plugin path traces **zero** unsorted ``push_coo`` calls.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import repro
from repro.core import backend as B
from repro.core.algorithm import available_algorithms, make_algorithm
from repro.core.fused import fused_query_step
from repro.core.semiring import resolve_semiring
from repro.graph import from_edges
from repro.graph.generators import gnm_edges
from repro.graph.partition import build_sharded_layout

TOL = dict(rtol=1e-5, atol=1e-6)

#: every registered semiring × a weight mode it supports
SEMIRING_WEIGHTS = [
    ("plus_times", "inv_out"),
    ("plus_times", "unit"),
    ("min_plus", "length"),
    ("min_min", "unit"),
    ("max_times", "unit"),
]
#: reduces for which sharding must be bitwise (reassociation-exact ⊕)
BITWISE_ADDS = ("min",)


def _mesh(max_devices: int = 8) -> Mesh:
    """A 1-D mesh over up to ``max_devices`` of the available devices."""
    n = min(jax.device_count(), max_devices)
    return Mesh(np.asarray(jax.devices()[:n]), ("shards",))


def _graph(n=300, m=2000, seed=0, n_cap=None):
    src, dst = gnm_edges(n, m, seed=seed)
    return from_edges(src, dst, n_cap or n, m + 64)


def _values(semiring, n, seed=0):
    s = resolve_semiring(semiring)
    rng = np.random.default_rng(seed)
    if np.issubdtype(s.np_dtype, np.floating):
        return jnp.asarray(rng.random(n).astype(s.np_dtype))
    return jnp.asarray(rng.integers(0, n, n).astype(s.np_dtype))


def _assert_matches(out, ref, semiring):
    s = resolve_semiring(semiring)
    assert out.dtype == ref.dtype
    if s.add in BITWISE_ADDS:
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    else:
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def test_suite_sees_forced_host_devices():
    """Under the sharded CI job (8 forced host devices) the mesh really
    spans 8 devices; elsewhere this documents what the run covered."""
    mesh = _mesh()
    assert mesh.devices.size == min(jax.device_count(), 8)
    if jax.device_count() >= 8:
        assert mesh.devices.size == 8


# ------------------------------------------------------------- push parity
@pytest.mark.parametrize("semiring,weight", SEMIRING_WEIGHTS)
@pytest.mark.parametrize("backend", ["segment_sum", "pallas"])
def test_sharded_push_matches_single_device(semiring, weight, backend):
    g = _graph()
    values = _values(semiring, g.node_capacity)
    ref = B.push(values, B.build_layout(g, weight=weight, semiring=semiring),
                 semiring=semiring, backend="segment_sum")
    mesh = _mesh()
    sharded = build_sharded_layout(g, mesh=mesh, weight=weight,
                                   semiring=semiring)
    out = B.push(values, sharded, semiring=semiring, backend=backend,
                 interpret=True)
    _assert_matches(out, ref, semiring)


@pytest.mark.parametrize("semiring,weight", SEMIRING_WEIGHTS)
def test_shard_loop_path_matches_single_device(semiring, weight):
    """mesh=None: the on-device shard loop is the reference semantics and
    keeps 8-way partitioning covered even on one device."""
    g = _graph(seed=3)
    values = _values(semiring, g.node_capacity, seed=4)
    ref = B.push(values, B.build_layout(g, weight=weight, semiring=semiring),
                 semiring=semiring, backend="segment_sum")
    sharded = build_sharded_layout(g, num_shards=8, weight=weight,
                                   semiring=semiring)
    out = B.push(values, sharded, semiring=semiring, backend="segment_sum")
    _assert_matches(out, ref, semiring)


def test_sharded_push_with_explicit_lengths_and_mask():
    """Per-edge lengths bake into the shards; masks filter the sharded
    sorted stream (the b_in boundary selection shape)."""
    g = _graph(n=200, m=1200, seed=5, n_cap=200)
    lengths = jnp.asarray(
        np.random.default_rng(6).uniform(0.5, 2.0, g.edge_capacity),
        jnp.float32)
    dist = _values("min_plus", 200, seed=7)
    single = B.build_layout(g, weight="length", semiring="min_plus",
                            lengths=lengths)
    sharded = build_sharded_layout(g, mesh=_mesh(), weight="length",
                                   semiring="min_plus", lengths=lengths)
    ref = B.push(dist, single, semiring="min_plus", backend="segment_sum")
    out = B.push(dist, sharded, semiring="min_plus", backend="segment_sum")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # masked: keep only edges into even receivers, in each stream's order
    m_single = (single.dst % 2) == 0
    m_sharded = (sharded.dst % 2) == 0
    ref_m = B.push(dist, single, semiring="min_plus", mask=m_single,
                   backend="segment_sum")
    out_m = B.push(dist, sharded, semiring="min_plus", mask=m_sharded,
                   backend="segment_sum")
    np.testing.assert_array_equal(np.asarray(out_m), np.asarray(ref_m))


def test_sharded_push_trace_time_guards():
    g = _graph(n=64, m=300, seed=8, n_cap=64)
    sharded = build_sharded_layout(g, num_shards=4, weight="unit",
                                   semiring="min_min")
    with pytest.raises(ValueError, match="sharded layout built for"):
        B.push(jnp.ones(64), sharded, semiring="plus_times")
    with pytest.raises(ValueError, match="mask must cover"):
        B.push(jnp.zeros(64, jnp.int32), sharded, semiring="min_min",
               mask=jnp.ones(64, bool), backend="segment_sum")
    with pytest.raises(ValueError, match="not in mesh"):
        build_sharded_layout(g, mesh=_mesh(), axes=("bogus",))
    with pytest.raises(ValueError, match="mesh= or num_shards="):
        build_sharded_layout(g)
    if jax.device_count() >= 2:  # with 1 device every shard count divides
        with pytest.raises(ValueError, match="multiple"):
            build_sharded_layout(g, mesh=_mesh(2), num_shards=3)


# -------------------------------------------- sharded build_summary parity
def _ek_triples(summary):
    """The valid (src, dst, w) E_K triples of a summary, order-normalized —
    flat and sharded buffers store the same multiset in different shapes."""
    k_cap = summary.hot_ids.shape[0]
    src = np.asarray(summary.ek_src)
    dst = np.asarray(summary.ek_dst)
    w = np.asarray(summary.ek_w)
    if src.ndim == 1:
        valid = np.arange(src.shape[0]) < int(summary.num_ek)
    else:
        valid = dst < k_cap
    t = np.stack([src[valid].astype(np.float64),
                  dst[valid].astype(np.float64),
                  w[valid].astype(np.float64)])
    return t[:, np.lexsort(t)]


#: (algorithm summary spec) -> the build_summary kwargs it exercises
SUMMARY_SPECS = [
    ("inv_out", False, "plus_times"),   # PageRank
    ("unit", False, "plus_times"),      # HITS fwd / Katz
    ("unit", True, "plus_times"),       # HITS rev
    ("unit", False, "min_min"),         # CC fwd
    ("unit", True, "min_min"),          # CC rev
    ("length", False, "min_plus"),      # SSSP
]


@pytest.mark.parametrize("weight,reverse,semiring", SUMMARY_SPECS)
def test_sharded_build_summary_matches_replicated(weight, reverse, semiring):
    """The distributed bucket sort builds the same summary the replicated
    compaction does: identical relabelling, counters and boundary, the same
    E_K edge multiset, and identical summarized pushes (bitwise for min)."""
    from repro.core.pagerank import build_summary

    g = _graph(n=280, m=1800, seed=21)
    values = _values(semiring, g.node_capacity, seed=22)
    hot = jnp.asarray(
        np.random.default_rng(23).random(g.node_capacity) < 0.3)
    caps = dict(hot_node_capacity=128, hot_edge_capacity=1024)
    kw = dict(weight=weight, reverse=reverse, semiring=semiring)
    ref = build_summary(g, values, hot, **caps, **kw)
    sharded_layout = build_sharded_layout(g, mesh=_mesh(), **kw)
    sh = build_summary(g, values, hot, **caps, layout=sharded_layout, **kw)

    assert sh.sharded and not ref.sharded
    assert sh.num_shards == sharded_layout.num_shards
    for field in ("num_hot", "num_ek", "num_eb", "overflow"):
        assert int(getattr(sh, field)) == int(getattr(ref, field)), field
    np.testing.assert_array_equal(np.asarray(sh.hot_ids),
                                  np.asarray(ref.hot_ids))
    _assert_matches(sh.b_in, ref.b_in, semiring)
    np.testing.assert_array_equal(_ek_triples(sh), _ek_triples(ref))
    # the summarized sweep consumes both forms through summary_layout/push
    local = _values(semiring, 128, seed=24)
    out_ref = B.push(local, B.summary_layout(ref, semiring=semiring),
                     semiring=semiring, backend="segment_sum")
    out_sh = B.push(local, B.summary_layout(sh, semiring=semiring),
                    semiring=semiring, backend="segment_sum")
    _assert_matches(out_sh, out_ref, semiring)


def test_sharded_summarized_sweeps_match_replicated():
    """End-to-end over the summarized kernels: PageRank (f32 tolerance) and
    SSSP (bitwise) answers agree between the two summary forms."""
    from repro.core.pagerank import (build_summary, pagerank,
                                     summarized_pagerank)
    from repro.core.traversal import sssp, summarized_sssp

    g = _graph(n=260, m=1600, seed=25)
    hot = jnp.asarray(
        np.random.default_rng(26).random(g.node_capacity) < 0.4)
    caps = dict(hot_node_capacity=160, hot_edge_capacity=2048)
    ranks, _ = pagerank(g, num_iters=5)
    ref = build_summary(g, ranks, hot, **caps)
    sh = build_summary(
        g, ranks, hot, **caps,
        layout=build_sharded_layout(g, mesh=_mesh(), weight="inv_out"))
    r_ref, _ = summarized_pagerank(ref, ranks, num_iters=10)
    r_sh, _ = summarized_pagerank(sh, ranks, num_iters=10)
    np.testing.assert_allclose(np.asarray(r_sh), np.asarray(r_ref), **TOL)

    source = jnp.zeros((g.node_capacity,), bool).at[0].set(True)
    dist, _ = sssp(g, source, num_iters=5)
    kw = dict(weight="length", semiring="min_plus")
    ref_m = build_summary(g, dist, hot, **caps, **kw)
    sh_m = build_summary(
        g, dist, hot, **caps, **kw,
        layout=build_sharded_layout(g, mesh=_mesh(), **kw))
    d_ref, _ = summarized_sssp(ref_m, dist, source, num_iters=10)
    d_sh, _ = summarized_sssp(sh_m, dist, source, num_iters=10)
    np.testing.assert_array_equal(np.asarray(d_sh), np.asarray(d_ref))


def test_sharded_summary_bucket_overflow_flags():
    """A destination bucket past its ⌈H_cap/S⌉ capacity raises ``overflow``
    even when |E_K| fits globally (the caller falls back to exact), and a
    roomy H_cap over the same skewed graph stays clean."""
    from repro.core.pagerank import build_summary

    # a star whose edges all sit in the FIRST slot shard (huge append
    # headroom) and all land on vertex 0: one (source shard, bucket) block
    # must carry every E_K edge
    n, m = 64, 20
    src = np.arange(1, m + 1, dtype=np.int32)
    dst = np.zeros(m, np.int32)
    g = from_edges(src, dst, n, 512)  # E_s = 64 -> all lives in shard 0
    hot = jnp.ones((n,), bool)
    ranks = jnp.ones((n,), jnp.float32)
    layout = build_sharded_layout(g, num_shards=8, weight="inv_out")
    # H_cap = 64 -> per-block capacity ⌈64/8⌉ = 8 < 20 edges in one block,
    # even though |E_K| = 20 fits H_cap globally
    tight = build_summary(g, ranks, hot, hot_node_capacity=n,
                          hot_edge_capacity=64, layout=layout)
    assert int(tight.num_ek) == m <= 64
    assert bool(tight.overflow)
    # same graph, H_cap sized so the block fits -> clean flag, full E_K
    roomy = build_summary(g, ranks, hot, hot_node_capacity=n,
                          hot_edge_capacity=8 * m, layout=layout)
    assert not bool(roomy.overflow)
    assert int(roomy.num_ek) == m


# --------------------------------------------------- engine shard rebalance
@pytest.mark.parametrize("name", ["sssp", "connected-components", "pagerank"])
def test_forced_imbalance_stream_triggers_rebalance(name):
    """A stream over a front-loaded edge buffer (huge append headroom ->
    every live slot in the head shards) must trip the engine's rebalance
    threshold exactly once, recut to an even partition, and keep the
    answers equal to the single-device engine — bitwise for the
    min-semiring workloads."""
    src, dst = gnm_edges(220, 1300, seed=31)
    kw = {"sssp": dict(sources=(0,))}.get(name, {})
    common = dict(algorithm=name, num_iters=8, edge_capacity=16384, **kw)
    with repro.session((src, dst), **common) as ref, \
         repro.session((src, dst), mesh=_mesh(), num_shards=8,
                       **common) as sh:
        assert sh.engine.config.rebalance_threshold == 1.0  # on by default
        assert sh.engine.rebalances == 0  # nothing measured before a query
        for s in (ref, sh):
            s.add_edges(np.arange(50), np.arange(50) + 100)
        r_ref = ref.query()
        r_sh = sh.query()
        assert sh.engine.rebalances == 1
        assert r_sh.stats.rebalanced
        assert sh.engine.last_imbalance > 1.0
        _assert_matches(np.asarray(r_sh.scores), np.asarray(r_ref.scores),
                        sh.algorithm.semiring)
        # the recut assignment is (near-)even and further balanced appends
        # do not re-trigger (dead slots were dealt round-robin too)
        from repro.graph.partition import shard_live_counts
        counts = np.asarray(
            shard_live_counts(sh.engine.state, sh.engine._shard_slots))
        assert counts.max() - counts.min() <= 1
        for s in (ref, sh):
            s.add_edges(np.arange(60), np.arange(60) + 30)
        r2_ref = ref.query()
        r2_sh = sh.query()
        assert sh.engine.rebalances == 1
        assert not r2_sh.stats.rebalanced
        _assert_matches(np.asarray(r2_sh.scores), np.asarray(r2_ref.scores),
                        sh.algorithm.semiring)


def test_num_shards_without_mesh_rejected():
    """num_shards only feeds the mesh layout/rebalance path; accepting it
    meshless would silently run unsharded."""
    src, dst = gnm_edges(40, 150, seed=33)
    with pytest.raises(ValueError, match="num_shards requires mesh"):
        repro.session((src, dst), algorithm="pagerank", num_shards=8)


def test_rebalance_disabled_and_threshold_none():
    """rebalance_threshold=None restores the contiguous-cut behaviour (the
    pre-rebalance engine) without touching results."""
    src, dst = gnm_edges(150, 800, seed=32)
    with repro.session((src, dst), algorithm="pagerank", num_iters=6,
                       edge_capacity=8192, mesh=_mesh(), num_shards=8,
                       rebalance_threshold=None) as s:
        s.add_edges([1, 2, 3], [4, 5, 6])
        s.query()
        assert s.engine.rebalances == 0
        assert s.engine._shard_slots is None


# ------------------------------------------------- fused query step parity
def _algo(name, num_iters=8):
    params = {"personalized-pagerank": dict(seeds=(1, 5))}.get(name, {})
    a = make_algorithm(name, **params)
    return a.__class__(**{**{f: getattr(a, f) for f in a.__dataclass_fields__},
                          "num_iters": num_iters})


@pytest.mark.parametrize("name", sorted(available_algorithms()))
def test_sharded_fused_query_step_matches_unsharded(name):
    """Full hot coverage: the summarized answer equals the exact sweep, so
    sharded-vs-unsharded disagreements cannot hide behind approximation."""
    g = _graph(n=250, m=1500, seed=10)
    algo = _algo(name)
    st0 = algo.init_state(g)
    st, _ = algo.exact(st0, g, backend="segment_sum")
    deg = jnp.copy(g.out_deg)
    act = jnp.copy(g.node_active)
    caps = dict(hot_node_capacity=g.node_capacity,
                hot_edge_capacity=g.edge_capacity)
    args = (g, st, deg, act, jnp.float32(0.0), jnp.float32(0.1))
    single = tuple(
        B.build_layout(g, weight=w, reverse=rev, semiring=s)
        for (w, rev, s) in map(B.normalize_layout_spec, algo.layout_specs))
    sharded = tuple(
        build_sharded_layout(g, mesh=_mesh(), weight=w, reverse=rev,
                             semiring=s)
        for (w, rev, s) in map(B.normalize_layout_spec, algo.layout_specs))
    ref_state, ref_stats = fused_query_step(
        *args, algo=algo, **caps, layouts=single, backend="segment_sum")
    out_state, out_stats = fused_query_step(
        *args, algo=algo, **caps, layouts=sharded, backend="segment_sum")
    assert not bool(ref_stats.used_fallback)
    assert int(out_stats.num_hot) == int(ref_stats.num_hot)
    assert int(out_stats.num_ek) == int(ref_stats.num_ek)
    for k in ref_state:
        _assert_matches(out_state[k], ref_state[k], algo.semiring)


@pytest.mark.parametrize("name", sorted(available_algorithms()))
def test_session_mesh_matches_unsharded_engine(name):
    """End to end through ``session(..., mesh=...)``: ingest a chunk, query,
    compare against the mesh-less engine."""
    src, dst = gnm_edges(220, 1300, seed=11)
    kw = {"sssp": dict(sources=(0,)),
          "personalized-pagerank": dict(seeds=(2,))}.get(name, {})
    with repro.session((src, dst), algorithm=name, num_iters=8, **kw) as ref, \
         repro.session((src, dst), algorithm=name, num_iters=8,
                       mesh=_mesh(), **kw) as sh:
        for s in (ref, sh):
            s.add_edges([1, 2, 3, 7], [4, 5, 6, 9])
        r_ref = ref.query()
        r_sh = sh.query()
        assert r_sh.action == r_ref.action
        _assert_matches(np.asarray(r_sh.scores), np.asarray(r_ref.scores),
                        sh.algorithm.semiring)
        # the sharded layout cache behaves like the single one, and its
        # arrays are placed across the mesh once per cache fill (not
        # re-distributed by every consuming shard_map)
        assert sh.engine.layout_builds == ref.engine.layout_builds
        lay = sh.engine.edge_layouts()[0]
        assert isinstance(lay, B.ShardedEdgeLayout)
        assert len(lay.src.sharding.device_set) == lay.mesh.devices.size


@pytest.mark.parametrize("name", ["pagerank", "sssp"])
def test_session_accepts_make_mesh(name):
    """The documented ``jax.make_mesh`` front door: its axes are
    ``Explicit`` by default, and the engine runs it like the same devices
    with auto axes."""
    src, dst = gnm_edges(180, 900, seed=13)
    kw = {"sssp": dict(sources=(0,))}.get(name, {})
    n = min(jax.device_count(), 8)
    mesh = jax.make_mesh((n,), ("shards",), devices=jax.devices()[:n])
    with repro.session((src, dst), algorithm=name, num_iters=8, **kw) as ref, \
         repro.session((src, dst), algorithm=name, num_iters=8, mesh=mesh,
                       **kw) as sh:
        for s in (ref, sh):
            s.add_edges([1, 2, 3], [4, 5, 6])
        _assert_matches(np.asarray(sh.query().scores),
                        np.asarray(ref.query().scores),
                        sh.algorithm.semiring)
        assert isinstance(sh.engine.edge_layouts()[0], B.ShardedEdgeLayout)


def test_sharded_plugin_path_traces_zero_push_coo():
    """The acceptance gate the dry-run enforces, pinned here: lowering the
    sharded ``fused_query_step`` touches no unsorted ``push_coo``."""
    g = _graph(n=251, m=1100, seed=12, n_cap=251)  # unique shapes => fresh trace
    algo = _algo("pagerank", num_iters=5)
    st = algo.init_state(g)
    mesh = _mesh()
    B.reset_trace_counts()
    fused_query_step(
        g, st, jnp.copy(g.out_deg), jnp.copy(g.node_active),
        jnp.float32(0.2), jnp.float32(0.1), algo=algo,
        hot_node_capacity=128, hot_edge_capacity=1024,
        backend="segment_sum", mesh=mesh)
    assert B.trace_count("push_coo") == 0
    # the mesh-less fallback (no layouts, no mesh) still goes through the
    # unsorted path — the counter is live, not vacuously zero
    B.reset_trace_counts()
    B.push_coo(jnp.ones(4), jnp.zeros(2, jnp.int32),
               jnp.ones(2, jnp.int32), 4)
    assert B.trace_count("push_coo") == 1
