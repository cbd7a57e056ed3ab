"""Pin the fused query step's overflow fallback contract, and the summary
buffers the engine sizes to the hot set.

``approximate_query_step`` computes the summarized result unconditionally
and reports capacity overflow in ``stats.used_fallback`` — the caller's
side of the contract (the engine's) is to *discard* the summarized ranks
and recompute exactly.  Both legs are pinned here against exact PageRank.

The engine runs the step split at the hot set (``hot_set_step``, a read
of |K| and |E_K|, then ``summarized_step`` at sizes from
``engine.hot_bucket``): the answers equal those at the configured
capacities, the ladder grows only when a count outgrows it, similar
queries compile nothing new, and a configured capacity below the hot set
still takes the exact fallback.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import backend as B
from repro.core import engine as E
from repro.core.fused import approximate_query_step, summarized_step
from repro.core.pagerank import pagerank
from repro.graph import from_edges
from repro.graph.generators import gnm_edges

TOL = dict(rtol=1e-5, atol=1e-6)


def _fixture(n=300, m=2000, seed=0):
    src, dst = gnm_edges(n, m, seed=seed)
    g = from_edges(src, dst, n, m + 64)
    ranks, _ = pagerank(g, num_iters=8)
    return g, ranks, jnp.copy(g.out_deg), jnp.copy(g.node_active)


def test_overflow_sets_used_fallback_and_caller_recomputes_exact():
    g, ranks, deg, act = _fixture()
    # a zero degree snapshot marks every active vertex as changed
    # -> |K| = all active >> capacity 16
    new_ranks, stats = approximate_query_step(
        g, ranks, jnp.zeros_like(deg), act, jnp.float32(0.0),
        jnp.float32(0.1), hot_node_capacity=16, hot_edge_capacity=64,
        num_iters=8)
    assert bool(stats.used_fallback)
    assert int(stats.num_hot) > 16
    # the summarized ranks were still computed (overflow does not branch on
    # device) and stay well-formed — but the caller must discard them and
    # serve the exact recompute (the engine leg below pins that end to end)
    assert new_ranks.shape == ranks.shape
    assert bool(jnp.all(jnp.isfinite(new_ranks)))


def test_no_overflow_with_full_capacities_matches_exact():
    """At full coverage (hot set = every active vertex, r=0) the summarized
    sweep must reproduce exact PageRank — the non-overflow leg."""
    g, ranks, deg, act = _fixture(seed=1)
    new_ranks, stats = approximate_query_step(
        g, ranks, jnp.zeros_like(deg), act, jnp.float32(0.0),
        jnp.float32(0.1), hot_node_capacity=g.node_capacity,
        hot_edge_capacity=g.edge_capacity, num_iters=30, tol=1e-7)
    assert not bool(stats.used_fallback)
    assert int(stats.num_hot) == int(g.num_active_nodes())
    exact, _ = pagerank(g, num_iters=30, tol=1e-7)
    np.testing.assert_allclose(np.asarray(new_ranks), np.asarray(exact),
                               **TOL)


def test_engine_discards_summarized_state_on_fused_overflow():
    """Engine-side of the contract through the fused path: capacities
    exceeded -> overflow_fallback recorded and the served ranks are the
    exact recomputation, not the truncated summarized state."""
    import repro

    src, dst = gnm_edges(250, 1500, seed=2)
    with repro.session((src, dst), algorithm="pagerank", num_iters=12,
                       hot_node_capacity=8, hot_edge_capacity=32,
                       r=0.0, delta=1e-6, fused=True) as s:
        assert s.engine.config.fused
        s.add_edges([0, 1, 2], [3, 4, 5])
        res = s.query()
        assert res.stats.overflow_fallback
        assert res.action == "compute-approximate"
        exact, _ = pagerank(s.engine.state, num_iters=12)
        np.testing.assert_allclose(res.scores, np.asarray(exact), **TOL)


# ---------------------------------------------------------------------------
# summary buffers sized to the hot set
# ---------------------------------------------------------------------------

#: a graph whose hot set is a small share of its capacities, streamed in
#: small blocks, one query each
BIG_N, BIG_M, BLOCK = 3000, 20000, 25
STREAM_FROM = BIG_M - 4 * BLOCK


def _streamed_answers(algorithm, backend, **over):
    src, dst = gnm_edges(BIG_N, BIG_M, seed=3)
    kw = {"sources": (0,)} if algorithm == "sssp" else {}
    with repro.session((src[:STREAM_FROM], dst[:STREAM_FROM]), algorithm,
                       node_capacity=BIG_N, backend=backend, **kw,
                       **over) as s:
        out = []
        for lo in range(STREAM_FROM, BIG_M, BLOCK):
            s.add_edges(src[lo:lo + BLOCK], dst[lo:lo + BLOCK])
            res = s.query()
            out.append((np.asarray(res.scores).copy(), res.stats))
        return out, s.engine


@pytest.mark.parametrize("algorithm,backend,bitwise", [
    ("pagerank", "segment_sum", True),
    ("pagerank", "pallas", True),
    ("sssp", "segment_sum", True),
    ("sssp", "pallas", True),
    # HITS normalises each half-step by sums over the K-slot buffers, and
    # a float32 sum's association follows the buffer's length
    ("hits", "segment_sum", False),
])
def test_bucketed_step_matches_full_capacity(algorithm, backend, bitwise,
                                             monkeypatch):
    bucketed, eng = _streamed_answers(algorithm, backend)
    cfg = eng.config
    monkeypatch.setattr(E, "hot_bucket",
                        lambda count, current, capacity: capacity)
    full, _ = _streamed_answers(algorithm, backend)
    for (got, st), (want, st_full) in zip(bucketed, full):
        assert st.hot_node_bucket < cfg.hot_node_capacity
        assert st.hot_edge_bucket < cfg.hot_edge_capacity
        assert st.num_ek <= st.hot_edge_bucket
        assert (st_full.hot_node_bucket, st_full.hot_edge_bucket) == (
            cfg.hot_node_capacity, cfg.hot_edge_capacity)
        assert (st.num_hot, st.num_ek, st.num_eb) == (
            st_full.num_hot, st_full.num_ek, st_full.num_eb)
        assert not st.overflow_fallback and not st_full.overflow_fallback
        if bitwise:
            assert st.iterations == st_full.iterations
            np.testing.assert_array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("count,current,capacity,want", [
    (0, 0, 10**6, E.BUCKET_FLOOR),     # an empty hot set gets the floor
    (100, 0, 10**6, 256),              # 2x headroom, rounded up to 2^k
    (128, 0, 10**6, 256),              # 2 * 128 is already a power of two
    (200, 256, 10**6, 256),            # fits: kept, headroom or not
    (256, 256, 10**6, 256),
    (257, 256, 10**6, 1024),           # outgrown: 2 * 257 -> 1024
    (10, 1024, 10**6, 1024),           # never shrinks
    (600, 0, 1000, 1000),              # capped at the configured capacity
    (5000, 1000, 1000, 1000),          # over it: the summary overflows
    (3, 0, 8, 8),                      # a capacity below the floor
])
def test_hot_bucket_ladder(count, current, capacity, want):
    assert E.hot_bucket(count, current, capacity) == want


def test_similar_queries_compile_no_new_summary_step():
    src, dst = gnm_edges(BIG_N, BIG_M, seed=4)
    blocks = range(STREAM_FROM, BIG_M, BLOCK)
    with repro.session((src[:STREAM_FROM], dst[:STREAM_FROM]), "pagerank",
                       node_capacity=BIG_N) as s:
        lo = blocks[0]
        s.add_edges(src[lo:lo + BLOCK], dst[lo:lo + BLOCK])
        first = s.query().stats
        growths = s.engine.bucket_growths
        compiled = summarized_step._cache_size()
        traced = B.trace_count("summary_layout")
        for lo in blocks[1:]:
            s.add_edges(src[lo:lo + BLOCK], dst[lo:lo + BLOCK])
            st = s.query().stats
            assert (st.hot_node_bucket, st.hot_edge_bucket) == (
                first.hot_node_bucket, first.hot_edge_bucket)
        assert growths == 1
        assert s.engine.bucket_growths == growths
        assert summarized_step._cache_size() == compiled
        assert B.trace_count("summary_layout") == traced


def test_small_hot_edge_capacity_still_takes_exact_fallback():
    src, dst = gnm_edges(250, 1500, seed=2)
    with repro.session((src, dst), algorithm="pagerank", num_iters=12,
                       hot_edge_capacity=32, r=0.0, delta=1e-6) as s:
        s.add_edges([0, 1, 2], [3, 4, 5])
        res = s.query()
        st = res.stats
        assert st.num_ek > 32 and st.num_hot <= st.hot_node_bucket
        assert st.hot_edge_bucket == 32
        assert st.overflow_fallback
        exact, _ = pagerank(s.engine.state, num_iters=12)
        np.testing.assert_allclose(res.scores, np.asarray(exact), **TOL)
