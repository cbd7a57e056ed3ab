"""Pin the fused query step's overflow fallback contract, and the summary
buffers the engine sizes to the hot set.

``summarized_step`` computes the summarized result unconditionally and
reports capacity overflow in ``stats.used_fallback`` — the caller's side
of the contract (the engine's) is to *discard* the summarized ranks and
recompute exactly.  Both legs are pinned here against exact PageRank.

The engine runs the step split at the hot set (``hot_set_step``, a read
of |K| and |E_K|, then ``summarized_step`` at sizes from
``engine.hot_bucket``): the answers equal those at the configured
capacities, the ladder grows only when a count outgrows it, similar
queries compile nothing new, and a configured capacity below the hot set
still takes the exact fallback.  The summaries built from the hot set
step's E_K mask are bitwise those built from the hot set alone.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import algorithm as A
from repro.core import backend as B
from repro.core import engine as E
from repro.core.algorithm import (PageRankAlgorithm, available_algorithms,
                                  make_algorithm)
from repro.core.fused import hot_set_step, summarized_step
from repro.core.pagerank import pagerank
from repro.core.semiring import resolve_semiring
from repro.graph import from_edges
from repro.graph.generators import gnm_edges
from repro.graph.graph import (add_edges_preserving, find_edge_slots,
                               remove_edges_by_slot)

TOL = dict(rtol=1e-5, atol=1e-6)


def _fixture(n=300, m=2000, seed=0):
    src, dst = gnm_edges(n, m, seed=seed)
    g = from_edges(src, dst, n, m + 64)
    ranks, _ = pagerank(g, num_iters=8)
    return g, ranks, jnp.copy(g.out_deg), jnp.copy(g.node_active)


def _split_step(g, ranks, deg_prev, act, *, algo, hot_node_capacity,
                hot_edge_capacity):
    """One PageRank query through the split step at the given sizes; r=0
    and Δ=0.1, so a zero degree snapshot makes every active vertex hot."""
    state = {"ranks": ranks}
    hot, hstats, _, ek = hot_set_step(g, state, deg_prev, act,
                                      jnp.float32(0.0), jnp.float32(0.1),
                                      algo=algo)
    new_state, stats = summarized_step(
        g, state, hot, hstats, algo=algo,
        hot_node_capacity=hot_node_capacity,
        hot_edge_capacity=hot_edge_capacity, ek_mask=ek)
    return new_state["ranks"], stats


def test_overflow_sets_used_fallback_and_caller_recomputes_exact():
    g, ranks, deg, act = _fixture()
    # a zero degree snapshot marks every active vertex as changed
    # -> |K| = all active >> capacity 16
    new_ranks, stats = _split_step(
        g, ranks, jnp.zeros_like(deg), act,
        algo=PageRankAlgorithm(num_iters=8),
        hot_node_capacity=16, hot_edge_capacity=64)
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
    new_ranks, stats = _split_step(
        g, ranks, jnp.zeros_like(deg), act,
        algo=PageRankAlgorithm(num_iters=30, tol=1e-7),
        hot_node_capacity=g.node_capacity, hot_edge_capacity=g.edge_capacity)
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


# ---------------------------------------------------------------------------
# the E_K mask handed from the hot set step to the summary
# ---------------------------------------------------------------------------

_SUMMARY_FIELDS = ("hot_ids", "ek_src", "ek_dst", "ek_w", "ek_row_offsets",
                   "num_ek", "b_in", "num_eb", "overflow")


def _churned_graph():
    """A graph before and after a block of inserts (one of them a
    self-loop) and a block of removals, so the hot set is a part of the
    active vertices and tombstones lie on edges into it."""
    src, dst = gnm_edges(2000, 8000, seed=5)
    g0 = from_edges(src[:7970], dst[:7970], 2000, 8064)
    new_src = np.r_[src[7970:], 7].astype(np.int32)
    new_dst = np.r_[dst[7970:], 7].astype(np.int32)
    g = add_edges_preserving(g0, jnp.asarray(new_src), jnp.asarray(new_dst))
    gone = np.arange(0, 7970, 200)
    g = remove_edges_by_slot(g, jnp.asarray(find_edge_slots(
        g, src[gone], dst[gone])))
    return g0, g


def _old_b_in(ranks_prev, hot, summary, kw):
    """``b_in`` as the per-slot E_B mask over the layout (emitter cold,
    receiver hot) computes it."""
    layout = kw["layout"]
    s = resolve_semiring(kw.get("semiring", "plus_times"))
    n = hot.shape[0]
    eb = ~hot[layout.src] & hot[jnp.minimum(layout.dst, n - 1)]
    full = B.push(ranks_prev, layout, backend=kw["backend"], mask=eb,
                  semiring=s)
    valid = jnp.arange(summary.hot_ids.shape[0]) < summary.num_hot
    return jnp.where(valid, full[..., summary.hot_ids],
                     jnp.asarray(s.zero, s.dtype))


@pytest.mark.parametrize("backend", ["segment_sum", "pallas"])
@pytest.mark.parametrize("name", available_algorithms())
def test_handed_ek_mask_gives_the_same_summaries(name, backend, monkeypatch):
    """``summarized_step`` with ``hot_set_step``'s E_K mask builds every
    summary field bitwise as ``build_summary`` does from the hot set alone
    with the per-slot E_B push, in each orientation the algorithm builds,
    and answers bitwise as without the mask."""
    params = {"personalized-pagerank": dict(seeds=(1, 5))}.get(name, {})
    algo = make_algorithm(name, **params)
    g0, g = _churned_graph()
    state, _ = algo.exact(algo.init_state(g0), g0)
    layouts = tuple(B.build_layout(g, weight=w, reverse=rev, semiring=s)
                    for (w, rev, s) in map(B.normalize_layout_spec,
                                           algo.layout_specs))
    hot, hstats, counts, ek = hot_set_step(
        g, state, g0.out_deg, g0.node_active, jnp.float32(0.2),
        jnp.float32(0.1), algo=algo)
    assert 0 < int(counts[0]) < int(g.num_active_nodes())
    assert bool(hot[7]) and int(counts[1]) == int(jnp.sum(ek))
    step = dict(algo=algo, hot_node_capacity=1024, hot_edge_capacity=2048,
                layouts=layouts, backend=backend)

    calls = []
    real = A._build_summary

    def record(graph, ranks_prev, hot_mask, **kw):
        out = real(graph, ranks_prev, hot_mask, **kw)
        calls.append((ranks_prev, kw, out))
        return out

    monkeypatch.setattr(A, "_build_summary", record)
    # the step's body outside its jit, so the summaries are concrete
    summarized_step.__wrapped__(g, state, hot, hstats, ek_mask=ek, **step)
    monkeypatch.undo()
    assert len(calls) == len(algo.layout_specs)
    live = g.edge_mask()
    for ranks_prev, kw, got in calls:
        assert kw["ek_mask"] is ek
        want = real(g, ranks_prev, hot, **{**kw, "ek_mask": None})
        e_src, e_dst = ((g.dst, g.src) if kw.get("reverse")
                        else (g.src, g.dst))
        num_eb = int(jnp.sum(live & ~hot[e_src] & hot[e_dst]))
        assert int(want.num_eb) == num_eb > 0 and not bool(want.overflow)
        for field in _SUMMARY_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(got, field)),
                np.asarray(getattr(want, field)), err_msg=field)
        old = _old_b_in(ranks_prev, hot, want, kw)
        np.testing.assert_array_equal(np.asarray(got.b_in), np.asarray(old))
        np.testing.assert_array_equal(np.asarray(want.b_in), np.asarray(old))

    got_state, got_stats = summarized_step(g, state, hot, hstats,
                                           ek_mask=ek, **step)
    want_state, want_stats = summarized_step(g, state, hot, hstats, **step)
    for key in want_state:
        np.testing.assert_array_equal(np.asarray(got_state[key]),
                                      np.asarray(want_state[key]))
    for got, want in zip(got_stats, want_stats):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
