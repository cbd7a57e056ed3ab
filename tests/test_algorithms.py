"""The pluggable-algorithm API: parity, registry, session front door.

Acceptance contract for every registered algorithm:

- *exactness at full coverage*: on a static graph (no pending updates) a
  summarized query with r = 1.0-equivalent selection (every vertex hot)
  reproduces the exact reference up to f32 reassociation;
- *accuracy at paper defaults*: over a streamed synthetic dataset with the
  paper's (r, n, Δ) = (0.2, 1, 0.1), per-query RBO vs an exact replay stays
  >= 0.95.
"""

import numpy as np
import pytest

import repro as veilgraph
from repro.core import (Action, EngineConfig, HITSAlgorithm,
                        PageRankAlgorithm, PersonalizedPageRankAlgorithm,
                        StreamingAlgorithm, VeilGraphEngine,
                        available_algorithms, make_algorithm,
                        register_algorithm)
from repro.core.policies import always
from repro.graph.generators import barabasi_albert_edges
from repro.metrics import rbo_from_scores
from repro.stream import StreamConfig, build_stream

ALGORITHMS = {
    "pagerank": lambda: PageRankAlgorithm(num_iters=60, tol=1e-7),
    "personalized-pagerank": lambda: PersonalizedPageRankAlgorithm(
        seeds=(0, 3, 14), num_iters=60, tol=1e-7),
    "hits": lambda: HITSAlgorithm(num_iters=60, tol=1e-7),
}


def _cfg(n_cap, e_cap, **kw):
    base = dict(node_capacity=n_cap, edge_capacity=e_cap,
                hot_node_capacity=n_cap, hot_edge_capacity=e_cap,
                r=0.2, n=1, delta=0.1)
    base.update(kw)
    return EngineConfig(**base)


@pytest.fixture(scope="module")
def static_graph():
    return barabasi_albert_edges(800, 3, seed=0)


@pytest.fixture(scope="module")
def paper_stream():
    # paper-representative churn: update chunks are ~0.5% of |E| per query
    src, dst = barabasi_albert_edges(5000, 4, seed=0)
    return build_stream(src, dst, StreamConfig(stream_size=1000,
                                               num_queries=8, seed=2))


@pytest.mark.parametrize("name", available_algorithms())
@pytest.mark.parametrize("fused", [True, False])
def test_full_hot_set_matches_exact(static_graph, name, fused):
    """r = 1.0 coverage (every active vertex hot, empty big vertex) ==> the
    summarized path is the exact computation, for every registered
    algorithm (the tuned instances above, else the registry's default)."""
    src, dst = static_graph
    algo = ALGORITHMS[name]() if name in ALGORITHMS else make_algorithm(name)
    # r < 0 makes every previously-seen vertex "changed" => K == V_active
    approx = VeilGraphEngine(_cfg(1000, 8192, r=-1.0, delta=1e9, fused=fused),
                             algo)
    exact = VeilGraphEngine(_cfg(1000, 8192, fused=fused), algo,
                            on_query=always(Action.EXACT))
    approx.start(src, dst)
    exact.start(src, dst)
    ra, sa = approx.query()
    re_, se = exact.query()
    assert sa.action == "compute-approximate"
    assert not sa.overflow_fallback
    assert sa.num_hot == sa.num_nodes  # full coverage
    np.testing.assert_allclose(ra, re_, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("name", sorted(ALGORITHMS))
def test_streamed_rbo_at_paper_defaults(paper_stream, name):
    """Summarized replay tracks the exact replay at (r, n, Δ) = (.2, 1, .1)."""
    algo = ALGORITHMS[name]()
    knobs = dict(node_capacity=5000, edge_capacity=40000, r=0.2, n=1,
                 delta=0.1)
    approx = veilgraph.session(paper_stream, algo, **knobs)
    exact = veilgraph.session(paper_stream, algo,
                              on_query=always(Action.EXACT), **knobs)
    for ra, re_ in zip(approx.play(), exact.play()):
        active = np.asarray(approx.engine.state.node_active)
        rbo = rbo_from_scores(ra.scores, re_.scores, depth=1000,
                              active=active)
        assert not ra.stats.overflow_fallback
        assert 0 < ra.stats.num_hot < ra.stats.num_nodes
        assert rbo >= 0.95, (name, ra.stats.query_id, rbo)


def test_registry_round_trip():
    listed = set(available_algorithms())
    assert {"pagerank", "personalized-pagerank", "hits"} <= listed
    assert "ppr" not in listed  # aliases resolve but are not listed
    assert isinstance(make_algorithm("ppr"), PersonalizedPageRankAlgorithm)
    a = make_algorithm("personalized-pagerank", seeds=(1, 2), beta=0.9)
    assert isinstance(a, PersonalizedPageRankAlgorithm)
    assert a.seeds == (1, 2) and a.beta == 0.9
    # instances pass through untouched
    assert make_algorithm(a) is a
    with pytest.raises(ValueError):
        make_algorithm(a, beta=0.5)
    with pytest.raises(KeyError):
        make_algorithm("no-such-algorithm")
    # custom registration: latest wins, visible through the session builder
    register_algorithm("custom-pr", lambda **kw: PageRankAlgorithm(**kw))
    assert "custom-pr" in available_algorithms()
    b = make_algorithm("custom-pr", beta=0.5)
    assert isinstance(b, PageRankAlgorithm) and b.beta == 0.5
    # legacy knobs forward through a **kwargs factory in the session builder
    src = np.asarray([0, 1, 2], np.int32)
    dst = np.asarray([1, 2, 0], np.int32)
    with veilgraph.session((src, dst), "custom-pr", num_iters=5) as s:
        assert s.algorithm.num_iters == 5


def test_algorithms_are_jit_static():
    """Frozen dataclasses: equal configs hash equal (shared jit caches)."""
    assert hash(PageRankAlgorithm(beta=0.9)) == hash(PageRankAlgorithm(beta=0.9))
    assert PageRankAlgorithm() != HITSAlgorithm()
    assert isinstance(PageRankAlgorithm(), StreamingAlgorithm)


def test_session_front_door(static_graph):
    src, dst = static_graph
    with veilgraph.session((src, dst), "pagerank", tol=1e-6) as s:
        r0 = s.query()
        assert r0.action == "compute-approximate"
        assert r0.scores.shape[0] == s.engine.config.node_capacity
        assert len(r0.top(7)) == 7
        s.add_edges([0, 1], [5, 6])
        r1 = s.query()
        assert r1.stats.pending_applied == 2
    # per-algorithm param routing through the builder
    s2 = veilgraph.session((src, dst), "ppr", seeds=(3,), num_iters=40)
    assert s2.algorithm.seeds == (3,)
    assert s2.algorithm.num_iters == 40
    # explicit config + overrides is an error
    with pytest.raises(ValueError):
        veilgraph.session((src, dst), "pagerank",
                          EngineConfig(10, 10, 10, 10), r=0.5)
    with pytest.raises(KeyError):
        veilgraph.session("no-such-dataset")
    # legacy knobs must reach the algorithm or fail loudly, never silently
    # configure nothing (beta/num_iters/tol are also EngineConfig fields)
    with pytest.raises(ValueError, match="already-constructed"):
        veilgraph.session((src, dst), HITSAlgorithm(), num_iters=50)
    with pytest.raises(ValueError, match="does not accept"):
        veilgraph.session((src, dst), "hits", beta=0.9)
    # forwarded algorithm knobs coexist with an explicit config
    s3 = veilgraph.session((src, dst), "hits",
                           EngineConfig(1000, 8192, 1000, 8192), num_iters=5)
    assert s3.algorithm.num_iters == 5
    with pytest.raises(ValueError):
        veilgraph.session((src, dst), "ppr", seeds=(-1,))


def test_session_stream_source(static_graph):
    src, dst = static_graph
    stream = build_stream(src, dst, StreamConfig(stream_size=200,
                                                 num_queries=2, seed=3))
    s = veilgraph.session(stream, "pagerank", tol=1e-6)
    results = list(s.play())
    assert len(results) == 2
    assert all(r.stats.action == "compute-approximate" for r in results)
    # sessions built from raw edges have no stream to play
    with pytest.raises(ValueError):
        next(veilgraph.session((src, dst)).play())


def test_query_view_refreshed_after_updates(static_graph):
    """OnQuery must see post-update node/edge counts (stale-view fix)."""
    src, dst = static_graph
    seen = {}

    def spy(query_id, view):
        seen.update(view)
        return Action.REPEAT_LAST

    eng = VeilGraphEngine(_cfg(1000, 8192), on_query=spy)
    eng.start(src, dst)
    e0 = int(eng.state.num_live_edges())
    # fresh vertices 900/901 so both node and edge counts must move
    eng.register_add_edges([900], [901])
    eng.query()
    assert seen["num_edges"] == e0 + 1
    assert seen["num_nodes"] == int(eng.state.num_active_nodes())
    assert seen["pending"] == 0 and seen["applied"] == 1


def test_repeat_last_staleness_accumulates(static_graph):
    """Updates integrated under repeat-last answers keep counting toward
    policy thresholds until a compute happens."""
    from repro.core.policies import repeat_below_threshold

    src, dst = static_graph
    eng = VeilGraphEngine(_cfg(1000, 8192, tol=1e-6),
                          on_query=repeat_below_threshold(25))
    eng.start(src, dst)
    actions = []
    for _ in range(4):
        eng.register_add_edges([0] * 10, list(range(10, 20)))
        _, st = eng.query()
        actions.append(st.action)
    # 10, 20 stale -> repeat; 30 crosses the threshold -> approximate;
    # counter resets -> 10 stale -> repeat again
    assert actions == ["repeat-last-answer", "repeat-last-answer",
                       "compute-approximate", "repeat-last-answer"]


def test_hits_rank_by_validated():
    with pytest.raises(ValueError):
        HITSAlgorithm(rank_by="authority")
    assert HITSAlgorithm(rank_by="hub").rank_by == "hub"


def test_removal_accounting_reports_resolved(static_graph):
    """Removals that match no live edge are requested but never resolved."""
    src, dst = static_graph
    eng = VeilGraphEngine(_cfg(1000, 8192, tol=1e-6))
    eng.start(src, dst)
    # two live edges + two that don't exist
    rm_s = np.array([src[0], src[1], 998, 999], np.int32)
    rm_d = np.array([dst[0], dst[1], 999, 998], np.int32)
    eng.register_remove_edges(rm_s, rm_d)
    assert eng.pending_updates == 4
    _, st = eng.query()
    assert st.removals_requested == 4
    assert st.removals_resolved == 2
    assert st.pending_applied == 2  # only what actually changed the graph
    assert eng.pending_updates == 0
