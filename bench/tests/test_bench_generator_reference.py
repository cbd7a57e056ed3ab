"""The Kronecker generator and the plain references, on small graphs."""

import numpy as np
import pytest

from harness import kronecker, reference
from harness.rbo import rbo_from_scores


def _gen(seed, graph_seed=7):
    return kronecker.generate(seed, graph_seed=graph_seed, scale=10,
                              edge_factor=8, a=0.57, b=0.19, c=0.19,
                              holdout_blocks=4, block=16)


def _keys(g):
    src = np.concatenate([g.init_src, g.stream_src]).astype(np.int64)
    dst = np.concatenate([g.init_dst, g.stream_dst])
    return src * g.num_vertices + dst


@pytest.mark.parametrize("seed", [0, 2**33 + 5])
def test_kronecker_is_deterministic_and_simple(seed):
    g1, g2 = _gen(seed), _gen(seed)
    for x, y in zip(g1[:4], g2[:4]):
        np.testing.assert_array_equal(x, y)
    assert g1.stream_src.shape == (64,)
    # undirected: every edge both ways, every block whole pairs both ways
    keys = set(_keys(g1).tolist())
    n = g1.num_vertices
    assert {(k % n) * n + k // n for k in keys} == keys
    blocks = zip(g1.stream_src.reshape(4, 16), g1.stream_dst.reshape(4, 16))
    for s_, d_ in blocks:
        np.testing.assert_array_equal(s_[:8], d_[8:])
        np.testing.assert_array_equal(s_[8:], d_[:8])
    assert not np.any(g1.init_src == g1.init_dst)
    assert not np.any(g1.stream_src == g1.stream_dst)
    keys = _keys(g1)
    assert np.unique(keys).size == keys.size
    assert g1.init_src.max() < g1.num_vertices and g1.init_src.min() >= 0
    # another run seed: the same graph, another stream
    other = _gen(seed + 1)
    np.testing.assert_array_equal(np.sort(_keys(other)), np.sort(keys))
    assert not np.array_equal(other.stream_src, g1.stream_src)
    # another graph seed: another graph
    assert not np.array_equal(np.sort(_keys(_gen(seed, graph_seed=8))),
                              np.sort(keys))


def test_kronecker_is_skewed_like_graph500():
    g = _gen(3)
    deg = np.bincount(g.init_src, minlength=g.num_vertices)
    # A=0.57 concentrates edges: the top 1% of vertices hold far more
    # than 1% of the out-edges
    top = np.sort(deg)[::-1][: g.num_vertices // 100].sum()
    assert top > 0.08 * deg.sum()


def _small_graph(seed=0, n=60, m=400):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    keep = src != dst
    return src[keep], dst[keep], n + 4  # four isolated ids


def _loop_pagerank(src, dst, n, beta, iters):
    out = np.bincount(src, minlength=n)
    active = (out + np.bincount(dst, minlength=n)) > 0
    x = active.astype(float)
    for _ in range(iters):
        y = np.zeros(n)
        for u, v in zip(src, dst):
            y[v] += x[u] / out[u]
        x = np.where(active, (1 - beta) + beta * y, 0.0)
    return x


def _loop_bfs(src, dst, n, source):
    d = np.full(n, np.inf)
    d[source] = 0
    for _ in range(n):
        for u, v in zip(src, dst):
            d[v] = min(d[v], d[u] + 1)
    return d


def test_exact_pagerank_matches_loop():
    src, dst, n = _small_graph()
    g = reference.GraphAt.of(src, dst, n)
    got = reference.exact_pagerank(g, beta=0.85, num_iters=30, threads=3)
    np.testing.assert_allclose(got, _loop_pagerank(src, dst, n, 0.85, 30),
                               rtol=1e-12)


def test_summarized_pagerank_on_everything_is_exact():
    src, dst, n = _small_graph(1)
    g = reference.GraphAt.of(src, dst, n)
    x = reference.summarized_pagerank(g, g.active, g.active.astype(float),
                                      beta=0.85, num_iters=30)
    want = reference.exact_pagerank(g, beta=0.85, num_iters=30)
    np.testing.assert_allclose(x, want[g.active], rtol=1e-12)


def test_bfs_fixed_point_accepts_bfs_and_refuses_a_wrong_answer():
    src, dst, n = _small_graph(2)
    g = reference.GraphAt.of(src, dst, n)
    source = int(np.argmax(np.bincount(src, minlength=n)))
    d = _loop_bfs(src, dst, n, source)
    hot = g.active
    inf = np.full(n, np.inf)
    np.testing.assert_array_equal(
        reference.bfs_fixed_point(g, hot, d, inf, source), d[hot])
    wrong = d.copy()
    v = int(np.nonzero(np.isfinite(d) & (d > 0))[0][0])
    wrong[v] += 1
    assert np.any(reference.bfs_fixed_point(g, hot, wrong, inf, source)
                  != wrong[hot])


def test_exact_bfs_matches_loop():
    src, dst, n = _small_graph(4)
    g = reference.GraphAt.of(src, dst, n)
    source = int(np.argmax(np.bincount(src, minlength=n)))
    np.testing.assert_array_equal(reference.exact_bfs(g, source),
                                  _loop_bfs(src, dst, n, source))


def test_a_graph_with_blocks_beside_it_is_the_whole_graph():
    src, dst, n = _small_graph(5)
    whole = reference.GraphAt.of(src, dst, n)
    part = reference.GraphAt(reference.Edges(src[:300], dst[:300], n),
                             src[300:], dst[300:])
    assert part.hash == whole.hash and part.num_edges == whole.num_edges
    np.testing.assert_array_equal(part.out_deg, whole.out_deg)
    np.testing.assert_allclose(
        reference.exact_pagerank(part, beta=0.85, num_iters=30, threads=2),
        reference.exact_pagerank(whole, beta=0.85, num_iters=30, threads=3),
        rtol=1e-12)
    ids = np.array([1, 5, 7])
    np.testing.assert_array_equal(part.in_rows(ids).toarray(),
                                  whole.in_rows(ids).toarray())


def test_rule_hot_adds_the_delta_expansion():
    # a path 0 -> 1 -> 2 -> 3 -> 4 -> 5 plus 6 -> 0; vertex 6 is new
    src = np.array([0, 1, 2, 3, 4], np.int32)
    dst = np.array([1, 2, 3, 4, 5], np.int32)
    prev = reference.GraphAt.of(src, dst, 8)
    g = reference.GraphAt.of(np.append(src, 6).astype(np.int32),
                             np.append(dst, 0).astype(np.int32), 8)
    rule = dict(r=0.2, n=1, hop_cap=4)
    assert set(np.nonzero(reference.certain_hot(g, prev, r=0.2, n=1))[0]) \
        == {6, 0}
    quiet = np.zeros(8)
    assert set(np.nonzero(reference.rule_hot(g, prev, quiet, delta=0.1,
                                             **rule))[0]) == {6, 0}
    # a large score on vertices 1 and 2 carries the expansion on; it stops
    # at 3, whose score is too small to join
    loud = np.array([0, 50, 50, 0, 50, 0, 0, 0], float)
    assert set(np.nonzero(reference.rule_hot(g, prev, loud, delta=0.1,
                                             **rule))[0]) == {6, 0, 1, 2}
    # a huge delta switches the expansion off
    assert set(np.nonzero(reference.rule_hot(g, prev, loud, delta=1e9,
                                             **rule))[0]) == {6, 0}


def test_certain_hot_holds_new_and_moved_vertices_and_their_out_neighbours():
    src = np.array([0, 1, 2, 3, 4], np.int32)
    dst = np.array([1, 2, 3, 4, 0], np.int32)
    prev = reference.GraphAt.of(src, dst, 8)
    # vertex 0 doubles its out-degree; vertex 6 is new
    g = reference.GraphAt.of(np.append(src, [0, 6]).astype(np.int32),
                          np.append(dst, [2, 5]).astype(np.int32), 8)
    hot = reference.certain_hot(g, prev, r=0.2, n=1)
    assert set(np.nonzero(hot)[0]) == {0, 1, 2, 5, 6}


def test_edge_hash_is_order_free_and_sees_one_edge():
    src, dst, _ = _small_graph(3)
    perm = np.random.default_rng(0).permutation(src.size)
    assert reference.edge_hash(src, dst) == reference.edge_hash(src[perm],
                                                                dst[perm])
    assert reference.edge_hash(src[1:], dst[1:]) != reference.edge_hash(src,
                                                                        dst)


def test_rbo_of_identical_and_reversed_rankings():
    a = np.arange(200, dtype=float)
    assert rbo_from_scores(a, a, depth=100) == pytest.approx(1.0)
    assert rbo_from_scores(a, -a, depth=100) < 0.1
