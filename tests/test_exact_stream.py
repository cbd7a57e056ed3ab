"""Exact answers on every query of an insert stream (``Action.EXACT``).

The deployment VeilGraph is measured against recomputes PageRank over
the whole graph on every answer.  Here a small undirected Kronecker graph
(scale 12, edge factor 8, A/B/C 0.57/0.19/0.19: graph500's generator at
a test size) takes three insert blocks, each answer computed exactly by
every registered algorithm, and every answer is compared with a plain
float64 NumPy reference written below from the edge list of the graph
that answer saw (power iteration, HITS, the Katz series, min-label
propagation, Bellman-Ford with unit lengths, max-min widest paths):

- on the sync path an answer reflects every block registered before it;
  with ``async_rebuild=True`` it reflects the blocks integrated at the
  earlier queries (query q serves the epoch promoted at q, which holds
  blocks 0..q-1);
- each answer's ``QueryStats`` counts (iterations, live edges, active
  vertices) are those of that graph;
- the Gelly normalisation the engine runs and the Graphalytics one
  (teleport and start divided by |V|, dangling mass redistributed) give
  ranks in the constant ratio |V_active| on an undirected graph, where no
  active vertex dangles;
- the exact branch dispatches the layout under ``veilgraph.layout``
  before the sweep's ``veilgraph.step``, on both query paths, and on the
  sync path an exact answer makes seven host syncs.
"""

import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro
from repro.core import Action, available_algorithms, spans
from repro.core.policies import always

SCALE, EDGE_FACTOR = 12, 8
N = 1 << SCALE
BETA, ITERS = 0.85, 30
BLOCKS, BLOCK_PAIRS = 3, 32
#: float32 sweeps against float64: the benchmark's limit for the same
#: comparison (CPU readings 2.3e-06 to 2.6e-06)
TOL = 2e-5
#: Katz's series converges only for α below 1/λ₁, and λ₁ ≈ 96 here
ALGO_KW = {"katz": dict(alpha=0.005)}
#: the default seed of personalized PageRank and source of the path
#: algorithms: vertex 0, graph500's largest hub
SOURCE = 0
LABEL_SENTINEL = np.iinfo(np.int32).max


def kronecker_pairs(scale, edge_factor, seed, a=0.57, b=0.19, c=0.19):
    """Graph500's Kronecker draw: one quadrant per bit of each endpoint."""
    rng = np.random.default_rng(seed)
    m = edge_factor << scale
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for bit in range(scale):
        u = rng.random(m)
        src |= (u >= a + b).astype(np.int64) << bit
        dst |= (((u >= a) & (u < a + b)) | (u >= a + b + c)).astype(
            np.int64) << bit
    return src, dst


def both_ways(pairs):
    return (np.concatenate([pairs[:, 0], pairs[:, 1]]).astype(np.int32),
            np.concatenate([pairs[:, 1], pairs[:, 0]]).astype(np.int32))


@pytest.fixture(scope="module")
def stream():
    """(initial edges, insert blocks): unordered pairs without loops or
    repeats, each an edge both ways; the blocks are held-out pairs."""
    s, d = kronecker_pairs(SCALE, EDGE_FACTOR, seed=12)
    keep = s != d
    pairs = np.unique(np.stack([np.minimum(s, d), np.maximum(s, d)], 1)[keep],
                      axis=0)
    pairs = pairs[np.random.default_rng(0).permutation(len(pairs))]
    held = BLOCKS * BLOCK_PAIRS
    blocks = [both_ways(pairs[i * BLOCK_PAIRS:(i + 1) * BLOCK_PAIRS])
              for i in range(BLOCKS)]
    return both_ways(pairs[held:]), blocks


def graph_after(stream, k):
    """Edge list of the initial graph plus the first ``k`` blocks."""
    (src, dst), blocks = stream
    return (np.concatenate([src] + [b[0] for b in blocks[:k]]),
            np.concatenate([dst] + [b[1] for b in blocks[:k]]))


def active_of(src, dst):
    """Active vertices: those an edge touches."""
    active = np.zeros(N, bool)
    active[src] = active[dst] = True
    return active


def pagerank64(src, dst, *, graphalytics=False, seed=None):
    """``ITERS`` power iterations in float64 over active vertices.  Gelly:
    start 1, teleport 1-β.  Graphalytics: start 1/|V|, teleport
    (1-β)/|V|, dangling mass spread over |V|.  Personalized (``seed``):
    start and teleport mass on the seed alone, teleport (1-β)."""
    active = active_of(src, dst)
    n = active.sum()
    out = np.bincount(src, minlength=N).astype(np.float64)
    if seed is None:
        r = np.where(active, 1.0 / n if graphalytics else 1.0, 0.0)
        tele = (1 - BETA) / n if graphalytics else 1 - BETA
    else:
        t = np.zeros(N)
        t[seed] = 1.0
        r, tele = np.where(active, t, 0.0), (1 - BETA) * t
    for _ in range(ITERS):
        inc = np.bincount(dst, weights=r[src] / out[src], minlength=N)
        if graphalytics:
            inc += r[active & (out == 0)].sum() / n
        r = np.where(active, tele + BETA * inc, 0.0)
    return r


def hits64(src, dst):
    """Authorities after ``ITERS`` HITS rounds in float64 from the uniform
    start: authorities sum the hubs over in-edges, then hubs sum the new
    authorities over out-edges, each L1-normalised.  The engine starts
    from its previous answer; HITS converges from any positive start, by
    (λ₂/λ₁)² ≈ 0.11 a round on this graph, so the start does not show."""
    active = active_of(src, dst)

    def l1(x):
        x = np.where(active, x, 0.0)
        return x / np.abs(x).sum()

    hub = l1(np.ones(N))
    for _ in range(ITERS):
        auth = l1(np.bincount(dst, weights=hub[src], minlength=N))
        hub = l1(np.bincount(src, weights=auth[dst], minlength=N))
    return auth


def katz64(src, dst, *, alpha, beta=1.0):
    """The Katz series c = β + α·Aᵀc summed ``ITERS`` terms deep in float64
    from c = β.  The engine starts from its previous answer; at
    α·λ₁ ≈ 0.48 the tail past ``ITERS`` terms is below 1e-9."""
    active = active_of(src, dst)
    c = np.where(active, beta, 0.0)
    for _ in range(ITERS):
        c = np.where(active, beta + alpha * np.bincount(
            dst, weights=c[src], minlength=N), 0.0)
    return c


def fixed_point(relax, x):
    """Apply ``relax`` until a sweep changes nothing or ``ITERS`` sweeps
    have run; returns ``(x, sweeps)``, counting the last sweep as the
    engine does."""
    for sweeps in range(1, ITERS + 1):
        new = relax(x)
        done = np.array_equal(new, x)
        x = new
        if done:
            break
    return x, sweeps


def components64(src, dst):
    """Min-label propagation over both edge orientations: every active
    vertex ends with the least vertex id of its component."""
    active = active_of(src, dst)

    def relax(lab):
        inc = np.full(N, LABEL_SENTINEL, np.int64)
        np.minimum.at(inc, dst, lab[src])
        np.minimum.at(inc, src, lab[dst])
        return np.where(active, np.minimum(lab, inc), LABEL_SENTINEL)

    return fixed_point(relax, np.where(active, np.arange(N), LABEL_SENTINEL))


def hops64(src, dst):
    """Bellman-Ford from ``SOURCE`` with unit lengths: hop counts, ∞ where
    unreachable."""
    source = np.arange(N) == SOURCE

    def relax(d):
        inc = np.full(N, np.inf)
        np.minimum.at(inc, dst, d[src] + 1.0)
        return np.where(source, 0.0, np.minimum(d, inc))

    return fixed_point(relax, np.where(source, 0.0, np.inf))


def widest64(src, dst):
    """Max-min widest paths from ``SOURCE`` with unit widths: 1 where
    reachable, 0 elsewhere."""
    source = np.arange(N) == SOURCE

    def relax(w):
        inc = np.zeros(N)
        np.maximum.at(inc, dst, np.minimum(w[src], 1.0))
        return np.where(source, 1.0, np.maximum(w, inc))

    return fixed_point(relax, np.where(source, 1.0, 0.0))


#: name -> (src, dst) -> (answer, sweeps).  ``None`` sweeps: the engine
#: warm-starts from its previous answer and stops once a float32 sweep
#: changes nothing, so only the ``ITERS`` bound is known
REFERENCES = {
    "pagerank": lambda s, d: (pagerank64(s, d), ITERS),
    "personalized-pagerank": lambda s, d: (pagerank64(s, d, seed=SOURCE),
                                           ITERS),
    "hits": lambda s, d: (hits64(s, d), None),
    "katz": lambda s, d: (katz64(s, d, **ALGO_KW["katz"]), None),
    "connected-components": components64,
    "sssp": hops64,
    "widest-path": widest64,
}


def rel_err(got, want, floor=1 - BETA):
    """Largest relative error, with ``floor`` below which it is absolute
    (every active vertex's Gelly PageRank is at least 1-β)."""
    return float(np.max(np.abs(np.asarray(got, np.float64) - want)
                        / np.maximum(np.abs(want), floor)))


def mismatch(name, got, want):
    """How far an answer is from a reference: 0 or ∞ for the discrete
    answers (labels, hop counts, widths), which must be equal; the
    relative error of the others, absolute below a thousandth of the
    reference's largest entry for the vectors with tiny entries."""
    if name in ("connected-components", "sssp", "widest-path"):
        return 0.0 if np.array_equal(np.asarray(got), want) else np.inf
    floor = {"pagerank": 1 - BETA, "katz": 1.0}.get(
        name, 1e-3 * np.abs(want).max())
    return rel_err(got, want, floor)


def exact_session(stream, algorithm="pagerank", **kw):
    (src, dst), _ = stream
    return repro.session((src, dst), algorithm, node_capacity=N,
                         update_pad=2 * BLOCK_PAIRS,
                         on_query=always(Action.EXACT),
                         **ALGO_KW.get(algorithm, {}), **kw)


@pytest.mark.parametrize("async_rebuild", [False, True],
                         ids=["sync", "async"])
@pytest.mark.parametrize("backend", ["pallas", "segment_sum"])
@pytest.mark.parametrize("algorithm", available_algorithms())
def test_every_exact_answer_is_pagerank_of_the_graph_it_saw(
        stream, algorithm, backend, async_rebuild):
    reference = REFERENCES[algorithm]
    sess = exact_session(stream, algorithm, backend=backend,
                         async_rebuild=async_rebuild)
    want, _ = reference(*graph_after(stream, 0))
    assert mismatch(algorithm, sess.scores, want) < TOL
    for q, (bs, bd) in enumerate(stream[1]):
        sess.add_edges(bs, bd)
        res = sess.query()
        # the async engine serves the epoch integrated at earlier queries
        seen = q if async_rebuild else q + 1
        src, dst = graph_after(stream, seen)
        want, sweeps = reference(src, dst)
        assert res.stats.action == Action.EXACT.value
        assert mismatch(algorithm, res.scores, want) < TOL, q
        if sweeps is None:
            assert 1 <= res.stats.iterations <= ITERS
        else:
            assert res.stats.iterations == sweeps
        assert res.stats.num_edges == src.size
        assert res.stats.num_nodes == np.union1d(src, dst).size
        # and not the graph one block later (async) or earlier (sync),
        # where that graph's answer differs
        other, _ = reference(*graph_after(
            stream, seen + (1 if async_rebuild else -1)))
        if mismatch(algorithm, other, want) > 20 * TOL:
            assert mismatch(algorithm, res.scores, other) > 10 * TOL, q


def test_gelly_ranks_are_graphalytics_ranks_times_active_vertices(stream):
    gelly = exact_session(stream, backend="segment_sum")
    ldbc = exact_session(stream, backend="segment_sum", teleport_by_n=True,
                         dangling=True)
    for bs, bd in stream[1][:1]:
        gelly.add_edges(bs, bd)
        ldbc.add_edges(bs, bd)
    g, l_ = gelly.query(), ldbc.query()
    src, dst = graph_after(stream, 1)
    active = np.union1d(src, dst)
    n = active.size
    # undirected: every active vertex has an out-edge, so none dangles
    assert np.all(np.bincount(src, minlength=N)[active] > 0)
    want = pagerank64(src, dst, graphalytics=True)
    scaled = n * np.asarray(l_.scores, np.float64)
    assert rel_err(scaled, n * want) < TOL
    assert rel_err(g.scores, scaled) < TOL
    np.testing.assert_allclose(pagerank64(src, dst), n * want, rtol=1e-12)


@pytest.mark.parametrize("async_rebuild", [False, True],
                         ids=["sync", "async"])
def test_exact_answer_dispatches_the_layout_before_the_sweep(
        stream, tmp_path, async_rebuild):
    sess = exact_session(stream, backend="segment_sum",
                         async_rebuild=async_rebuild)
    blocks = stream[1]
    sess.add_edges(*blocks[0])
    sess.query()  # compiles outside the trace
    sess.add_edges(*blocks[1])
    with jax.profiler.trace(str(tmp_path)):
        sess.query()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    events = sorted(([ev.name, ev.start_ns, ev.start_ns + ev.duration_ns]
                     for plane in ProfileData.from_file(path).planes
                     if plane.name == "/host:CPU"
                     for line in plane.lines for ev in line.events
                     if ev.name.startswith("veilgraph.")),
                    key=lambda e: (e[1], -e[2]))
    (query,) = [e for e in events if e[0] == spans.QUERY]
    (layout,) = [e for e in events if e[0] == spans.LAYOUT]
    (step,) = [e for e in events if e[0] == spans.STEP]
    (iters,) = [e for e in events if e[0] == spans.SYNC_ITERS]
    assert query[1] <= layout[1] and layout[2] <= step[1]
    assert step[2] <= query[2]
    assert step[1] <= iters[1] and iters[2] <= step[2]
    if not async_rebuild:
        syncs = [e[0] for e in events if e[0].startswith("veilgraph.sync.")]
        assert {n: syncs.count(n) for n in syncs} == {
            spans.SYNC_VIEW: 4, spans.SYNC_ITERS: 1, spans.SYNC_READY: 1,
            spans.SYNC_FETCH: 1}
