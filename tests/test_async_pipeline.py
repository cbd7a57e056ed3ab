"""Differential async-correctness harness + epoch-invariant property suite.

The tentpole contract of the async rebuild pipeline
(``EngineConfig.async_rebuild=True``): every answer the async engine
serves must equal what a **synchronous oracle engine** computes when fed
the *identical* update/query interleaving, aligned at the served epoch —
updates the async engine integrated at query q become visible at query
q+1's promotion, so the oracle receives epoch e's update batches
immediately before its first query that serves epoch e.  Identical jitted
programs on identical inputs make the match **bitwise** for the
reassociation-exact min/max-semiring workloads (CC, SSSP, widest path)
and for the meshless sum algebras; allclose covers the one case where FP
reduction order can legitimately differ (mesh sum algebras across the
one-epoch-deferred rebalance recut — see ``rebalance_decision``).

Interleavings are hypothesis-driven: each example draws one integer seed
and derives a random script of add / remove / query(APPROXIMATE | EXACT |
REPEAT_LAST) events from ``np.random.default_rng(seed)`` (the shim in
``tests/_hypothesis_compat.py`` only supports scalar strategies, and a
seed keeps shapes bounded so the suite compiles a handful of programs,
not one per example).  With the real hypothesis installed the matrix is
7 algorithm cases × 30 examples ≥ 200 interleavings; the deterministic
shim runs a 5-example slice of the same space.

The satellite property suite pins the four epoch invariants:
(a) epoch ids are monotone and ``snapshot_lag`` ∈ {0, 1};
(b) no query reads a half-built summary — a served snapshot's buffers
    and layouts are immutable while later epochs build past it;
(c) promotion never skips or overwrites a completed build;
(d) drift accumulated in epoch N is charged to epoch N's stats row,
    never to N+1.
"""

import json
from pathlib import Path

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.api import serve_session, session
from repro.core.algorithm import Action
from repro.core.epoch import (AsyncRebuildPipeline, EpochSnapshot,
                              snapshot_counts)
from repro.graph import graph as G

# capacities are fixed across every example so the whole suite compiles a
# bounded program set (chunk shapes: update_pad-sized adds + one 4-wide
# remainder + 4-wide removal batches)
N_CAP, E_CAP = 48, 768
H_NODE, H_EDGE = 40, 512
INIT_EDGES = 90
UPDATE_PAD = 8
QUERIES = 8

MIN_SEMIRINGS = ("min_plus", "min_min", "max_times")

#: the differential matrix: every fused workload family, plus a
#: tight-capacity case that forces the overflow→exact fallback and a
#: closed-loop case where the controller's refresh decisions must also
#: replay identically.
CASES = {
    "pagerank": dict(algo="pagerank", kw={}),
    "ppr": dict(algo="personalized-pagerank", kw={"seeds": (2, 5)}),
    "sssp": dict(algo="sssp", kw={"sources": (0, 3)}),
    "cc": dict(algo="connected-components", kw={}),
    "widest": dict(algo="widest-path", kw={"sources": (1,)}),
    "pagerank-overflow": dict(algo="pagerank", kw={}, hot=(6, 12)),
    "sssp-quality": dict(algo="sssp", kw={"sources": (0,)}, quality=0.9),
}


def _make_sessions(case, src, dst, *, mesh=None, rebalance=None):
    """One async engine + one synchronous oracle, identically configured."""
    hot_n, hot_e = case.get("hot", (H_NODE, H_EDGE))
    common = dict(
        node_capacity=N_CAP, edge_capacity=E_CAP,
        hot_node_capacity=hot_n, hot_edge_capacity=hot_e,
        update_pad=UPDATE_PAD,
    )
    if case.get("quality") is not None:
        common["quality_target"] = case["quality"]
    if mesh is not None:
        common["mesh"] = mesh
        common["rebalance_threshold"] = rebalance
    mk = lambda ar: session((src, dst), case["algo"], async_rebuild=ar,
                            **common, **case["kw"])
    return mk(True), mk(False)


def _draw_script(rng, live_edges):
    """One random interleaving: per query, an optional add batch, an
    optional remove batch (always riding an add batch, so every mutating
    batch resolves and dispatches an epoch), and the OnQuery action.
    ``live_edges`` is the mutable host-side model of removable edges."""
    script = []
    for _ in range(QUERIES):
        adds, removes = [], []
        if rng.random() < 0.75:
            k = int(rng.choice([4, UPDATE_PAD]))
            adds.append((rng.integers(0, N_CAP, k).astype(np.int32),
                         rng.integers(0, N_CAP, k).astype(np.int32)))
            if live_edges and rng.random() < 0.5:
                take = min(4, len(live_edges))
                picks = [live_edges.pop(int(rng.integers(len(live_edges))))
                         for _ in range(take)]
                # pad to a fixed removal width of 4 with a definitely-dead
                # edge request, exercising the requested-but-unresolved
                # accounting without changing compiled shapes
                while len(picks) < 4:
                    picks.append(picks[-1])
                removes.append((
                    np.asarray([p[0] for p in picks], np.int32),
                    np.asarray([p[1] for p in picks], np.int32)))
        action = [Action.APPROXIMATE, Action.APPROXIMATE,
                  Action.APPROXIMATE, Action.EXACT,
                  Action.REPEAT_LAST][int(rng.integers(5))]
        script.append((adds, removes, action))
    return script


def _run_differential(case, seed, *, mesh=None, rebalance=None,
                      sum_algebra_bitwise=True):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, N_CAP, INIT_EDGES).astype(np.int32)
    dst = rng.integers(0, N_CAP, INIT_EDGES).astype(np.int32)
    # removable pool: unique initial edges (duplicate (s,d) pairs resolve
    # to one shared slot — removing both double-counts, so keep one)
    seen, live_edges = set(), []
    for s, d in zip(src.tolist(), dst.tolist()):
        if (s, d) not in seen:
            seen.add((s, d))
            live_edges.append((s, d))
    script = _draw_script(rng, live_edges)
    actions = [s[2] for s in script]

    sa, so = _make_sessions(case, src, dst, mesh=mesh, rebalance=rebalance)
    sa.engine._on_query = so.engine._on_query = (
        lambda qid, view: actions[qid])
    bitwise = (sa.algorithm.semiring in MIN_SEMIRINGS
               or sum_algebra_bitwise)

    # ---- async run, tracking the harness's own epoch model --------------
    latest = 0
    epoch_batches = {}  # epoch id -> the update batch it integrated
    async_rows = []
    for adds, removes, _action in script:
        batch = []
        for a, b in adds:
            sa.engine.register_add_edges(a, b)
            batch.append(("add", a, b))
        for a, b in removes:
            sa.engine.register_remove_edges(a, b)
            batch.append(("rm", a, b))
        res, row = sa.engine.query()
        served_epoch = latest  # promote happens before integrate
        if batch:
            latest += 1
            epoch_batches[latest] = batch
        assert row.epoch == served_epoch, (
            f"served epoch {row.epoch}, harness model says {served_epoch}")
        async_rows.append((res.copy(), row))

    # ---- oracle replay at the served epochs -----------------------------
    fed = 0
    for qid, (res_async, row) in enumerate(async_rows):
        while fed < row.epoch:
            fed += 1
            for kind, a, b in epoch_batches[fed]:
                if kind == "add":
                    so.engine.register_add_edges(a, b)
                else:
                    so.engine.register_remove_edges(a, b)
        res_oracle, row_oracle = so.engine.query()
        if bitwise:
            np.testing.assert_array_equal(
                res_async, res_oracle,
                err_msg=(f"query {qid} (epoch {row.epoch}, "
                         f"action {row.action}) diverged from the oracle"))
        else:
            np.testing.assert_allclose(
                res_async, res_oracle, rtol=1e-5, atol=1e-7,
                err_msg=(f"query {qid} (epoch {row.epoch}, "
                         f"action {row.action}) diverged from the oracle"))
        # overflow fallbacks and controller refreshes must replay too —
        # they change *which* program produced the answer
        assert row.overflow_fallback == row_oracle.overflow_fallback
        assert row.refreshed == row_oracle.refreshed
        # both paths size the summary buffers by the same ladder
        assert (row.hot_node_bucket, row.hot_edge_bucket) == (
            row_oracle.hot_node_bucket, row_oracle.hot_edge_bucket)
    return async_rows


# ---------------------------------------------------------------------------
# tentpole: the differential harness
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("case_name", sorted(CASES))
@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_async_engine_matches_sync_oracle(case_name, seed):
    """Every served answer equals the synchronous oracle at the served
    epoch — bitwise (min semirings AND meshless sum algebras: identical
    programs, identical inputs) across random interleavings of add /
    remove / approximate / exact / repeat-last / overflow-fallback /
    controller-refresh events."""
    _run_differential(CASES[case_name], seed)


@pytest.mark.parametrize("algorithm", ["pagerank", "sssp"])
def test_async_matches_sync_with_buckets_below_capacity(algorithm):
    """On a graph whose hot sets are a small share of the (default, full)
    capacities, the pipelined path runs the summary at the same
    hot-set-sized buckets as the sync path, and answers bitwise alike."""
    from repro.graph.generators import gnm_edges

    n, m, block = 3000, 20000, 25
    src, dst = gnm_edges(n, m, seed=9)
    kw = {"sources": (0,)} if algorithm == "sssp" else {}
    mk = lambda ar: session((src[:m - 4 * block], dst[:m - 4 * block]),
                            algorithm, node_capacity=n, async_rebuild=ar,
                            **kw)
    sa, so = mk(True), mk(False)
    cfg = so.engine.config
    blocks = [(src[lo:lo + block], dst[lo:lo + block])
              for lo in range(m - 4 * block, m, block)]
    # the async engine serves epoch q at query q+1: feed it one block
    # ahead and compare each answer with the oracle's at the same epoch
    # (both first answer epoch 0, so both ladders see the same counts)
    sa.engine.register_add_edges(*blocks[0])
    sa.engine.query()
    so.engine.query()
    for q, blk in enumerate(blocks):
        if q + 1 < len(blocks):
            sa.engine.register_add_edges(*blocks[q + 1])
        got, row = sa.engine.query()
        so.engine.register_add_edges(*blk)
        want, row_o = so.engine.query()
        assert row.epoch == q + 1
        np.testing.assert_array_equal(got, want)
        assert (row.hot_node_bucket, row.hot_edge_bucket) == (
            row_o.hot_node_bucket, row_o.hot_edge_bucket)
        assert row.hot_edge_bucket < cfg.hot_edge_capacity
        assert row.hot_node_bucket < cfg.hot_node_capacity
    assert sa.engine.bucket_growths == so.engine.bucket_growths


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="mesh case needs >= 2 devices "
                           "(CI forces 8 host devices)")
@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_async_mesh_rebalance_matches_oracle_bitwise(seed):
    """Sharded engines with live rebalancing: the async recut lands one
    epoch later than the sync engine's (the verdict is fetched at
    promotion), which only reorders ⊕ — so the min-semiring workloads
    must still match the oracle **bitwise** through recut epochs."""
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("d",))
    _run_differential(CASES["cc"], seed, mesh=mesh, rebalance=0.75)


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="mesh case needs >= 2 devices")
def test_async_mesh_sum_algebra_matches_oracle_allclose():
    """Mesh sum algebras across a deferred recut: allclose (FP reduction
    order differs at exactly the recut epoch, nowhere else)."""
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("d",))
    _run_differential(CASES["pagerank"], seed=7, mesh=mesh, rebalance=0.5,
                      sum_algebra_bitwise=False)


# ---------------------------------------------------------------------------
# satellite: epoch-invariant property suite
# ---------------------------------------------------------------------------


def _started_async(seed=0, **over):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, N_CAP, INIT_EDGES).astype(np.int32)
    dst = rng.integers(0, N_CAP, INIT_EDGES).astype(np.int32)
    over.setdefault("node_capacity", N_CAP)
    over.setdefault("edge_capacity", E_CAP)
    over.setdefault("hot_node_capacity", H_NODE)
    over.setdefault("hot_edge_capacity", H_EDGE)
    over.setdefault("update_pad", UPDATE_PAD)
    return session((src, dst), "pagerank", async_rebuild=True, **over), rng


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_epoch_ids_monotone_and_lag_bounded(seed):
    """(a) Epoch ids never decrease, advance by at most one per query,
    and snapshot_lag is always 0 or 1 (double buffering, by
    construction)."""
    s, rng = _started_async(seed)
    prev_epoch = 0
    for q in range(10):
        if rng.random() < 0.7:
            s.engine.register_add_edges(
                rng.integers(0, N_CAP, UPDATE_PAD).astype(np.int32),
                rng.integers(0, N_CAP, UPDATE_PAD).astype(np.int32))
        _, row = s.engine.query()
        assert row.epoch >= prev_epoch
        assert row.epoch - prev_epoch <= 1
        assert row.snapshot_lag in (0, 1)
        # no buffered mutations -> the engine must not invent an epoch
        if row.pending_applied == 0 and row.epoch > 0:
            assert row.epoch == prev_epoch
        prev_epoch = row.epoch


def test_snapshot_immutable_while_next_epoch_builds():
    """(b) No query reads a half-built summary: the served snapshot's
    graph buffers and sorted layouts are unchanged — value-identical on
    host — while later epochs apply updates and build past it."""
    s, rng = _started_async(3)
    eng = s.engine
    snap = eng._pipeline.current
    layouts = eng._snapshot_layouts(snap)
    frozen = jax.device_get({
        "src": snap.state.src, "dst": snap.state.dst,
        "alive": snap.state.edge_alive, "num_edges": snap.state.num_edges,
        "out_deg": snap.state.out_deg, "deg": snap.deg,
        "lay_dst": layouts[0].dst, "lay_w": layouts[0].weight,
    })
    for _ in range(4):  # several epochs of churn past the frozen snapshot
        eng.register_add_edges(
            rng.integers(0, N_CAP, UPDATE_PAD).astype(np.int32),
            rng.integers(0, N_CAP, UPDATE_PAD).astype(np.int32))
        eng.query()
    after = jax.device_get({
        "src": snap.state.src, "dst": snap.state.dst,
        "alive": snap.state.edge_alive, "num_edges": snap.state.num_edges,
        "out_deg": snap.state.out_deg, "deg": snap.deg,
        "lay_dst": layouts[0].dst, "lay_w": layouts[0].weight,
    })
    for key in frozen:
        np.testing.assert_array_equal(
            frozen[key], after[key],
            err_msg=f"snapshot buffer {key!r} mutated under async builds")
    # and the snapshot's layout cache never rebuilds: same objects
    assert eng._snapshot_layouts(snap)[0] is layouts[0]


def test_promotion_never_skips_or_overwrites_a_build():
    """(c) Pipeline discipline: a dispatched build must be promoted
    before the next dispatch; epoch ids must be successors; after a
    drained stream promotions == dispatches (nothing lost)."""
    state = G.from_edges(np.asarray([0, 1], np.int32),
                         np.asarray([1, 2], np.int32), 8, 16)

    def snap(epoch):
        return EpochSnapshot(
            epoch=epoch, state=state,
            deg=state.out_deg, active=state.node_active,
            counts=snapshot_counts(state))

    pipe = AsyncRebuildPipeline(snap(0))
    assert pipe.promote() is None  # nothing in flight: promote is a no-op
    pipe.dispatch(snap(1))
    assert pipe.snapshot_lag == 1
    with pytest.raises(RuntimeError, match="never +promoted"):
        pipe.dispatch(snap(2))  # would overwrite (= skip) epoch 1
    promoted = pipe.promote()
    assert promoted is not None and promoted.epoch == 1
    assert pipe.current is promoted and pipe.snapshot_lag == 0
    with pytest.raises(RuntimeError, match="non-monotone"):
        pipe.dispatch(snap(3))  # 1 -> 3 skips epoch 2
    pipe.dispatch(snap(2))
    pipe.promote()
    assert pipe.promotions == pipe.dispatches == 2

    # the engine end-to-end: epochs promoted == epochs dispatched once
    # the stream drains (every build became a served epoch)
    s, rng = _started_async(11)
    for _ in range(6):
        s.engine.register_add_edges(
            rng.integers(0, N_CAP, 4).astype(np.int32),
            rng.integers(0, N_CAP, 4).astype(np.int32))
        s.engine.query()
    s.engine.query()  # boundary with nothing pending: promotes the last build
    epipe = s.engine._pipeline
    assert epipe.building is None
    assert epipe.promotions == epipe.dispatches == epipe.current.epoch


def test_drift_charged_to_the_epoch_that_accumulated_it():
    """(d) A huge buffered burst must not leak into the quiet epoch
    being served: the query that *dispatches* the burst still reports
    epoch N with (near-)zero drift, and the burst's churn lands on the
    next row, stamped epoch N+1."""
    s, rng = _started_async(5, quality_target=0.9)
    eng = s.engine
    quiet_rows = [eng.query()[1] for _ in range(3)]  # settle, no updates
    quiet = quiet_rows[-1]
    assert quiet.epoch == 0 and quiet.pending_applied == 0

    burst = 4 * UPDATE_PAD  # several chunks of fresh churn
    eng.register_add_edges(
        rng.integers(0, N_CAP, burst).astype(np.int32),
        rng.integers(0, N_CAP, burst).astype(np.int32))
    _, dispatch_row = eng.query()  # serves quiet epoch 0, dispatches 1
    _, visible_row = eng.query()   # serves epoch 1: the burst is visible

    assert dispatch_row.epoch == quiet.epoch
    assert visible_row.epoch == quiet.epoch + 1
    assert visible_row.pending_applied == burst
    # the quiet epoch's row reads like the quiet baseline: no burst
    # drift, no controller reaction
    assert dispatch_row.drift == pytest.approx(quiet.drift, abs=1e-6)
    assert not dispatch_row.refreshed
    # ...and the churn is charged to the epoch that integrated it — the
    # controller reacts on N+1's row (an SLO-breach refresh if the burst
    # blew the budget, a raw drift reading otherwise)
    assert visible_row.refreshed or visible_row.drift > dispatch_row.drift


def test_unresolved_removals_report_on_the_current_row():
    """A removal batch that matches no live edge mutates nothing: no new
    epoch is dispatched, and the request surfaces on the row that
    processed it instead of vanishing."""
    s, _ = _started_async(9)
    s.engine.register_remove_edges(
        np.asarray([N_CAP - 1] * 4, np.int32),
        np.asarray([N_CAP - 1] * 4, np.int32))
    _, row = s.engine.query()
    assert row.epoch == 0 and row.removals_requested == 4
    assert s.engine._pipeline.building is None
    _, row2 = s.engine.query()
    assert row2.epoch == 0  # still nothing to promote


def test_async_requires_fused_path():
    with pytest.raises(ValueError, match="async_rebuild requires"):
        _started_async(0, fused=False)


# ---------------------------------------------------------------------------
# serving: the wave loop on the same pipeline
# ---------------------------------------------------------------------------


def test_serving_waves_promote_at_boundaries_and_match_semantics():
    """The serving engine serves whole waves from one snapshot: updates
    buffered mid-wave become visible exactly one wave later, and the
    ServeStats epoch/lag columns track the pipeline."""
    rng = np.random.default_rng(0)
    src = rng.integers(0, N_CAP, INIT_EDGES).astype(np.int32)
    dst = rng.integers(0, N_CAP, INIT_EDGES).astype(np.int32)
    srv = serve_session((src, dst), slots=2,
                        node_capacity=N_CAP, edge_capacity=E_CAP,
                        hot_node_capacity=H_NODE, hot_edge_capacity=H_EDGE,
                        update_pad=UPDATE_PAD, async_rebuild=True)
    t0 = srv.submit("personalized-pagerank", seeds=(3,))
    srv.step()
    assert t0.done and srv.stats.epoch == 0
    srv.add_edges(rng.integers(0, N_CAP, UPDATE_PAD).astype(np.int32),
                  rng.integers(0, N_CAP, UPDATE_PAD).astype(np.int32))
    t1 = srv.submit("personalized-pagerank", seeds=(3,))
    srv.step()  # dispatched the build, but this wave still served epoch 0
    assert t1.done and srv.stats.epoch == 0 and srv.stats.snapshot_lag == 1
    np.testing.assert_array_equal(t0.result, t1.result)
    t2 = srv.submit("personalized-pagerank", seeds=(3,))
    srv.step()  # the promotion boundary: updates visible now
    assert t2.done and srv.stats.epoch == 1 and srv.stats.snapshot_lag == 0
    assert not np.array_equal(t1.result, t2.result)

    # differential: a sync serving engine fed the same updates *before*
    # the wave that serves them answers identically at that epoch
    srv_sync = serve_session((src, dst), slots=2,
                             node_capacity=N_CAP, edge_capacity=E_CAP,
                             hot_node_capacity=H_NODE,
                             hot_edge_capacity=H_EDGE,
                             update_pad=UPDATE_PAD, async_rebuild=False)
    u0 = srv_sync.submit("personalized-pagerank", seeds=(3,))
    srv_sync.step()
    np.testing.assert_array_equal(t0.result, u0.result)


def test_bench_sweeps_records_async_overlap_acceptance():
    """BENCH_sweeps.json carries the ISSUE 10 acceptance numbers: query
    p95 during a write burst is better on the async engine than the sync
    one (the deferred rebuild drains into inter-query think-time)."""
    root = Path(__file__).resolve().parent.parent
    record = json.loads((root / "BENCH_sweeps.json").read_text())
    overlap = record["meta"]["async_overlap"]
    assert overlap["async_p95_us"] < overlap["sync_p95_us"]
    assert overlap["p95_speedup"] > 1.0
    names = {row["name"] for row in record["rows"]}
    assert {"async_overlap_sync_query_p50",
            "async_overlap_sync_query_p95",
            "async_overlap_async_query_p50",
            "async_overlap_async_query_p95"} <= names
