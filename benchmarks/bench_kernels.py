"""Kernel microbenchmarks (CPU wall time of the jnp paths;
Pallas kernels are TPU-target and validated in interpret mode, so CPU wall
times here measure the reference implementations the dry-run lowers).

Emits ``name,us_per_call,derived`` CSV rows.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _bench(fn, *args, iters=10, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6  # us


def bench_pagerank_iteration():
    from repro.graph import from_edges
    from repro.graph.generators import gnm_edges
    from repro.core.pagerank import pagerank
    src, dst = gnm_edges(50_000, 500_000, seed=0)
    g = from_edges(src, dst, 50_000, 520_000)
    fn = jax.jit(lambda s: pagerank(s, num_iters=30)[0])
    us = _bench(fn, g, iters=3)
    return [("pagerank_exact_30it_500k_edges", us,
             f"{30*520_000/(us/1e6)/1e9:.2f}Gedge/s")]


def sweep_tune_specs(nodes=50_000, edges=500_000):
    """Autotune keys for the sweep-fixture layouts.  ``run.py --autotune
    full`` measures these into ``benchmarks/autotune_cache.json`` so the
    sweep rows replay tuned geometry from the committed cache."""
    cap = edges + 20_000
    return [
        dict(edge_capacity=cap, num_segments=nodes, reduce="sum"),
        dict(edge_capacity=cap, num_segments=nodes, reduce="min"),
    ]


def _tuned_geometry(g, reduce):
    """Cached-mode geometry for a sweep-fixture layout (the committed
    autotune cache answers when loaded; the analytic argmin otherwise —
    the same resolution the engine does at layout-build time)."""
    from repro.kernels.spmv import autotune as AT

    tile_n, chunk = AT.tune_for_push(
        edge_capacity=g.edge_capacity, num_segments=g.node_capacity,
        reduce=reduce, mode="cached")
    return dict(tile_n=tile_n, chunk=chunk)


def _sweep_fixture(nodes=50_000, edges=500_000):
    """The 500k-edge reference graph + everything a sweep bench needs."""
    from repro.graph import from_edges
    from repro.graph.generators import gnm_edges
    from repro.core import backend as B
    from repro.core.pagerank import build_summary, pagerank

    src, dst = gnm_edges(nodes, edges, seed=0)
    g = from_edges(src, dst, nodes, edges + 20_000)
    layout = B.build_layout(g, weight="inv_out", **_tuned_geometry(g, "sum"))
    ranks, _ = pagerank(g, num_iters=5)
    hot = jnp.asarray(
        np.random.default_rng(0).random(nodes) < 0.15)
    summary = build_summary(g, ranks, hot, hot_node_capacity=8192,
                            hot_edge_capacity=65536)
    return g, layout, ranks, summary


def _minplus_fixture(g):
    """min-plus (SSSP) operands over the same reference graph: a length
    layout, warm distances from a few relaxations, and a min_plus summary."""
    from repro.core import backend as B
    from repro.core.pagerank import build_summary
    from repro.core.traversal import sssp

    nodes = g.node_capacity
    layout = B.build_layout(g, weight="length", semiring="min_plus",
                            **_tuned_geometry(g, "min"))
    source = jnp.zeros((nodes,), bool).at[0].set(True)
    dist, _ = sssp(g, source, num_iters=3, layout=layout,
                   backend="segment_sum")
    hot = jnp.asarray(np.random.default_rng(1).random(nodes) < 0.15)
    summary = build_summary(g, dist, hot, hot_node_capacity=8192,
                            hot_edge_capacity=65536, weight="length",
                            semiring="min_plus")
    return layout, dist, source, summary


def _sharded_cases(g, ranks, live_edges, *, iters, shard_counts=(2, 4, 8)):
    """Sharded push rows (the shard_map partial-push + psum backend): one
    row per host-shard count.  When the process has >= S devices (the CI
    sharded job forces 8 host devices) the row measures the real
    shard_map-ed path over an S-device mesh; otherwise the on-device
    shard-loop reference path — the tag records which.
    """
    from jax.sharding import Mesh
    from repro.core import backend as B
    from repro.graph.partition import (build_sharded_layout,
                                       place_sharded_layout)

    cases = []
    for s_count in shard_counts:
        mesh = None
        if jax.device_count() >= s_count:
            mesh = Mesh(np.asarray(jax.devices()[:s_count]), ("shards",))
        # place once, like the engine cache — otherwise the timed calls
        # would measure per-call redistribution of the edge stream
        layout_s = place_sharded_layout(build_sharded_layout(
            g, mesh=mesh, num_shards=s_count, weight="inv_out"))
        fn = jax.jit(lambda r, lay: B.push(r, lay, backend="segment_sum"))
        us = _bench(fn, ranks, layout_s, iters=iters, warmup=1)
        tag = "mesh" if mesh is not None else "loop"
        cases.append((f"push_sharded_s{s_count}_{tag}", us,
                      f"{live_edges / (us / 1e6) / 1e9:.3f}Gedge/s"))
    return cases


def _sharded_summary_cases(g, ranks, *, iters, sweep_iters, num_shards=8):
    """Sharded-summary + rebalance rows: the distributed-bucket-sort
    ``build_summary`` (vs the replicated compaction, same hot mask), the
    summarized sweep over the resulting per-shard E_K layout, and the
    rebalance recut (counts + imbalance + balanced re-deal).  Mesh path
    when the process has the devices, the shard-loop reference otherwise —
    the tag records which, mirroring the sharded-push rows."""
    from jax.sharding import Mesh
    from repro.core.pagerank import build_summary, summarized_pagerank
    from repro.graph.partition import (balanced_shard_slots,
                                       build_sharded_layout,
                                       place_sharded_layout,
                                       rebalance_sharded_layout)

    nodes = g.node_capacity
    mesh = None
    if jax.device_count() >= num_shards:
        mesh = Mesh(np.asarray(jax.devices()[:num_shards]), ("shards",))
    tag = "mesh" if mesh is not None else "loop"
    layout_s = place_sharded_layout(build_sharded_layout(
        g, mesh=mesh, num_shards=num_shards, weight="inv_out"))
    hot = jnp.asarray(np.random.default_rng(0).random(nodes) < 0.15)
    caps = dict(hot_node_capacity=8192, hot_edge_capacity=65536)

    cases = []
    build_rep = jax.jit(lambda s, r, h: build_summary(s, r, h, **caps))
    us = _bench(build_rep, g, ranks, hot, iters=iters, warmup=1)
    cases.append(("build_summary_replicated", us, "E-space compaction"))
    build_sh = jax.jit(lambda s, r, h, lay: build_summary(
        s, r, h, **caps, layout=lay))
    us = _bench(build_sh, g, ranks, hot, layout_s, iters=iters, warmup=1)
    cases.append((f"build_summary_sharded_s{num_shards}_{tag}", us,
                  "distributed bucket sort"))

    summary_s = build_summary(g, ranks, hot, **caps, layout=layout_s)
    fn = jax.jit(lambda s, r: summarized_pagerank(
        s, r, num_iters=sweep_iters)[0])
    us = _bench(fn, summary_s, ranks, iters=iters, warmup=1)
    cases.append((f"summarized_sweep_sharded_s{num_shards}_{tag}_"
                  f"{sweep_iters}it", us,
                  f"|K|={int(summary_s.num_hot)},"
                  f"|E_K|={int(summary_s.num_ek)}"))

    recut = jax.jit(lambda s: balanced_shard_slots(s, num_shards=num_shards))
    us = _bench(recut, g, iters=iters, warmup=1)
    cases.append((f"rebalance_recut_s{num_shards}", us,
                  "balanced_shard_slots deal"))
    # the full detect-and-recut front door, host round-trip included (what
    # the engine pays once per applied update batch); warm up once so the
    # row measures steady state, not jit compilation, like every other row
    rebalance_sharded_layout(g, num_shards=num_shards, threshold=0.0)
    t0 = time.perf_counter()
    for _ in range(iters):
        _, rebalanced, imb = rebalance_sharded_layout(
            g, num_shards=num_shards, threshold=0.0)
    us = (time.perf_counter() - t0) / iters * 1e6
    cases.append((f"rebalance_detect_s{num_shards}", us,
                  f"imbalance={imb:.3f},recut={rebalanced}"))
    return cases


def _serving_cases(g, ranks, live_edges, *, iters, batch_sizes=(1, 8, 32)):
    """Multi-tenant serving rows: the batched ``[B, N]`` push vs the B-way
    loop of single pushes over the same layout, per batch size, plus one
    end-to-end serving-engine throughput row.  The derived column is
    queries per second (B pushes answered per call for the push rows;
    completed queries over wave wall time for the engine row) — the
    continuous-batching engine's case rests on the batched rows beating
    the looped ones at B >= 8.
    """
    from repro.core import backend as B

    layout = B.build_layout(g, weight="inv_out")
    nodes = g.node_capacity
    rng = np.random.default_rng(7)

    cases = []
    for bsz in batch_sizes:
        vals = jnp.asarray(rng.random((bsz, nodes), np.float32))
        batched = jax.jit(lambda v, lay: B.push(v, lay,
                                                backend="segment_sum"))
        us = _bench(batched, vals, layout, iters=iters, warmup=1)
        cases.append((f"serving_push_batched_b{bsz}", us,
                      f"{bsz / (us / 1e6):.0f}q/s"))
        looped = jax.jit(lambda v, lay, n=bsz: jnp.stack(
            [B.push(v[i], lay, backend="segment_sum") for i in range(n)]))
        us = _bench(looped, vals, layout, iters=iters, warmup=1)
        cases.append((f"serving_push_looped_b{bsz}", us,
                      f"{bsz / (us / 1e6):.0f}q/s"))

    # end-to-end: a slot-4 serving engine draining 8 PPR + 4 SSSP queries
    # over a smaller graph (full waves, refill, harvest — wall time is
    # dominated by trace/compile on the first wave, so report steady state
    # by timing a second drain on the warm engine)
    from repro.api import serve_session
    from repro.graph.generators import gnm_edges

    s_src, s_dst = gnm_edges(2_000, 16_000, seed=3)
    srv = serve_session((s_src, s_dst), slots=4,
                        hot_node_capacity=2_048, hot_edge_capacity=32_768)
    def _drain():
        for s in range(8):
            srv.submit("personalized-pagerank", seeds=(s,))
        for s in range(4):
            srv.submit("sssp", sources=(s,))
        srv.run()
    _drain()  # warm: traces the two lane programs
    waves0, wall0 = srv.stats.waves, srv.stats.wall_s
    t0 = time.perf_counter()
    _drain()
    us = (time.perf_counter() - t0) * 1e6
    waves = srv.stats.waves - waves0
    cases.append(("serving_engine_slots4_12q", us,
                  f"{12 / (us / 1e6):.1f}q/s,{waves}waves"))
    srv.close()
    return cases


def _controller_cases(*, smoke: bool = False):
    """``controller_*`` rows: the closed accuracy loop vs open-loop
    full accuracy on one drifting synthetic stream.

    Three sessions replay the identical stream: an exact oracle
    (ground-truth ranks per step), a ``quality_target=0.95`` closed-loop
    session, and the open-loop full-accuracy configuration (r=0, tiny Δ
    — every churned vertex hot).  Per step we score the approximate
    ranks against the oracle with RBO@100 and charge summarized work as
    E_K + E_B pushed edges (refresh/fallback steps charge the full live
    edge count — the controller pays for its exact recomputes).  The
    returned meta dict carries the acceptance numbers ISSUE 9 pins:
    closed-loop quality >= target with work strictly below open loop.
    """
    from repro.api import Action, session
    from repro.graph.generators import gnm_edges
    from repro.metrics.rbo import rbo_from_scores

    n, m = (600, 4_000) if smoke else (2_000, 16_000)
    steps = 4 if smoke else 10
    chunk = 60 if smoke else 200
    src, dst = gnm_edges(n, m, seed=7)
    rng = np.random.default_rng(11)
    stream = [(rng.integers(0, n, chunk).astype(np.int32),
               rng.integers(0, n, chunk).astype(np.int32))
              for _ in range(steps)]
    caps = dict(node_capacity=n, edge_capacity=m + steps * chunk + 1024)

    def _replay(label, **kw):
        scores, works, wall = [], [], 0.0
        with session((src, dst), algorithm="pagerank", **caps, **kw) as s:
            for a, b in stream:
                s.add_edges(a, b)
                t0 = time.perf_counter()
                res = s.query()
                wall += time.perf_counter() - t0
                st = res.stats
                full = (st.action == "exact" or st.overflow_fallback
                        or getattr(st, "refreshed", False))
                works.append(st.num_edges if full else st.num_ek + st.num_eb)
                scores.append(np.asarray(res.scores))
        return scores, works, wall / steps * 1e6

    exact_scores, _, _ = _replay(
        "exact", on_query=lambda qid, view: Action.EXACT)
    ctl_scores, ctl_work, ctl_us = _replay(
        "closedloop", quality_target=0.95)
    ol_scores, ol_work, ol_us = _replay(
        "openloop", r=0.0, delta=1e-6)

    active = exact_scores[-1] > -np.inf  # all rows; RBO masks via scores
    def _quality(series):
        vals = [float(rbo_from_scores(jnp.asarray(s), jnp.asarray(e),
                                      depth=100))
                for s, e in zip(series, exact_scores)]
        return float(np.mean(vals)), float(np.min(vals))

    q_ctl, q_ctl_min = _quality(ctl_scores)
    q_ol, _ = _quality(ol_scores)
    w_ctl = float(np.mean(ctl_work))
    w_ol = float(np.mean(ol_work))
    cases = [
        ("controller_closedloop_query", ctl_us,
         f"q={q_ctl:.4f},min={q_ctl_min:.4f},work={w_ctl:.0f}e/q"),
        ("controller_openloop_full_query", ol_us,
         f"q={q_ol:.4f},work={w_ol:.0f}e/q"),
    ]
    meta = {
        "quality_target": 0.95,
        "quality": q_ctl,
        "quality_min": q_ctl_min,
        "work_per_query": w_ctl,
        "openloop_quality": q_ol,
        "openloop_work_per_query": w_ol,
        "stream": {"nodes": n, "edges": m, "steps": steps, "chunk": chunk},
    }
    return cases, meta


def _async_overlap_cases(*, smoke: bool = False):
    """``async_overlap_*`` rows: query latency during a write burst,
    synchronous vs async-rebuild (``async_rebuild=True``) engine.

    Two sessions replay the identical stream — every query preceded by a
    ``chunk``-edge write burst, every query followed by a fixed host
    think-time (the inter-query gap a serving loop naturally has).  The
    sync engine pays layout sort + summary rebuild inside ``query()``;
    the async engine dispatches the same rebuild un-awaited, so it drains
    into the think-time gap and the measured ``query()`` wall collapses
    to the fused step + stats fetch.  Rows are query-wall p50/p95 per
    mode; the meta dict carries the ISSUE 10 acceptance number (async
    p95 < sync p95 under the burst).
    """
    from repro.api import session
    from repro.graph.generators import gnm_edges

    n, m = (4_000, 30_000) if smoke else (20_000, 120_000)
    steps = 8 if smoke else 30
    chunk = 256 if smoke else 1024
    # think-time sized to absorb the deferred rebuild (~35ms of layout
    # sort + preserving apply at the full config): shorter gaps push the
    # un-drained remainder onto the next query's fetch and the async
    # advantage shrinks toward zero
    think_s = 0.05
    src, dst = gnm_edges(n, m, seed=5)
    rng = np.random.default_rng(3)
    stream = [(rng.integers(0, n, chunk).astype(np.int32),
               rng.integers(0, n, chunk).astype(np.int32))
              for _ in range(steps)]
    caps = dict(node_capacity=n, edge_capacity=m + steps * chunk + 1024,
                update_pad=chunk)

    def _replay(async_rebuild):
        lats = []
        with session((src, dst), algorithm="pagerank",
                     async_rebuild=async_rebuild, **caps) as s:
            for a, b in stream:
                s.add_edges(a, b)
                t0 = time.perf_counter()
                s.query()
                lats.append(time.perf_counter() - t0)
                time.sleep(think_s)   # think-time: async dispatch drains here
        return np.asarray(lats[2:]) * 1e6  # drop compile warm-up queries

    sync_us = _replay(False)
    async_us = _replay(True)
    pct = lambda a, q: float(np.percentile(a, q))
    s50, s95 = pct(sync_us, 50), pct(sync_us, 95)
    a50, a95 = pct(async_us, 50), pct(async_us, 95)
    burst = f"burst={chunk}e,think={think_s * 1e3:.0f}ms"
    cases = [
        ("async_overlap_sync_query_p50", s50, burst),
        ("async_overlap_sync_query_p95", s95, burst),
        ("async_overlap_async_query_p50", a50,
         f"{burst},x{s50 / a50:.2f} vs sync"),
        ("async_overlap_async_query_p95", a95,
         f"{burst},x{s95 / a95:.2f} vs sync"),
    ]
    meta = {
        "stream": {"nodes": n, "edges": m, "steps": steps, "chunk": chunk},
        "think_time_us": think_s * 1e6,
        "sync_p50_us": s50, "sync_p95_us": s95,
        "async_p50_us": a50, "async_p95_us": a95,
        "p95_speedup": s95 / a95,
    }
    return cases, meta


def bench_sweep_backends(*, smoke: bool = False, nodes=50_000, edges=500_000):
    """Backend-vs-backend rows: a plus_times push + summarized PageRank
    sweep, and a min_plus push + summarized SSSP sweep, per backend on the
    500k-edge reference graph, plus sharded-push rows over 2/4/8 host
    shards, the sharded-summary / rebalance rows (distributed bucket
    sort vs replicated compaction, recut cost), and the serving rows
    (batched [B, N] push vs the B-way loop, engine throughput).  The
    pallas rows run in interpret mode off-TPU — they track kernel-logic
    cost trajectory, not TPU wall time (the dry-run covers that); the
    min_plus rows exercise the masked-reduce kernel variant instead of
    the one-hot matmul.
    Returns (rows, records); the records feed BENCH_sweeps.json.
    """
    from repro.core import backend as B
    from repro.core.pagerank import summarized_pagerank
    from repro.core.traversal import summarized_sssp

    g, layout, ranks, summary = _sweep_fixture(nodes, edges)
    mp_layout, dist, source, mp_summary = _minplus_fixture(g)
    iters = 1 if smoke else 3
    sweep_iters = 1 if smoke else 30
    interpret = B.default_interpret()
    live_edges = int(g.num_live_edges())

    cases = []
    for backend in ("segment_sum", "pallas"):
        tag = f"{backend}{'_interp' if backend == 'pallas' and interpret else ''}"
        push_fn = jax.jit(lambda r, lay, b=backend: B.push(
            r, lay, backend=b, interpret=interpret))
        us = _bench(push_fn, ranks, layout, iters=iters, warmup=1)
        cases.append((f"push_exact_{tag}_{edges // 1000}k", us,
                      f"{live_edges / (us / 1e6) / 1e9:.3f}Gedge/s"))
        summ_fn = jax.jit(lambda s, r, b=backend: summarized_pagerank(
            s, r, num_iters=sweep_iters, backend=b)[0])
        us = _bench(summ_fn, summary, ranks, iters=iters, warmup=1)
        cases.append((f"summarized_sweep_{sweep_iters}it_{tag}", us,
                      f"|K|={int(summary.num_hot)},|E_K|={int(summary.num_ek)}"))
        mp_push_fn = jax.jit(lambda d, lay, b=backend: B.push(
            d, lay, semiring="min_plus", backend=b, interpret=interpret))
        us = _bench(mp_push_fn, dist, mp_layout, iters=iters, warmup=1)
        cases.append((f"push_minplus_{tag}_{edges // 1000}k", us,
                      f"{live_edges / (us / 1e6) / 1e9:.3f}Gedge/s"))
        mp_sweep_fn = jax.jit(lambda s, d, m, b=backend: summarized_sssp(
            s, d, m, num_iters=sweep_iters, backend=b)[0])
        us = _bench(mp_sweep_fn, mp_summary, dist, source, iters=iters,
                    warmup=1)
        cases.append((f"summarized_sssp_{sweep_iters}it_{tag}", us,
                      f"|K|={int(mp_summary.num_hot)},"
                      f"|E_K|={int(mp_summary.num_ek)}"))
    cases.extend(_sharded_cases(g, ranks, live_edges, iters=iters))
    cases.extend(_sharded_summary_cases(g, ranks, iters=iters,
                                        sweep_iters=sweep_iters))
    cases.extend(_serving_cases(g, ranks, live_edges, iters=iters))
    controller_cases, controller_meta = _controller_cases(smoke=smoke)
    cases.extend(controller_cases)
    overlap_cases, overlap_meta = _async_overlap_cases(smoke=smoke)
    cases.extend(overlap_cases)
    records = [
        {"name": name, "us_per_call": round(us, 1), "derived": derived,
         # pallas rows carry _interp in the name when they ran in interpret
         # mode; everything else (and on-TPU pallas) is a compiled timing
         "mode": "interpret" if "_interp" in name else "compiled"}
        for name, us, derived in cases
    ]
    meta = {
        "graph": {"nodes": nodes, "edges": edges, "live_edges": live_edges},
        "interpret": interpret,
        "device": jax.default_backend(),
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "device_count": jax.device_count(),
        "smoke": smoke,
        "sweep_iters": sweep_iters,
        # geometry the full-graph push layouts were built with (autotuned
        # when benchmarks/autotune_cache.json was loaded first)
        "push_geometry": {
            "plus_times": [layout.tile_n, layout.tile_chunk],
            "min_plus": [mp_layout.tile_n, mp_layout.tile_chunk],
        },
        # ISSUE 9 acceptance numbers: closed-loop quality/work vs the
        # open-loop full-accuracy replay of the same drifting stream
        "controller": controller_meta,
        # ISSUE 10 acceptance numbers: query p50/p95 during a write
        # burst, sync vs async-rebuild engine (async p95 must win)
        "async_overlap": overlap_meta,
    }
    return cases, {"meta": meta, "rows": records}


def bench_kernel_matrix(*, smoke: bool = False, autotune_mode: str = "cached"):
    """``--only kernels``: the per-geometry kernel matrix.

    Times both kernel variants — the one-hot-matmul sum push and the
    segmented-scan masked reduce — across the autotuner's ``(tile_n,
    chunk)`` candidate grid on a synthetic sorted edge stream, then pits
    the autotuned geometry against the hardcoded ``(TILE_N, CHUNK)``
    defaults on a summary-shaped stream (small destination space) where
    the defaults leave time on the table.  Off-TPU the kernels run in
    interpret mode; rows are tagged so the artifact records which.

    ``autotune_mode`` is the :func:`repro.kernels.spmv.autotune.tune` mode
    used for the tuned-vs-default rows: ``"full"`` times the whole pruned
    candidate grid (this is how ``benchmarks/autotune_cache.json`` is
    regenerated), ``"cached"`` replays a loaded cache (the CI smoke path).

    Returns (rows, record) shaped like :func:`bench_sweep_backends`.
    """
    from repro.core import backend as B
    from repro.kernels.spmv import autotune as AT
    from repro.kernels.spmv.kernel import CHUNK, TILE_N

    interpret = B.default_interpret()
    itag = "_interp" if interpret else ""
    iters = 1 if smoke else 3
    platform = jax.default_backend()

    # matrix shape: mid-sized stream, full destination space
    mx_n, mx_e = (2_048, 16_384) if smoke else (8_192, 131_072)
    tiles = (128, 512) if smoke else AT.TILE_N_CANDIDATES
    chunks = (256, 1024) if smoke else AT.CHUNK_CANDIDATES

    cases = []
    for reduce in ("sum", "min"):
        key = AT.TuneKey(e_pad=mx_e, n=mx_n, b=1, dtype="float32",
                         reduce=reduce, platform=platform)
        for tile_n in tiles:
            for chunk in chunks:
                cost = AT.modeled_push_cost(
                    e_pad=mx_e, n=mx_n, reduce=reduce,
                    tile_n=tile_n, chunk=chunk)
                if cost.vmem_bytes > AT.VMEM_LIMIT_BYTES:
                    continue
                us = AT._time_candidate(key, tile_n, chunk,
                                        interpret=interpret,
                                        iters=iters) * 1e6
                cases.append((
                    f"kernel_{reduce}_t{tile_n}_c{chunk}{itag}", us,
                    f"modeled={cost.bound_time_s * 1e6:.2f}us,"
                    f"hbm={cost.hbm_bytes / 1e6:.2f}MB"))

    # tuned vs hardcoded defaults on a non-default (summary-shaped) stream.
    # The shape is identical in smoke and full runs so the committed
    # autotune cache covers the CI smoke replay.
    cmp_n, cmp_e = 1_024, 65_536
    for reduce in ("sum", "min"):
        key = AT.TuneKey(e_pad=cmp_e, n=cmp_n, b=1, dtype="float32",
                         reduce=reduce, platform=platform)
        tile_t, chunk_t = AT.tune(key, autotune_mode, measure_top=99)
        us_t = AT._time_candidate(key, tile_t, chunk_t,
                                  interpret=interpret, iters=iters) * 1e6
        us_d = AT._time_candidate(key, TILE_N, CHUNK,
                                  interpret=interpret, iters=iters) * 1e6
        cases.append((f"kernel_{reduce}_tuned_summary1k{itag}", us_t,
                      f"t{tile_t}xc{chunk_t},{us_d / us_t:.2f}x vs default"))
        cases.append((f"kernel_{reduce}_default_summary1k{itag}", us_d,
                      f"t{TILE_N}xc{CHUNK}"))

    records = [
        {"name": name, "us_per_call": round(us, 1), "derived": derived,
         "mode": "interpret" if "_interp" in name else "compiled"}
        for name, us, derived in cases
    ]
    meta = {
        "matrix_shape": {"nodes": mx_n, "edges": mx_e},
        "compare_shape": {"nodes": cmp_n, "edges": cmp_e},
        "interpret": interpret,
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "smoke": smoke,
        "autotune_mode": autotune_mode,
    }
    return cases, {"meta": meta, "rows": records}


# bench_sweep_backends is invoked by benchmarks.run (it also feeds the
# BENCH_sweeps.json artifact), not by the CSV-only main() below.
ALL = [bench_pagerank_iteration]


def main():
    rows = []
    for b in ALL:
        rows.extend(b())
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    return rows


if __name__ == "__main__":
    main()
