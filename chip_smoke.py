"""Run VeilGraph's main path on a TPU and check every answer it gives.

    python chip_smoke.py              # one chip: PageRank, SSSP, serving
    python chip_smoke.py --chips 4    # the sharded path on four chips, only

The graph is a 1M-vertex Barabási–Albert web-graph stand-in (~20.8M
edges, the scale of eu-2005, the largest graph of the paper's Table 1),
generated from ``--seed``.  Three chunks of 20k edges are held out as the
update stream.  Every phase goes through the user entry points
(``repro.session`` / ``repro.serve_session``) with the default backend,
which must resolve to the compiled Pallas kernels, and is compared with
the same calls on the ``segment_sum`` backend and with numpy references:

1. the device, the JAX and libtpu versions, the compile cache directory;
2. streaming PageRank: three chunks with a summarized query after each
   (allclose to ``segment_sum``), one exact push against ``np.bincount``,
   and RBO@1000 of each query against exact PageRank (printed only);
3. streaming SSSP from vertex 0: bitwise equal to ``segment_sum``, and one
   ``min_plus`` push bitwise equal to ``np.minimum.at``;
4. ``serve_session(slots=4)`` with 4 personalized-PageRank and 2 SSSP
   tickets (the batched kernels) against the same tickets on
   ``segment_sum``;
5. compile seconds and peak device memory (set-up facts, not speed).

``--chips 4`` runs only the mesh path: PageRank (allclose) and SSSP
(bitwise) sessions on ``jax.make_mesh((4,), ("shards",))`` against
mesh-less sessions on one of those chips, printing the rebalance count and
the live edges per shard with the device that holds each shard.

The last line of standard output is one JSON object naming the device.  The
script exits non-zero, without that line, when JAX finds no TPU, when it is
run without the rest of the repository, or on any mismatch.  Everything
runs in this one process.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from importlib import metadata
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402

import repro  # noqa: E402
from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.core import Action  # noqa: E402
from repro.core import backend as B  # noqa: E402
from repro.core.policies import always  # noqa: E402
from repro.graph.generators import barabasi_albert_edges  # noqa: E402
from repro.metrics import rbo_from_scores  # noqa: E402
from repro.stream import StreamConfig, build_stream  # noqa: E402

N_VERTICES = 1_000_000
OUT_EDGES = 16
CHUNKS = 3
CHUNK_EDGES = 20_000
RBO_DEPTH = 1000

_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - _T0:6.1f}s] {msg}", flush=True)


def require(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


#: unit roundoff of f32
U = 2.0**-24


def assert_sum_close(got, want, rtol: float, what: str,
                     atol_scale: float = 1e-6) -> None:
    """allclose at ``rtol``, with ``atol`` scaled to the vector."""
    got, want = np.asarray(got), np.asarray(want)
    atol = atol_scale * float(np.max(np.abs(want)))
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=what)


def max_rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.maximum(np.abs(want), 1e-6 * np.max(np.abs(want)))
    return float(np.max(np.abs(got - want) / scale))


def make_stream(seed: int, n_vertices: int = N_VERTICES,
                chunk_edges: int = CHUNK_EDGES):
    """The seeded graph with ``CHUNKS`` chunks of edges held out."""
    t0 = time.perf_counter()
    src, dst = barabasi_albert_edges(n_vertices, OUT_EDGES, seed,
                                     directed_both=0.3)
    stream = build_stream(src, dst, StreamConfig(
        stream_size=CHUNKS * chunk_edges, num_queries=CHUNKS, seed=seed))
    log(f"graph: {stream.total_nodes} vertices, {stream.total_edges} edges"
        f" ({CHUNKS} chunks of {chunk_edges} held out), generated in "
        f"{time.perf_counter() - t0:.1f}s on the host")
    return stream


def sum_rtol(stream) -> float:
    """Relative tolerance between two f32 summation orders on ``stream``'s
    graph: a sum of d positive f32 terms rounds to within ~d·2⁻²⁴ of the
    exact sum, so two orders over the largest in-degree may differ by
    twice that."""
    dst = np.concatenate([stream.init_dst] + [d for _, d in stream.chunks])
    max_in = int(np.bincount(dst).max())
    rtol = max(2.0 * max_in * U, 1e-5)
    log(f"max in-degree {max_in}: f32 sums compared at rtol={rtol:.2e}")
    return rtol


def play(stream, algorithm: str, *, expect_backend: str = "pallas", **kw):
    """Scores of every query of a session replaying ``stream``."""
    with repro.session(stream, algorithm, **kw) as s:
        require(s.engine.backend == expect_backend,
                f"{algorithm} session resolved backend "
                f"{s.engine.backend!r}, expected {expect_backend!r}")
        scores = [r.scores for r in s.play()]
        return scores, s.engine


def live_edges(engine):
    """Live ``(src, dst, mask)`` of the engine's graph, on the host."""
    st = engine.state
    return (np.asarray(st.src), np.asarray(st.dst),
            np.asarray(st.edge_mask()))


def check_sum_push(engine, seed: int) -> None:
    """One exact ``plus_times`` push on the default backend against an f64
    ``np.bincount`` over the live edges.

    The kernel's own summation bounds the error: each chunk's one-hot
    product sums at most ``chunk`` terms in f32 and the chunk partials of a
    destination are added one after another, so a destination of in-degree
    d is within (chunk + ⌈d/chunk⌉ + 2)·u of the exact sum of its positive
    terms (the 2 for rounding the contribution itself).  The check allows
    twice that, relative to each destination and with no absolute slack."""
    st = engine.state
    n = st.node_capacity
    values = np.random.default_rng(seed).random(n).astype(np.float32)
    layout = B.build_layout(st, weight="inv_out")
    push = jax.jit(B.push, static_argnames="backend")
    got = push(values, layout)
    src, dst, mask = live_edges(engine)
    src, dst = src[mask], dst[mask]
    deg = np.bincount(src, minlength=n).astype(np.float64)
    want = np.bincount(dst, weights=values[src] / deg[src], minlength=n)
    chunk = layout.tile_chunk
    max_in = int(np.bincount(dst).max())
    rtol = 2.0 * (chunk + -(-max_in // chunk) + 2) * U
    assert_sum_close(got, want, rtol, "plus_times push vs np.bincount",
                     atol_scale=0.0)
    seg = push(values, layout, backend="segment_sum")
    log(f"  exact push over {src.size} edges matches np.bincount at "
        f"rtol={rtol:.2e}; max relative error against it: pallas "
        f"{max_rel_err(got, want):.2e}, segment_sum "
        f"{max_rel_err(seg, want):.2e}")


def check_min_plus_push(engine, distances) -> None:
    """One ``min_plus`` relaxation on the default backend, bitwise against
    ``np.minimum.at``."""
    st = engine.state
    values = np.asarray(distances, np.float32)
    layout = B.build_layout(st, weight="length", semiring="min_plus")
    got = np.asarray(jax.jit(
        lambda v, lay: B.push(v, lay, semiring="min_plus"))(values, layout))
    src, dst, mask = live_edges(engine)
    length = (np.ones(mask.sum(), np.float32) if st.edge_len is None
              else np.asarray(st.edge_len, np.float32)[mask])
    want = np.full(st.node_capacity, np.inf, np.float32)
    np.minimum.at(want, dst[mask], values[src[mask]] + length)
    require(np.array_equal(got, want),
            "min_plus push differs from np.minimum.at")
    log(f"  min_plus push over {int(mask.sum())} edges is bitwise equal to "
        f"np.minimum.at")


def pagerank_phase(stream, seed: int, rtol: float) -> None:
    log("phase 2: streaming PageRank")
    got, engine = play(stream, "pagerank")
    check_sum_push(engine, seed)
    active = np.asarray(engine.state.node_active)
    del engine
    ref, _ = play(stream, "pagerank", backend="segment_sum",
                  expect_backend="segment_sum")
    exact, _ = play(stream, "pagerank", on_query=always(Action.EXACT))
    for q, (a, r, e) in enumerate(zip(got, ref, exact)):
        assert_sum_close(a, r, rtol,
                         f"pagerank query {q}: pallas vs segment_sum")
        rbo = rbo_from_scores(a.astype(np.float64), e.astype(np.float64),
                              depth=RBO_DEPTH, active=active)
        log(f"  query {q}: matches segment_sum (max relative difference "
            f"{max_rel_err(a, r):.2e}); RBO@{RBO_DEPTH} against exact "
            f"PageRank {rbo:.4f}")


def sssp_phase(stream) -> None:
    log("phase 3: streaming SSSP from vertex 0")
    got, engine = play(stream, "sssp", sources=(0,))
    check_min_plus_push(engine, got[-1])
    del engine
    ref, _ = play(stream, "sssp", sources=(0,), backend="segment_sum",
                  expect_backend="segment_sum")
    for q, (a, r) in enumerate(zip(got, ref)):
        require(np.array_equal(a, r),
                f"sssp query {q}: pallas differs from segment_sum")
        log(f"  query {q}: bitwise equal to segment_sum "
            f"({int(np.isfinite(a).sum())} vertices reached)")


def serve(stream, tickets, **kw):
    """Results of ``tickets`` served through one ``serve_session``."""
    with repro.serve_session(stream, slots=4, **kw) as srv:
        handles = [srv.submit(name, **params) for name, params in tickets]
        stats = srv.run()
        require(all(t.done for t in handles), "a served ticket did not end")
        return [np.asarray(t.result) for t in handles], stats


def serving_phase(stream, seed: int, rtol: float) -> None:
    log("phase 4: serve_session(slots=4), 4 PPR + 2 SSSP tickets")
    rng = np.random.default_rng(seed)
    ids = rng.choice(stream.total_nodes, size=6, replace=False)
    tickets = ([("personalized-pagerank", {"seeds": (int(v),)})
                for v in ids[:4]]
               + [("sssp", {"sources": (int(v),)}) for v in ids[4:]])
    got, stats = serve(stream, tickets)
    ref, _ = serve(stream, tickets, backend="segment_sum")
    for (name, params), a, r in zip(tickets, got, ref):
        if name == "sssp":
            require(np.array_equal(a, r),
                    f"served sssp {params} differs from segment_sum")
        else:
            assert_sum_close(a, r, rtol,
                             f"served PPR {params} vs segment_sum")
    log(f"  6 tickets match segment_sum over {stats.waves} waves")


def mesh_phase(stream, chips: int, rtol: float) -> None:
    log(f"mesh phase: sessions sharded over {chips} chips")
    mesh = jax.make_mesh((chips,), ("shards",))
    for algorithm, kw in (("pagerank", {}), ("sssp", {"sources": (0,)})):
        got, engine = play(stream, algorithm, mesh=mesh, **kw)
        layout = engine.edge_layouts()[0]
        per_shard = [(sh.device.id, int(np.asarray(sh.data).sum()))
                     for sh in layout.valid.addressable_shards]
        log(f"  {algorithm}: rebalances {engine.rebalances}, live edges per "
            f"shard (device, edges) {per_shard}")
        del engine, layout
        ref, _ = play(stream, algorithm, **kw)
        for q, (a, r) in enumerate(zip(got, ref)):
            if algorithm == "sssp":
                require(np.array_equal(a, r),
                        f"sharded sssp query {q} differs from one chip")
            else:
                assert_sum_close(a, r, rtol, f"sharded pagerank query {q}")
        log(f"  {algorithm}: {len(got)} sharded queries match one chip")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded path on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache_dir = use_compile_cache()
    compiles = []

    def count_compile(event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(count_compile)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{dev.platform!r}); nothing was run")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} TPUs, JAX found {len(devices)}")
    log(f"phase 1: device {dev.platform} / {dev.device_kind} x "
        f"{len(devices)}; jax {jax.__version__}, libtpu "
        f"{metadata.version('libtpu')}; compile cache {cache_dir}")

    stream = make_stream(args.seed)
    rtol = sum_rtol(stream)
    if args.chips == 1:
        pagerank_phase(stream, args.seed, rtol)
        sssp_phase(stream)
        serving_phase(stream, args.seed, rtol)
    else:
        mesh_phase(stream, args.chips, rtol)

    peak = dev.memory_stats().get("peak_bytes_in_use")
    log(f"phase 5: {len(compiles)} programs compiled in {sum(compiles):.1f}s"
        f" (persistent cache hits are not compiles); peak device memory "
        f"{peak} bytes")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
