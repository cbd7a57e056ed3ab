"""The paper's evaluation protocol (§5): 18 (r, n, Δ) combos × datasets.

For each dataset: ground-truth replay (exact PageRank every query) plus one
summarized replay per parameter combo, Q=50 queries each, shuffled streams.
Emits one JSON per (dataset, combo) with the per-query series of the
paper's four metrics — summary vertex ratio (Figs 3/7/11/15/19/23/27),
summary edge ratio (Figs 4/8/12/16/20/24/28), RBO (Figs 5/9/13/17/21/25/29)
and speedup (Figs 6/10/14/18/22/26/30) — into artifacts/paper_sweep/.

  PYTHONPATH=src python -m benchmarks.paper_sweep --datasets synth-citation
  PYTHONPATH=src python -m benchmarks.paper_sweep --full
  PYTHONPATH=src python -m benchmarks.paper_sweep --algorithm hits

Runs through the session front door (`repro.api.session`), so `--algorithm`
sweeps any registered StreamingAlgorithm with the same protocol.
"""

from __future__ import annotations

import argparse
import itertools
import json
import time
from pathlib import Path

import numpy as np

import repro as veilgraph
from repro.compile_cache import use_compile_cache
from repro.core import Action
from repro.core.policies import always
from repro.graph.generators import DATASETS, generate
from repro.metrics import rbo_from_scores
from repro.stream import StreamConfig, build_stream

ART = Path(__file__).resolve().parent.parent / "artifacts" / "paper_sweep"

# the paper's §5.2 parameter grid: 18 combos
R_VALUES = (0.10, 0.20, 0.30)
N_VALUES = (0, 1)
DELTA_VALUES = (0.01, 0.10, 0.90)


def _pow2(x: int) -> int:
    n = 1
    while n < x:
        n *= 2
    return n


def _session_knobs(spec, stream, r, n, delta, hot_nodes=None,
                   hot_edges=None) -> dict:
    n_cap = spec.nodes
    e_cap = int(stream.total_edges * 1.1) + 1024
    return dict(
        node_capacity=n_cap, edge_capacity=e_cap,
        hot_node_capacity=min(hot_nodes or n_cap, n_cap),
        hot_edge_capacity=min(hot_edges or e_cap, e_cap),
        r=r, n=n, delta=delta, num_iters=30, tol=1e-6,
    )


def calibrate_capacities(spec, stream, algorithm, r, n, delta,
                         probe_queries=5):
    """Capacity planning: probe the first queries with generous buffers and
    size the hot buffers to ~1.5x the observed peak (pow2-bucketed so combos
    share compilations).  This is the deployment-realistic counterpart of the
    paper's dynamically-sized Flink summary; overflow at runtime falls back
    to exact recomputation and is recorded."""
    sess = veilgraph.session(stream, algorithm,
                             **_session_knobs(spec, stream, r, n, delta))
    max_hot, max_ek = 1, 1
    for q, res in enumerate(sess.play()):
        if q >= probe_queries:
            break
        max_hot = max(max_hot, res.stats.num_hot)
        max_ek = max(max_ek, res.stats.num_ek + 1)
    return (max(2048, _pow2(int(1.5 * max_hot))),
            max(8192, _pow2(int(1.5 * max_ek))))


def ground_truth(spec, stream, algorithm, queries):
    sess = veilgraph.session(stream, algorithm,
                             on_query=always(Action.EXACT),
                             **_session_knobs(spec, stream, 0.2, 1, 0.1))
    ranks, times = [], []
    for res in sess.play():
        ranks.append(res.scores)
        times.append(res.stats.wall_time_s)
    return ranks, times


def run_combo(spec, stream, algorithm, r, n, delta, gt_ranks, gt_times,
              depth):
    hot_nodes, hot_edges = calibrate_capacities(
        spec, stream, algorithm, r, n, delta)
    knobs = _session_knobs(spec, stream, r, n, delta, hot_nodes, hot_edges)
    sess = veilgraph.session(stream, algorithm, **knobs)
    rows = []
    for q, res in enumerate(sess.play()):
        st = res.stats
        rbo = rbo_from_scores(res.scores, gt_ranks[q], depth=depth,
                              active=np.asarray(sess.engine.state.node_active))
        rows.append({
            "q": q,
            "vertex_ratio": st.vertex_ratio,
            "edge_ratio": st.edge_ratio,
            "rbo": rbo,
            "speedup": gt_times[q] / max(st.wall_time_s, 1e-9),
            "num_hot": st.num_hot, "num_ek": st.num_ek, "num_eb": st.num_eb,
            "fallback": bool(st.overflow_fallback),
            "iterations": st.iterations,
        })
    # record the capacities the engine actually ran with (the calibrated
    # values are clamped to the graph capacities inside _session_knobs)
    return rows, (knobs["hot_node_capacity"], knobs["hot_edge_capacity"])


def sweep_dataset(name: str, queries: int = 50, shuffle: bool = True,
                  seed: int = 7, combos=None, verbose=True,
                  algorithm: str = "pagerank"):
    ART.mkdir(parents=True, exist_ok=True)
    spec = DATASETS[name]
    src, dst = generate(spec, seed=0)
    sc = StreamConfig(stream_size=spec.stream_size, num_queries=queries,
                      shuffle=shuffle, seed=seed)
    stream = build_stream(src, dst, sc)
    depth = 1000 if sc.edges_per_query <= 200 else 4000
    if verbose:
        print(f"[{name}] V~{stream.total_nodes} E={stream.total_edges} "
              f"chunk={sc.edges_per_query} rbo_depth={depth}")
    t0 = time.time()
    gt_ranks, gt_times = ground_truth(spec, stream, algorithm, queries)
    if verbose:
        print(f"  ground truth: {time.time()-t0:.1f}s "
              f"(mean query {1e3*np.mean(gt_times[1:]):.1f} ms)")

    combos = combos or list(itertools.product(R_VALUES, N_VALUES, DELTA_VALUES))
    results = {}
    for r, n, delta in combos:
        t0 = time.time()
        rows, cfg_used = run_combo(spec, stream, algorithm, r, n, delta,
                                   gt_ranks, gt_times, depth)
        key = f"r{r}_n{n}_d{delta}"
        results[key] = rows
        if not rows:
            raise SystemExit(
                f"[{name}] combo {key}: the replay produced no query rows "
                f"(empty stream?) — nothing to summarize")
        # the warm-up query is skipped when there is more than one row
        w = rows[1:] or rows
        summary = {
            "vertex_ratio": float(np.mean([x["vertex_ratio"] for x in w])),
            "edge_ratio": float(np.mean([x["edge_ratio"] for x in w])),
            "rbo": float(np.mean([x["rbo"] for x in w])),
            "rbo_final": w[-1]["rbo"],
            "speedup": float(np.mean([x["speedup"] for x in w])),
            "speedup_min": float(np.min([x["speedup"] for x in w])),
            "fallbacks": int(np.sum([x["fallback"] for x in w])),
        }
        out = {"dataset": name, "algorithm": algorithm,
               "r": r, "n": n, "delta": delta,
               "queries": queries, "shuffle": shuffle,
               "hot_node_capacity": cfg_used[0],
               "hot_edge_capacity": cfg_used[1],
               "summary": summary, "rows": rows}
        suffix = "" if algorithm == "pagerank" else f"__{algorithm}"
        (ART / f"{name}{suffix}__{key}.json").write_text(json.dumps(out))
        if verbose:
            print(f"  r={r} n={n} Δ={delta}: vr={summary['vertex_ratio']:.3f} "
                  f"er={summary['edge_ratio']:.3f} rbo={summary['rbo']:.4f} "
                  f"speedup={summary['speedup']:.2f} "
                  f"({time.time()-t0:.1f}s)")
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--datasets", nargs="*",
                    default=["synth-citation", "synth-social"])
    ap.add_argument("--full", action="store_true",
                    help="all datasets × all 18 combos")
    ap.add_argument("--queries", type=int, default=50)
    ap.add_argument("--no-shuffle", action="store_true")
    ap.add_argument("--algorithm", default="pagerank",
                    choices=sorted(veilgraph.available_algorithms()))
    args = ap.parse_args(argv)
    use_compile_cache()
    names = sorted(DATASETS) if args.full else args.datasets
    for name in names:
        sweep_dataset(name, queries=args.queries,
                      shuffle=not args.no_shuffle,
                      algorithm=args.algorithm)


if __name__ == "__main__":
    main()
