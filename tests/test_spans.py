"""The query path's named scopes and profiler spans (repro.core.spans).

- every scope and span name the program emits is listed in
  ``spans.SCOPES`` / ``spans.SPANS``, and nothing else in ``src/`` opens
  a ``named_scope`` or a ``TraceAnnotation``;
- the fused query step's compiled HLO carries the hot-set, summary and
  sweep scopes in its ``op_name`` metadata, for PageRank and SSSP;
- under ``jax.profiler.trace`` each query's spans nest inside its
  ``veilgraph.query`` span, a fused answer makes nine host syncs, and
  ``QueryStats.wall_time_s`` is that span's interval, from the update
  integration through the score fetch; a serving wave is one
  ``veilgraph.wave`` span.
"""

import glob
import os
import re
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro
from repro.core import spans
from repro.core.fused import fused_query_step
from repro.graph.generators import gnm_edges

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"
N, M = 160, 900
#: blocking reads of one fused answer on the sync path: two reads in each
#: of the two ``_stats_view`` calls, the hot-set counts that size the
#: summary, the fallback flag, the ready wait, the stats transfer and the
#: score fetch
SYNCS = {spans.SYNC_VIEW: 4, spans.SYNC_COUNTS: 1, spans.SYNC_FALLBACK: 1,
         spans.SYNC_READY: 1, spans.SYNC_STATS: 1, spans.SYNC_FETCH: 1}
#: the step is dispatched in two programs, either side of the counts read
DISPATCHES = {spans.APPLY: 1, spans.LAYOUT: 1, spans.STEP: 2}


def _session(algorithm="pagerank"):
    src, dst = gnm_edges(N, M, seed=5)
    kw = {"sources": (0,)} if algorithm == "sssp" else {}
    return repro.session((src[:M - 60], dst[:M - 60]), algorithm,
                         node_capacity=N, **kw), (src[M - 60:], dst[M - 60:])


def _program_spans(trace_dir):
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    data = ProfileData.from_file(path)
    return sorted(([ev.name, ev.start_ns, ev.start_ns + ev.duration_ns]
                   for plane in data.planes if plane.name == "/host:CPU"
                   for line in plane.lines for ev in line.events
                   if ev.name.startswith("veilgraph.")),
                  key=lambda e: (e[1], -e[2]))


def test_every_name_the_program_emits_is_listed():
    names = spans.SCOPES + spans.SPANS
    assert all(n.startswith("veilgraph.") for n in names)
    assert len(set(spans.SCOPES)) == len(spans.SCOPES)
    assert len(set(spans.SPANS)) == len(spans.SPANS)
    used = {"scope": set(), "span": set()}
    for path in SRC.rglob("*.py"):
        text = path.read_text()
        for kind, const in re.findall(r"spans\.(scope|span)\(spans\.(\w+)\)",
                                      text):
            used[kind].add(getattr(spans, const))
        if path.name != "spans.py":
            assert "named_scope(" not in text, path
            assert "TraceAnnotation(" not in text, path
    assert used["scope"] == set(spans.SCOPES)
    assert used["span"] == set(spans.SPANS)


@pytest.mark.parametrize("algorithm", ["pagerank", "sssp"])
def test_fused_step_hlo_carries_the_stage_scopes(algorithm):
    sess, _ = _session(algorithm)
    eng, cfg = sess.engine, sess.engine.config
    hlo = fused_query_step.lower(
        eng.state, eng.algo_state, eng.deg_prev, eng.active_prev,
        jnp.float32(cfg.r), jnp.float32(cfg.delta), eng._probe_ids,
        algo=eng.algorithm, hot_node_capacity=cfg.hot_node_capacity,
        hot_edge_capacity=cfg.hot_edge_capacity, n=cfg.n,
        delta_hop_cap=cfg.delta_hop_cap, degree_mode=cfg.degree_mode,
        expand_both=cfg.expand_both, layouts=eng.edge_layouts(),
        backend=eng.backend).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', hlo)
    for name in (spans.HOTSET, spans.HOTSET_KN, spans.HOTSET_KDELTA,
                 spans.SUMMARY, spans.SUMMARY_SELECT, spans.SUMMARY_BIN,
                 spans.SUMMARY_ORDER, spans.SWEEP, spans.PUSH):
        assert any(name in o.split("/") for o in op_names), name


def test_query_spans_nest_count_and_time_the_answer(tmp_path):
    sess, (src, dst) = _session()
    sess.query()  # compiles outside the trace
    results = []
    with jax.profiler.trace(str(tmp_path)):
        for lo in (0, 30):
            sess.add_edges(src[lo:lo + 30], dst[lo:lo + 30])
            results.append(sess.query())
    events = _program_spans(tmp_path)
    assert {e[0] for e in events} <= set(spans.SPANS)
    queries = [e for e in events if e[0] == spans.QUERY]
    assert len(queries) == 2
    for (_, q0, q1), res in zip(queries, results):
        inside = [e for e in events
                  if e[0] != spans.QUERY and q0 <= e[1] and e[2] <= q1]
        names = [e[0] for e in inside]
        assert {n: names.count(n) for n in names
                if n.startswith("veilgraph.sync.")} == SYNCS
        for n, count in DISPATCHES.items():
            assert names.count(n) == count, n
        # wall_time_s is the query span: integration through the fetch
        assert res.stats.wall_time_s == pytest.approx((q1 - q0) * 1e-9,
                                                      rel=0.05, abs=2e-3)
    # every program span lies in some query
    for name, a, b in events:
        assert any(q0 <= a and b <= q1 for _, q0, q1 in queries), name


def test_wall_time_counts_update_integration():
    sess, (src, dst) = _session()
    eng = sess.engine
    apply = eng._apply_pending

    def slow_apply(*a, **k):
        time.sleep(0.2)
        return apply(*a, **k)

    eng._apply_pending = slow_apply
    sess.add_edges(src[:30], dst[:30])
    t0 = time.perf_counter()
    res = sess.query()
    outer = time.perf_counter() - t0
    assert 0.2 <= res.stats.wall_time_s <= outer
    assert np.asarray(res.scores).shape == (N,)


def test_a_serving_wave_is_one_span(tmp_path):
    src, dst = gnm_edges(N, M, seed=6)
    srv = repro.serve_session((src, dst), slots=2, node_capacity=N)
    srv.submit("sssp", sources=(3,))
    srv.step()  # compiles outside the trace
    srv.submit("sssp", sources=(5,))
    with jax.profiler.trace(str(tmp_path)):
        srv.step()
    events = _program_spans(tmp_path)
    (wave,) = [e for e in events if e[0] == spans.WAVE]
    names = [e[0] for e in events if e is not wave]
    assert spans.STEP in names and spans.SYNC_STATS in names
    assert all(wave[1] <= a and b <= wave[2] for _, a, b in events)
