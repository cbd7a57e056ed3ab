"""Unified propagation backend: pallas (interpret) vs segment_sum parity.

Every sweep family — exact, summarized, and the big-vertex pass — must
produce the same numbers on both backends for every registered algorithm;
the engine must sort the edge layout at most once per applied update batch.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import backend as B
from repro.core.algorithm import available_algorithms, make_algorithm
from repro.core.pagerank import build_summary, pagerank, summarized_pagerank
from repro.core.semiring import resolve_semiring
from repro.graph import from_edges
from repro.graph.csr import gather_push, sort_by_dst
from repro.graph.generators import gnm_edges
from repro.graph.graph import (add_edges, find_edge_slots, inv_out_degree,
                               remove_edges_by_slot)

TOL = dict(rtol=1e-5, atol=1e-6)


def _algo(name):
    # registry factories needing parameters get deterministic ones here
    params = {"personalized-pagerank": dict(seeds=(1, 5))}.get(name, {})
    a = make_algorithm(name, **params)
    # shrink sweeps so interpret-mode kernels stay fast
    return a.__class__(**{**{f: getattr(a, f) for f in a.__dataclass_fields__},
                          "num_iters": 8})


def _graph(n=300, m=2000, seed=0, n_cap=None):
    src, dst = gnm_edges(n, m, seed=seed)
    return from_edges(src, dst, n_cap or n, m + 64)


def _layouts(g, algo):
    return tuple(B.build_layout(g, weight=w, reverse=rev, semiring=s)
                 for (w, rev, s) in map(B.normalize_layout_spec,
                                        algo.layout_specs))


def _hot(n_cap, seed=0, frac=0.5):
    return jnp.asarray(np.random.default_rng(seed).random(n_cap) < frac)


# ------------------------------------------------------------------ parity
@pytest.mark.parametrize("name", sorted(available_algorithms()))
def test_exact_sweep_backend_parity(name):
    g = _graph()
    algo = _algo(name)
    st0 = algo.init_state(g)
    layouts = _layouts(g, algo)
    ref, _ = algo.exact(st0, g, layouts=layouts, backend="segment_sum")
    out, _ = algo.exact(st0, g, layouts=layouts, backend="pallas")
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   **TOL)
    # and against the no-layout (unsorted COO) reference path
    base, _ = algo.exact(st0, g, layouts=None, backend="segment_sum")
    for k in ref:
        np.testing.assert_allclose(np.asarray(ref[k]), np.asarray(base[k]),
                                   **TOL)


@pytest.mark.parametrize("name", sorted(available_algorithms()))
def test_summarized_sweep_backend_parity(name):
    g = _graph()
    algo = _algo(name)
    st0 = algo.init_state(g)
    st, _ = algo.exact(st0, g)
    hot = _hot(g.node_capacity)
    layouts = _layouts(g, algo)
    caps = dict(hot_node_capacity=256, hot_edge_capacity=1024)
    summaries = algo.build_summaries(st, g, hot, **caps)
    # the big-vertex pass with a cached layout must match the unsorted one
    with_layout = algo.build_summaries(
        st, g, hot, **caps, layouts=layouts, backend="segment_sum")
    for s, sl in zip(summaries, with_layout):
        assert not bool(s.overflow)
        np.testing.assert_allclose(np.asarray(sl.b_in), np.asarray(s.b_in),
                                   **TOL)
    ref, _ = algo.summarized(st, g, summaries, backend="segment_sum")
    out, _ = algo.summarized(st, g, summaries, backend="pallas")
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   **TOL)


def test_push_parity_custom_tile_geometry():
    """tile_n/chunk are parameters, not module constants."""
    g = _graph(n=257, m=900, seed=2, n_cap=257)  # non-multiple-of-tile N
    layout = B.build_layout(g, weight="inv_out")
    r = jnp.asarray(np.random.default_rng(3).random(257).astype(np.float32))
    ref = B.push(r, layout, backend="segment_sum")
    for tile_n, chunk in [(128, 256), (64, 512), (256, 128)]:
        out = B.push(r, layout, backend="pallas", tile_n=tile_n, chunk=chunk,
                     interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def test_gather_push_is_the_sorted_fallback():
    """csr.gather_push == unsorted segment_sum on the sorted layout."""
    g = _graph(n=200, m=1500, seed=4, n_cap=200)
    se = sort_by_dst(g)
    vals = jnp.asarray(np.random.default_rng(5).random(200).astype(np.float32))
    out = gather_push(se, vals, 200)
    ref = jax.ops.segment_sum(
        jnp.where(g.edge_mask(), vals[g.src], 0.0), g.dst, num_segments=200)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)
    # weighted form (what backend.push uses)
    w = jnp.asarray(np.random.default_rng(6).random(se.src.shape[0]), jnp.float32)
    out_w = gather_push(se, vals, 200, weight=w)
    ref_w = jax.ops.segment_sum(
        jnp.where(se.valid, vals[se.src] * w, 0.0),
        jnp.minimum(se.dst, 199), num_segments=200, indices_are_sorted=True)
    np.testing.assert_allclose(np.asarray(out_w), np.asarray(ref_w), **TOL)


# -------------------------------------------------------------- edge cases
@pytest.mark.parametrize("backend", ["segment_sum", "pallas"])
def test_push_empty_graph(backend):
    g = from_edges(np.zeros(0, np.int32), np.zeros(0, np.int32), 256, 64)
    layout = B.build_layout(g, weight="inv_out")
    out = B.push(jnp.ones(256), layout, backend=backend, interpret=True)
    assert out.shape == (256,)
    assert float(jnp.abs(out).max()) == 0.0


@pytest.mark.parametrize("backend", ["segment_sum", "pallas"])
def test_push_ignores_tombstoned_edges(backend):
    """Removed edges sort into the padding region and contribute nothing."""
    g = _graph(n=128, m=700, seed=7, n_cap=128)
    slots = find_edge_slots(g, np.asarray(g.src)[:200], np.asarray(g.dst)[:200])
    g2 = remove_edges_by_slot(g, jnp.asarray(slots))
    layout = B.build_layout(g2, weight="inv_out")
    r = jnp.asarray(np.random.default_rng(8).random(128).astype(np.float32))
    out = B.push(r, layout, backend=backend, interpret=True)
    from repro.graph.graph import inv_out_degree
    ref = jax.ops.segment_sum(
        jnp.where(g2.edge_mask(), (r * inv_out_degree(g2))[g2.src], 0.0),
        g2.dst, num_segments=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


@pytest.mark.parametrize("backend", ["segment_sum", "pallas"])
def test_summarized_overflow_flag_and_no_crash(backend):
    g = _graph(n=300, m=2000, seed=9)
    r0, _ = pagerank(g, num_iters=5)
    hot = jnp.ones(g.node_capacity, bool)
    s = build_summary(g, r0, hot, hot_node_capacity=32, hot_edge_capacity=64)
    assert bool(s.overflow)
    # the result is discarded on overflow, but the sweep must still run
    ranks, _ = summarized_pagerank(s, r0, num_iters=3, backend=backend)
    assert ranks.shape == r0.shape
    assert bool(jnp.all(jnp.isfinite(ranks)))


def test_summary_ek_buffer_is_destination_sorted():
    g = _graph()
    r0, _ = pagerank(g, num_iters=5)
    hot = _hot(g.node_capacity, seed=1)
    s = build_summary(g, r0, hot, hot_node_capacity=256,
                      hot_edge_capacity=1024)
    ek_dst = np.asarray(s.ek_dst)
    assert (np.diff(ek_dst) >= 0).all()
    n_ek = int(s.num_ek)
    assert (ek_dst[n_ek:] == 256).all()  # padding sentinel sorts last
    ro = np.asarray(s.ek_row_offsets)
    assert ro.shape == (257,)
    assert ro[0] == 0 and ro[-1] == n_ek
    for z in (0, 17, 255):
        assert (ek_dst[ro[z]:ro[z + 1]] == z).all()


# ------------------------------------------------------- engine-level cache
def test_engine_reuses_sorted_layout_across_queries():
    src, dst = gnm_edges(400, 2500, seed=11)
    with repro.session((src, dst), algorithm="pagerank") as s:
        eng = s.engine
        assert eng.layout_builds == 1  # built for the initial exact
        cached = eng.edge_layouts()
        s.query()
        s.query()  # two consecutive queries, no interleaved updates
        assert eng.layout_builds == 1
        assert eng.edge_layouts() is cached  # same tuple, no re-sort
        s.add_edges([0, 1], [2, 3])
        s.query()  # applied update batch -> exactly one re-sort
        assert eng.layout_builds == 2


def test_engine_unresolved_removal_keeps_layout_cache():
    src, dst = gnm_edges(200, 1200, seed=12)
    with repro.session((src, dst), algorithm="pagerank") as s:
        eng = s.engine
        s.query()
        builds = eng.layout_builds
        s.remove_edges([199], [198])  # matches no live edge
        st = s.query().stats
        assert st.removals_requested == 1 and st.removals_resolved == 0
        assert eng.layout_builds == builds


# -------------------------------------------------------- backend selection
def test_resolve_backend_env_override(monkeypatch):
    monkeypatch.setenv(B.BACKEND_ENV_VAR, "pallas")
    assert B.resolve_backend(None) == "pallas"
    assert B.resolve_backend("auto") == "pallas"
    # explicit argument beats the environment
    assert B.resolve_backend("segment_sum") == "segment_sum"
    monkeypatch.setenv(B.BACKEND_ENV_VAR, "auto")
    expected = "pallas" if jax.default_backend() == "tpu" else "segment_sum"
    assert B.resolve_backend(None) == expected
    monkeypatch.setenv(B.BACKEND_ENV_VAR, "bogus")
    with pytest.raises(ValueError):
        B.resolve_backend(None)
    with pytest.raises(ValueError):
        B.resolve_backend("cuda")


def test_engine_config_backend_knob():
    src, dst = gnm_edges(150, 800, seed=13)
    with repro.session((src, dst), algorithm="pagerank",
                       backend="pallas") as sp, \
         repro.session((src, dst), algorithm="pagerank",
                       backend="segment_sum") as ss:
        assert sp.engine.backend == "pallas"
        assert ss.engine.backend == "segment_sum"
        rp = sp.query()
        rs = ss.query()
        np.testing.assert_allclose(rp.scores, rs.scores, **TOL)


def test_build_layout_rejects_reverse_inv_out():
    g = _graph(n=64, m=200, seed=14, n_cap=64)
    with pytest.raises(ValueError):
        B.build_layout(g, weight="inv_out", reverse=True)


def test_mismatched_layout_is_rejected():
    """A cached layout whose baked weights don't match the sweep must fail
    loudly at trace time, not silently mis-weight (e.g. an algorithm
    overriding layout_specs without overriding build_summaries)."""
    g = _graph(n=64, m=400, seed=15, n_cap=64)
    unit = B.build_layout(g, weight="unit")
    rev = B.build_layout(g, weight="unit", reverse=True)
    r0, _ = pagerank(g, num_iters=3)
    hot = _hot(64, seed=2)
    with pytest.raises(ValueError, match="build_summary needs a layout"):
        build_summary(g, r0, hot, hot_node_capacity=64,
                      hot_edge_capacity=512, layout=unit)
    with pytest.raises(ValueError, match="build_summary needs a layout"):
        build_summary(g, r0, hot, hot_node_capacity=64, hot_edge_capacity=512,
                      weight="unit", layout=rev)
    with pytest.raises(ValueError, match="pagerank needs a layout"):
        pagerank(g, num_iters=3, layout=unit)
    from repro.core.hits import hits
    with pytest.raises(ValueError, match="fwd_layout needs a layout"):
        hits(g, num_iters=3, fwd_layout=rev, rev_layout=rev)


def test_push_rejects_chunk_beyond_layout_padding():
    """The kernel's chunk loads are only in-bounds up to the layout's pad."""
    g = _graph(n=64, m=400, seed=16, n_cap=64)
    layout = B.build_layout(g, weight="inv_out", chunk=256)
    r = jnp.ones(64)
    ref = B.push(r, layout, backend="segment_sum")
    out = B.push(r, layout, backend="pallas", chunk=256, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)
    with pytest.raises(ValueError, match="pad_chunk"):
        B.push(r, layout, backend="pallas", chunk=512, interpret=True)


# ---------------------------------------------------- layout construction
_N_CAP, _E_CAP = 96, 640


@functools.partial(jax.jit, static_argnames=("weight", "reverse",
                                              "semiring"))
def _reference_layout(state, *, weight, reverse=False,
                      semiring="plus_times", lengths=None):
    """The layout as an argsort, E-sized gathers and a ``searchsorted``
    over the sorted keys build it: the reference every field of
    :func:`B.build_layout` must equal bit for bit."""
    if weight == "length" and lengths is None:
        lengths = state.edge_len
    s = resolve_semiring(semiring)
    mask = state.edge_mask()
    n, e_cap = state.node_capacity, state.edge_capacity
    e_src, e_dst = (state.dst, state.src) if reverse else (state.src,
                                                           state.dst)
    key = jnp.where(mask, e_dst, n)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    dst, src, valid = key[order], e_src[order], mask[order]
    row_offsets = jnp.searchsorted(
        dst, jnp.arange(n + 1, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)
    w = B.bake_weights(s, weight, valid, src, inv_deg=inv_out_degree(state),
                       lengths=None if lengths is None else lengths[order])
    pad = B.padded_length(e_cap, B.CHUNK) - e_cap
    src, order, valid = (jnp.pad(src, (0, pad)),
                         jnp.pad(order, (0, pad), constant_values=e_cap),
                         jnp.pad(valid, (0, pad)))
    dst = jnp.pad(dst, (0, pad), constant_values=n)
    w = jnp.pad(w, (0, pad), constant_values=s.zero)
    rank = None
    if s.add != "sum":
        start = row_offsets[jnp.minimum(dst, n)]
        rank = jnp.where(valid, jnp.arange(dst.shape[0], dtype=jnp.int32)
                         - start, 0)
    return dict(src=src, dst=dst, weight=w, valid=valid,
                row_offsets=row_offsets, order=order, rank=rank)


def _layout_graph(kind):
    """Graphs at one pair of capacities, so each layout compiles once."""
    rng = np.random.default_rng(21)
    if kind == "empty":
        return from_edges(np.zeros(0, np.int32), np.zeros(0, np.int32),
                          _N_CAP, _E_CAP)
    src, dst = gnm_edges(80, 500, seed=22)
    weights = (rng.random(src.shape[0]).astype(np.float32) + 0.5
               if kind == "weighted" else None)
    g = from_edges(src, dst, _N_CAP, _E_CAP, weights=weights)
    if kind == "tombstoned":
        # every third edge, some of them named twice in one batch
        gone = np.arange(0, 500, 3)
        slots = find_edge_slots(g, src[np.r_[gone, gone[:20]]],
                                dst[np.r_[gone, gone[:20]]])
        g = remove_edges_by_slot(g, jnp.asarray(slots))
    elif kind == "overfull":
        # appends past edge_capacity drop what does not fit
        for _ in range(2):
            g = add_edges(g, jnp.asarray(rng.integers(0, 90, 128), jnp.int32),
                          jnp.asarray(rng.integers(0, 90, 128), jnp.int32))
        assert int(g.num_edges) == _E_CAP
    return g


_LAYOUT_SPECS = [
    dict(weight="inv_out"),
    dict(weight="unit"),
    dict(weight="unit", reverse=True),
    dict(weight="unit", semiring="min_min"),
    dict(weight="length", semiring="min_plus"),
    dict(weight="length", semiring="min_plus", reverse=True),
    dict(weight="length", semiring="min_plus", lengths=True),
    dict(weight="length", semiring="max_times", lengths=True),
]


@pytest.mark.parametrize("kind", ["plain", "weighted", "tombstoned",
                                  "overfull", "empty"])
@pytest.mark.parametrize("spec", _LAYOUT_SPECS,
                         ids=lambda d: "-".join(f"{k}={v}"
                                                for k, v in d.items()))
def test_build_layout_matches_argsort_reference(kind, spec):
    """build_layout's one sort with row offsets from the degree counters
    gives every field the argsort/gather/searchsorted construction gives."""
    g = _layout_graph(kind)
    spec = dict(spec)
    if spec.pop("lengths", False):
        spec["lengths"] = jnp.asarray(np.random.default_rng(23).random(
            _E_CAP).astype(np.float32))
    got = B.build_layout(g, **spec)
    ref = _reference_layout(g, **spec)
    for field, want in ref.items():
        have = getattr(got, field)
        if want is None:
            assert have is None, field
            continue
        assert have.dtype == want.dtype and have.shape == want.shape, field
        np.testing.assert_array_equal(np.asarray(have), np.asarray(want),
                                      err_msg=field)


def _edge_sized_gathers(hlo: str, sizes) -> int:
    found = re.findall(r'"?stablehlo\.gather"?.*->\s*tensor<(\d+)x', hlo)
    return sum(int(n) in sizes for n in found)


@pytest.mark.parametrize("spec,gathers", [
    (dict(weight="inv_out"), 1),                        # inv_deg[src]
    (dict(weight="unit"), 0),
    (dict(weight="unit", reverse=True), 0),
    (dict(weight="length", semiring="min_plus"), 0),    # rank from a scan
])
def test_build_layout_has_no_edge_sized_gather_or_search(spec, gathers):
    """The sort carries every output: the lowered layout holds no E-sized
    gather beyond ``inv_out``'s ``inv_deg[src]`` and no search loop."""
    g = _layout_graph("plain")
    hlo = B.build_layout.lower(g, **spec).as_text()
    sizes = {_E_CAP, B.padded_length(_E_CAP, B.CHUNK)}
    assert _edge_sized_gathers(hlo, sizes) == gathers
    assert "stablehlo.while" not in hlo
    assert "stablehlo.sort" in hlo


@pytest.mark.parametrize("stage,name,gathers", [
    ("hot_set_step", "pagerank", 4),
    ("hot_set_step", "sssp", 4),
    # values[layout.src] in each b_in push, and nothing else
    ("summarized_step", "pagerank", 1),
    ("summarized_step", "personalized-pagerank", 1),
    ("summarized_step", "katz", 1),
    ("summarized_step", "sssp", 1),
    ("summarized_step", "hits", 2),                   # both orientations
    ("summarized_step", "connected-components", 2),
    # max_times over lengths keeps the slot mask ~hot[layout.src]
    ("summarized_step", "widest-path", 2),
])
def test_split_step_gathers_the_hot_set_at_the_edges_once(stage, name,
                                                         gathers):
    """``hot_set_step`` gathers the hot set at the edges for the E_K
    count; ``summarized_step``, handed that mask, gathers only the pushed
    values at the layout's emitters."""
    from repro.core.fused import hot_set_step, summarized_step

    g = _layout_graph("plain")
    algo = _algo(name)
    st = algo.init_state(g)
    args = (g, st, g.out_deg, g.node_active, jnp.float32(0.2),
            jnp.float32(0.1))
    sizes = {_E_CAP, B.padded_length(_E_CAP, B.CHUNK)}
    if stage == "hot_set_step":
        hlo = hot_set_step.lower(*args, algo=algo).as_text()
    else:
        hot, hstats, _, ek = hot_set_step(*args, algo=algo)
        hlo = summarized_step.lower(
            g, st, hot, hstats, algo=algo, hot_node_capacity=32,
            hot_edge_capacity=128, layouts=_layouts(g, algo),
            backend="pallas", ek_mask=ek).as_text()
    assert _edge_sized_gathers(hlo, sizes) == gathers
