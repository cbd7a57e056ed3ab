"""The session-style front door to VeilGraph.

One call builds a started engine around any registered
:class:`~repro.core.algorithm.StreamingAlgorithm`:

    import repro as veilgraph   # or: from repro.api import session

    with veilgraph.session((src, dst), algorithm="pagerank") as s:
        s.add_edges(new_src, new_dst)
        result = s.query()
        print(result.top(10), result.stats.vertex_ratio)

``graph_source`` may be a ``(src, dst)`` edge-array pair, a named synthetic
dataset (``repro.graph.generators.DATASETS``), or a prebuilt
:class:`~repro.stream.EdgeStream` — in the stream case the session starts
from the stream's initial graph and ``s.play()`` replays the update chunks,
one query per chunk.

The registry spans the whole semiring family, not just ranking — the same
call drives non-float workloads end to end::

    veilgraph.session((src, dst), algorithm="sssp", sources=(0,))
    veilgraph.session((src, dst), algorithm="connected-components")
    veilgraph.session((src, dst), algorithm="katz", alpha=0.01)

``result.scores`` then carries the algorithm's own result dtype (f32
distances, int32 component labels, …); the engine's hot-set policy is
driven by each algorithm's float ``selection_view`` (label churn /
distance deltas for the traversal workloads).

Capacities are sized automatically from the source when no
:class:`EngineConfig` is given.  ``hot_node_capacity`` /
``hot_edge_capacity`` are upper bounds on the summary (|K| and |E_K|): they
default to the full node and edge capacities, so a fresh session never
overflow-falls-back, and explicit smaller values give the paper's
bounded-summary behaviour (a hot set beyond them is recomputed exactly).
Below the bounds the summary buffers follow each answer's hot set
(:func:`repro.core.engine.hot_bucket`), whatever the bounds are.

Migration from the pre-plugin API
---------------------------------
``VeilGraphEngine(cfg, on_query=...)`` keeps working — it runs PageRank
configured from the config's ``beta``/``num_iters``/``tol`` knobs.  New code
should prefer::

    s = veilgraph.session(src_dst, algorithm="hits", num_iters=50)
    s = veilgraph.session(src_dst, algorithm=PersonalizedPageRankAlgorithm(seeds=(3,)))

with the ``r``/``n``/``delta`` model knobs and buffer capacities passed as
keyword overrides.

The propagation backend for every sweep is likewise a config override:
``session(src_dst, backend="pallas")`` forces the destination-tiled Pallas
kernels (the one-hot-matmul MXU path for sum-of-products, the masked-reduce
variant for min/max semirings), ``"segment_sum"`` the sorted-XLA fallback,
and the default ``"auto"`` resolves per device (TPU → pallas) with
``$VEILGRAPH_BACKEND`` as the environment override.  Which semiring a sweep
runs over is the *algorithm's* declaration (``StreamingAlgorithm.semiring``
/ ``layout_specs``), not a session knob — see :mod:`repro.core.backend` and
:mod:`repro.core.semiring`.

Sharded execution is one more config override: pass a device mesh and the
engine partitions its cached edge layouts into one locally-sorted shard
per device and runs every O(E) sweep — exact, summarized boundary, fused —
as a shard_map partial push + semiring all-reduce::

    mesh = jax.make_mesh((jax.device_count(),), ("shards",))
    veilgraph.session((src, dst), algorithm="pagerank", mesh=mesh)

``mesh_axes`` optionally restricts which mesh axes the shard dimension
spans (default: all of them).  Results match the single-device engine —
bitwise for the min-semiring workloads, to f32 summation order for the
ranking family; see :mod:`repro.graph.partition`.
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Callable, Dict, Iterator, Optional, Tuple, Union

import numpy as np

from repro.core.algorithm import (Action, StreamingAlgorithm,
                                  available_algorithms, make_algorithm)
from repro.core.engine import EngineConfig, QueryStats, VeilGraphEngine
from repro.graph.generators import DATASETS, generate
from repro.stream import EdgeStream

GraphSource = Union[str, Tuple[np.ndarray, np.ndarray], EdgeStream]

#: EngineConfig fields accepted as keyword overrides by :func:`session`.
_CONFIG_KEYS = frozenset(f.name for f in fields(EngineConfig))


def _result_valid(scores: np.ndarray, active: np.ndarray) -> np.ndarray:
    """Vertices whose result value is an answer, not padding: active, and
    not the ⊕-identity sentinel of a min/max workload (+∞ unreachable
    distances, int-extreme labels of never-seen capacity slots)."""
    valid = np.asarray(active, bool).copy()
    if np.issubdtype(scores.dtype, np.floating):
        valid &= np.isfinite(scores)
    elif np.issubdtype(scores.dtype, np.integer):
        info = np.iinfo(scores.dtype)
        valid &= (scores != info.max) & (scores != info.min)
    return valid


def _top_ids(scores: np.ndarray, k: int, *, descending: bool = True,
             valid: Optional[np.ndarray] = None) -> np.ndarray:
    """Ids of the k best-ranked vertices (stable ties).  ``descending``
    follows the algorithm's ``rank_descending`` (False for distances /
    min-labels); sentinel/inactive vertices are dropped, so fewer than
    ``k`` ids may come back."""
    order = np.argsort(-scores if descending else scores, kind="stable")
    if valid is not None:
        order = order[valid[order]]
    return order[:k]


@dataclass
class QueryResult:
    """One served query: the result vector plus the engine's stats row.

    ``scores`` is the algorithm's ``result_view`` in its own dtype (f32
    ranks/distances, int32 component labels); ``valid`` masks the entries
    that are real answers (capacity padding, never-seen vertices and
    unreachable-∞ slots are False) and ``descending`` records the
    algorithm's ranking direction — both feed :meth:`top`.
    """

    scores: np.ndarray
    stats: QueryStats
    valid: Optional[np.ndarray] = None
    descending: bool = True

    @property
    def action(self) -> str:
        return self.stats.action

    def top(self, k: int = 10) -> np.ndarray:
        return _top_ids(self.scores, k, descending=self.descending,
                        valid=self.valid)


class VeilGraphSession:
    """A started engine plus the streaming conveniences around it.

    Construct via :func:`session`.  Usable as a context manager (``with`` …)
    so OnStop fires on exit; the raw engine stays reachable at ``.engine``
    for anything not surfaced here.
    """

    def __init__(self, engine: VeilGraphEngine,
                 stream: Optional[EdgeStream] = None):
        self.engine = engine
        self.stream = stream

    # ---- convenience views ----------------------------------------------
    @property
    def algorithm(self) -> StreamingAlgorithm:
        """The resolved :class:`StreamingAlgorithm` instance the engine
        runs (frozen dataclass — its knobs are readable fields)."""
        return self.engine.algorithm

    @property
    def scores(self) -> np.ndarray:
        """Current score vector (whatever the last query/start computed)."""
        return np.asarray(self.engine.ranks)

    @property
    def stats_log(self):
        """Engine-accumulated :class:`~repro.core.engine.QueryStats`, one
        row per served query (index -1 = the initial exact compute)."""
        return self.engine.stats_log

    def top(self, k: int = 10) -> np.ndarray:
        """Ids of the k best-ranked vertices under the *current* scores
        (without serving a query): descending for score algorithms,
        ascending for distances/labels; sentinel and inactive vertices are
        dropped, so fewer than ``k`` ids may come back."""
        scores = self.scores
        return _top_ids(
            scores, k,
            descending=self.algorithm.rank_descending,
            valid=_result_valid(scores,
                                np.asarray(self.engine.state.node_active)))

    # ---- streaming -------------------------------------------------------
    def add_edges(self, src, dst) -> "VeilGraphSession":
        """Buffer edge additions (int 1-D ``src``/``dst`` of equal length,
        ids < ``node_capacity``); applied at the next :meth:`query`.
        Returns ``self`` for chaining."""
        self.engine.register_add_edges(np.asarray(src), np.asarray(dst))
        return self

    def remove_edges(self, src, dst) -> "VeilGraphSession":
        """Buffer edge removals (resolved to live buffer slots at apply
        time; a removal matching no live edge is counted as requested but
        never resolved).  Returns ``self`` for chaining."""
        self.engine.register_remove_edges(np.asarray(src), np.asarray(dst))
        return self

    def query(self, msg: Optional[Dict] = None) -> QueryResult:
        """Serve one query (Alg. 1 lines 6-21): apply buffered updates, let
        the OnQuery policy pick repeat/approximate/exact, run it, and wrap
        the answer.  Returns a :class:`QueryResult` whose ``scores`` is the
        algorithm's ``result_view`` (dtype[node_capacity]) with ``stats``
        the engine's row for this query."""
        scores, stats = self.engine.query(msg)
        return QueryResult(
            scores=scores, stats=stats,
            valid=_result_valid(scores,
                                np.asarray(self.engine.state.node_active)),
            descending=self.algorithm.rank_descending)

    def play(self) -> Iterator[QueryResult]:
        """Replay the attached stream: one update chunk + one query each."""
        if self.stream is None:
            raise ValueError(
                "session was not built from an EdgeStream; feed updates "
                "with add_edges()/query() instead")
        for s, d in self.stream:
            self.add_edges(s, d)
            yield self.query()

    # ---- lifecycle -------------------------------------------------------
    def close(self):
        """Fire the OnStop UDF (also called by ``with``-block exit)."""
        self.engine.stop()

    def __enter__(self) -> "VeilGraphSession":
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def _resolve_source(graph_source: GraphSource):
    """-> (init_src, init_dst, stream_or_none, node_hint, edge_hint)."""
    if isinstance(graph_source, str):
        try:
            spec = DATASETS[graph_source]
        except KeyError:
            raise KeyError(
                f"unknown dataset {graph_source!r}; available: "
                f"{', '.join(sorted(DATASETS))}") from None
        src, dst = generate(spec)
        return src, dst, None, spec.nodes, src.shape[0]
    if isinstance(graph_source, EdgeStream):
        es = graph_source
        return (es.init_src, es.init_dst, es, es.total_nodes, es.total_edges)
    src, dst = graph_source
    src = np.asarray(src, np.int32)
    dst = np.asarray(dst, np.int32)
    nodes = 0
    if src.size:
        # raw edge lists carry no node-count bound, so leave headroom for
        # later add_edges with unseen ids (the engine rejects ids beyond
        # node_capacity rather than corrupting silently)
        nodes = int((int(max(src.max(), dst.max())) + 1) * 1.1) + 16
    return src, dst, None, nodes, src.shape[0]


def session(
    graph_source: GraphSource,
    algorithm: Union[StreamingAlgorithm, str] = "pagerank",
    config: Optional[EngineConfig] = None,
    *,
    on_start: Optional[Callable] = None,
    before_updates: Optional[Callable] = None,
    on_query: Optional[Callable] = None,
    on_query_result: Optional[Callable] = None,
    on_stop: Optional[Callable] = None,
    **overrides,
) -> VeilGraphSession:
    """Build and start a :class:`VeilGraphSession`.

    ``algorithm`` is a registry name (see
    :func:`repro.core.algorithm.available_algorithms`) or an instance.
    Keyword ``overrides`` split two ways: names matching
    :class:`EngineConfig` fields override the (auto-sized) config, the rest
    are forwarded to the algorithm factory::

        veilgraph.session("synth-citation", "personalized-pagerank",
                          r=0.3, delta=0.5, seeds=(0, 7), num_iters=50)
        veilgraph.session((src, dst), "hits", backend="pallas")

    ``hot_node_capacity`` / ``hot_edge_capacity`` default to the node and
    edge capacities.  They bound the summary; its buffers are sized from
    each answer's hot set below them (see the module docstring).

    ``quality_target=`` (e.g. ``0.95``) switches the engine to
    closed-loop quality control (:mod:`repro.core.control`): the fused
    query step measures drift on device and a controller steers the
    effective ``r``/``delta`` and exact-refresh cadence to keep
    estimated error inside ``1 - quality_target``.  Knob precedence: a
    knob you also pass explicitly (``quality_target=0.95, r=0.1``) is
    pinned at your value — the controller only adjusts the knobs you
    left to it.

    The five UDFs pass straight through to the engine.
    """
    init_src, init_dst, stream, node_hint, edge_hint = _resolve_source(
        graph_source)

    cfg_over = {k: v for k, v in overrides.items() if k in _CONFIG_KEYS}
    algo_params = {k: v for k, v in overrides.items() if k not in _CONFIG_KEYS}
    if cfg_over.get("quality_target") is not None:
        # knob precedence: an explicitly passed r/delta wins over the
        # controller — pin it unless the caller set control_* themselves
        cfg_over.setdefault("control_r", "r" not in cfg_over)
        cfg_over.setdefault("control_delta", "delta" not in cfg_over)
    # beta/num_iters/tol are EngineConfig fields only for the legacy
    # no-algorithm constructor; with an explicit algorithm they belong to
    # the algorithm itself, so forward them to the factory — and refuse to
    # drop them silently when they cannot reach it (instance passed, or the
    # factory doesn't take the knob).
    _legacy_knobs = [k for k in ("beta", "num_iters", "tol") if k in cfg_over]
    if isinstance(algorithm, StreamingAlgorithm):
        if _legacy_knobs:
            raise ValueError(
                f"{sorted(_legacy_knobs)} cannot be applied to an already-"
                f"constructed algorithm — pass them to "
                f"{type(algorithm).__name__}(...) instead")
    elif _legacy_knobs:
        from repro.core.algorithm import algorithm_factory, factory_accepts

        factory = algorithm_factory(algorithm)
        rejected = [k for k in _legacy_knobs
                    if not factory_accepts(factory, k)]
        if rejected:
            raise ValueError(
                f"algorithm {algorithm!r} does not accept {sorted(rejected)}")
        for k in _legacy_knobs:
            # the knob belongs to the algorithm once forwarded — leaving it
            # in cfg_over would double-apply it to EngineConfig and falsely
            # conflict with an explicitly passed config
            algo_params[k] = cfg_over.pop(k)
    algo = make_algorithm(algorithm, **algo_params)

    if config is None:
        node_cap = cfg_over.pop("node_capacity", max(node_hint, 2))
        edge_cap = cfg_over.pop(
            "edge_capacity", int(edge_hint * 1.15) + 1024)
        config = EngineConfig(
            node_capacity=node_cap,
            edge_capacity=edge_cap,
            # upper bounds only: the buffers follow the hot set below them
            hot_node_capacity=cfg_over.pop("hot_node_capacity", node_cap),
            hot_edge_capacity=cfg_over.pop("hot_edge_capacity", edge_cap),
            **cfg_over,
        )
    elif cfg_over:
        raise ValueError(
            f"pass either an explicit config or field overrides, not both: "
            f"{sorted(cfg_over)}")

    udfs = {}
    if on_start is not None:
        udfs["on_start"] = on_start
    if before_updates is not None:
        udfs["before_updates"] = before_updates
    if on_query is not None:
        udfs["on_query"] = on_query
    if on_query_result is not None:
        udfs["on_query_result"] = on_query_result
    if on_stop is not None:
        udfs["on_stop"] = on_stop

    engine = VeilGraphEngine(config, algo, **udfs)
    engine.start(init_src, init_dst)
    return VeilGraphSession(engine, stream)


def serve_session(
    graph_source: GraphSource,
    config: Optional[EngineConfig] = None,
    *,
    slots: int = 4,
    algorithm: Union[StreamingAlgorithm, str] = "pagerank",
    **overrides,
):
    """Build a started session and wrap it for multi-tenant serving.

    The sibling of :func:`session` for concurrent query workloads: one
    shared graph/engine, a
    :class:`~repro.serve.graph.GraphServingEngine` front door with
    ``slots`` static batch slots per algorithm lane::

        srv = veilgraph.serve_session((src, dst), slots=4)
        t1 = srv.submit("personalized-pagerank", seeds=(3,))
        t2 = srv.submit("sssp", sources=(17,))
        srv.run()
        t1.result, srv.stats.queries_per_s

    ``algorithm``/``config``/``overrides`` configure the underlying
    engine exactly as in :func:`session` (capacities, hot-set knobs,
    backend, mesh) — ``algorithm`` only sets the engine's base workload
    for the initial exact compute; served queries each carry their own.
    ``quality_target=`` enables the closed accuracy loop per serving
    lane: each wave's per-slot drift rides the existing row-delta
    transfer, a per-lane controller steers the effective knobs, and an
    SLO breach re-marks the lane's live slots cold so the next wave
    re-covers them (same knob precedence as :func:`session`).
    The underlying :class:`VeilGraphSession` stays reachable at
    ``.session`` and is closed by the serving engine's ``with``-exit.
    """
    from repro.serve.graph import GraphServingEngine

    base = session(graph_source, algorithm, config, **overrides)
    srv = GraphServingEngine(base.engine, slots=slots)
    srv.session = base
    return srv


__all__ = [
    "Action",
    "QueryResult",
    "VeilGraphSession",
    "available_algorithms",
    "serve_session",
    "session",
]
