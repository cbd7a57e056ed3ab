"""VeilGraph execution engine — the paper's Alg. 1 as a Python/JAX hybrid.

The engine is the orchestration layer: it ingests stream messages
(RegisterAddEdge / RegisterRemoveEdge / Query), buffers updates until a query
arrives, and serves each query through the five-UDF structure:

    OnStart -> [BeforeUpdates -> ApplyUpdates -> OnQuery ->
                {repeat-last | approximate | exact} -> OnQueryResult]* -> OnStop

The engine is **algorithm-generic**: everything rank-computation-specific
lives behind the :class:`~repro.core.algorithm.StreamingAlgorithm` plugin
(PageRank is just the default).  The engine owns the graph state, the update
buffers, the hot-set selection snapshots (previous degrees/activity) and the
UDF policy loop; the algorithm owns its per-vertex state pytree (ranks,
hub/authority vectors, teleport vectors, …) and its exact / summarized
kernels.

Heavy computation (update application, hot-set selection, summary
construction, power iterations) is jitted with static capacities; the UDFs
are host callbacks so users can express arbitrary policies, exactly as the
paper's API intends.

Prefer the session front door :func:`repro.api.session` for new code; the
``VeilGraphEngine(cfg, on_query=...)`` constructor (algorithm omitted)
remains supported as the legacy PageRank-only signature — the PageRank knobs
on :class:`EngineConfig` (``beta``/``num_iters``/``tol``) configure the
default algorithm in that case.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, replace
from typing import Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backend as B
from repro.core import spans
from repro.core.algorithm import (Action, AlgoState, PageRankAlgorithm,
                                  StreamingAlgorithm, make_algorithm,
                                  summaries_overflow)
from repro.core.hotset import select_hot_set
from repro.graph import graph as G


@dataclass
class EngineConfig:
    """Static engine configuration: buffer capacities, the paper's hot-set
    model knobs (r, n, Δ), and execution selection (backend, mesh,
    sharding, rebalancing).  Capacities are jit-static — changing them
    recompiles; everything the engine can vary per query is runtime state
    instead.  Field groups are commented inline below."""

    node_capacity: int
    edge_capacity: int
    # upper bounds of the summary buffers (|K| and |E_K|): a query whose
    # hot set exceeds one falls back to exact recomputation.  Below them
    # the buffers follow the hot set (see hot_bucket); mesh engines use
    # them as they are
    hot_node_capacity: int
    hot_edge_capacity: int
    # legacy PageRank knobs — configure the default algorithm when none is
    # passed to the engine (kept for the old constructor signature; plugin
    # algorithms carry their own numeric knobs)
    beta: float = 0.85
    num_iters: int = 30
    tol: float = 0.0
    # hot-set parameters (r, n, Δ) — the paper's model knobs
    r: float = 0.2
    n: int = 1
    delta: float = 0.1
    delta_hop_cap: int = 4
    degree_mode: str = "out"
    expand_both: bool = False
    # update chunks are padded to a multiple of this to bound recompiles
    update_pad: int = 1024
    # fused=True runs selection+summary+iteration as a single XLA program
    # (overflow fallback handled on host after a one-flag device read)
    fused: bool = True
    # propagation backend for every sweep: "pallas" (destination-tiled MXU
    # kernel; interpret mode off-TPU), "segment_sum" (sorted XLA fallback),
    # or "auto" (per device, overridable via $VEILGRAPH_BACKEND) — see
    # repro.core.backend
    backend: str = "auto"
    # per-shape kernel-geometry autotuning for the pallas push: "off" keeps
    # the TILE_N/CHUNK defaults; "cached" picks the analytic cost-model
    # argmin (or a cached/JSON-loaded tuning — deterministic, CI-safe);
    # "full" additionally times the top model-ranked candidates on synthetic
    # streams and caches the winner.  Tunings are keyed per shape and reused
    # across layout rebuilds; engine.autotune_runs counts timed searches.
    # See repro.kernels.spmv.autotune.
    autotune: str = "off"
    # storage dtype for baked edge weights ("bfloat16"/"float16"): halves
    # the weight column of the edge-stream HBM traffic.  Accumulation stays
    # f32 (jnp type promotion inside the semiring combine).  Only applied to
    # float32 semirings — integer-algebra layouts (e.g. min_min labels)
    # keep their native dtype.  None = no compression.
    weight_dtype: Optional[str] = None
    # device mesh for sharded execution: edge layouts are cut into one
    # locally-sorted shard per device over `mesh_axes` (default: every mesh
    # axis) and every O(E) sweep runs as a shard_map partial push + semiring
    # all-reduce; None = single-layout execution.  See
    # repro.graph.partition.build_sharded_layout
    mesh: Optional["jax.sharding.Mesh"] = None
    mesh_axes: Optional[Tuple[str, ...]] = None
    # shard count for the mesh layouts: None = one shard per device on
    # `mesh_axes`; a multiple of the device count runs surplus shards as a
    # per-device loop (how a 1-device dev box exercises S-way partitioning
    # and rebalancing)
    num_shards: Optional[int] = None
    # per-(shard, bucket) hot-edge slot cap for the mesh-sharded summary
    # construction: None keeps the conservative default C = ceil(H_cap/S)
    # (per-device E_K footprint S*C grows with H_cap even when hot edges
    # are well spread); a tighter cap shrinks the footprint to
    # S * shard_hot_edge_capacity and relies on the overflow flag (-> exact
    # fallback) for the rare skewed batch.  See
    # repro.core.pagerank._build_summary_sharded.
    shard_hot_edge_capacity: Optional[int] = None
    # shard-rebalancing trigger (mesh engines only): after each applied
    # update batch the engine measures per-shard live-edge imbalance
    # ((max - min) / mean, see repro.graph.partition.shard_imbalance) and
    # recuts the slot partition when it exceeds this threshold — streaming
    # appends land at the high-water mark, so the contiguous cut fills
    # tail-heavy without it.  None disables rebalancing (the pre-rebalance
    # contiguous-cut behaviour); rebalances are counted in
    # `engine.rebalances`.
    rebalance_threshold: Optional[float] = 1.0
    # closed-loop quality control (repro.core.control): an accuracy SLO in
    # (0, 1) — e.g. 0.95 — that replaces open-loop r/Δ tuning.  The fused
    # query step additionally computes an on-device drift estimate (the
    # algorithm's fixed-point residual sampled on `drift_probes` fixed
    # vertices + the residual mass frozen outside K), and a host-side
    # QualityController steers the *effective* r/Δ and requests exact
    # refreshes to keep the estimated error inside 1 - quality_target.
    # control_r/control_delta=False pin a knob at its configured value
    # (knob precedence: an explicitly passed r/delta wins — repro.api
    # clears the matching control_* flag).  None = open loop (no drift
    # computation, no controller).  Requires fused=True and an algorithm
    # with supports_fused.
    quality_target: Optional[float] = None
    control_r: bool = True
    control_delta: bool = True
    drift_probes: int = 64
    # epoch-versioned async rebuild (repro.core.epoch): queries serve an
    # immutable EpochSnapshot N while snapshot N+1's update application,
    # layout sorts and rebalance probe are dispatched-but-not-awaited —
    # JAX's async dispatch overlaps the rebuild with the host loop because
    # the engine stops forcing results between apply and query.  Epochs
    # promote at query/wave boundaries only (snapshot_lag ∈ {0, 1});
    # QueryStats/ServeStats grow epoch/snapshot_lag columns.  Requires
    # fused=True and a supports_fused algorithm (like quality_target).
    async_rebuild: bool = False


@dataclass
class QueryStats:
    """One row of engine observability per served query: the action taken,
    wall time, graph/hot-set/summary sizes (the paper's model statistics —
    ``vertex_ratio``/``edge_ratio`` are Figs. 4/8's axes), update
    accounting, and the overflow/rebalance flags."""

    query_id: int
    action: str
    wall_time_s: float
    num_nodes: int
    num_edges: int
    num_hot: int = 0
    num_kr: int = 0
    num_kn: int = 0
    num_kdelta: int = 0
    num_ek: int = 0
    num_eb: int = 0
    iterations: int = 0
    overflow_fallback: bool = False
    # updates integrated by this query: pending_applied = edge additions +
    # *resolved* removals (a buffered removal that matches no live edge slot
    # is counted in removals_requested but not here)
    pending_applied: int = 0
    removals_requested: int = 0
    removals_resolved: int = 0
    # True when this query's applied updates pushed per-shard live-edge
    # imbalance past the threshold and the edge partition was recut
    rebalanced: bool = False
    algorithm: str = "pagerank"
    # closed-loop quality columns (quality_target engines only): the drift
    # estimate this query observed, the controller's error-based quality
    # estimate, the effective knobs it chose, and whether it forced an
    # exact refresh to stay inside the SLO
    drift: float = 0.0
    quality_est: float = 1.0
    r_eff: float = 0.0
    delta_eff: float = 0.0
    refreshed: bool = False
    # async-pipeline staleness columns (async_rebuild engines; sync engines
    # keep the zeros): the epoch this query was served from, and how many
    # epochs the served snapshot trailed the newest dispatched build when
    # the answer was computed (0 = caught up; never exceeds 1)
    epoch: int = 0
    snapshot_lag: int = 0
    # static sizes of the summary buffers this answer ran at (|K| and |E_K|
    # slots; 0 when no summary was built) — num_ek / hot_edge_bucket is
    # how full the edge buffer was
    hot_node_bucket: int = 0
    hot_edge_bucket: int = 0

    @property
    def vertex_ratio(self) -> float:
        return self.num_hot / max(self.num_nodes, 1)

    @property
    def edge_ratio(self) -> float:
        # summary graph edges = E_K ∪ E_B, as a fraction of |E| (paper Figs 4/8/…)
        return (self.num_ek + self.num_eb) / max(self.num_edges, 1)


#: smallest summary buffer the ladder of :func:`hot_bucket` hands out
BUCKET_FLOOR = 16


def hot_bucket(count: int, current: int, capacity: int) -> int:
    """Static size of a summary buffer that must hold ``count`` entries.

    A ladder of powers of two with 2× headroom: a bucket of ``current``
    slots (0 before the first query) is kept while ``count`` fits in it,
    and otherwise replaced by the power of two at or above
    ``max(2·count, BUCKET_FLOOR)``.  It never shrinks and never passes
    ``capacity``, the configured upper bound, so an engine compiles at
    most log2(capacity) sizes over its life and steady traffic one.  A
    count above ``capacity`` gets ``capacity``: the summary then
    overflows and the query falls back to exact recomputation.
    """
    if current and count <= current:
        return current
    want = max(2 * count, BUCKET_FLOOR)
    return min(max(1 << (want - 1).bit_length(), current), capacity)


# Default UDFs ---------------------------------------------------------------


def default_before_updates(pending: int, stats: Dict) -> bool:
    """Default BeforeUpdates UDF: always integrate pending updates."""
    return True


def default_on_query(query_id: int, view: Dict) -> Action:
    """Default OnQuery UDF: always take the summarized fast path."""
    return Action.APPROXIMATE


class VeilGraphEngine:
    """Streaming approximate graph-processing engine.

    ``algorithm`` is a :class:`StreamingAlgorithm` instance or registry name
    (``"pagerank"``, ``"personalized-pagerank"``, ``"hits"``, …).  Omitted,
    the engine runs PageRank configured from the legacy ``EngineConfig``
    knobs — the paper's case study and the pre-plugin constructor signature.
    """

    def __init__(
        self,
        config: EngineConfig,
        algorithm: Union[StreamingAlgorithm, str, None] = None,
        *,
        on_start: Optional[Callable] = None,
        before_updates: Callable[[int, Dict], bool] = default_before_updates,
        on_query: Callable[[int, Dict], Action] = default_on_query,
        on_query_result: Optional[Callable] = None,
        on_stop: Optional[Callable] = None,
    ):
        self.config = config
        if config.mesh is None and config.num_shards is not None:
            # the field is only consumed by the mesh layout/rebalance path;
            # accepting it meshless would silently run unsharded
            raise ValueError(
                "EngineConfig.num_shards requires mesh= (sharding and "
                "rebalancing are mesh-engine features; a 1-device box can "
                "pass a 1-device mesh with num_shards=S)")
        if config.mesh is not None:
            from repro.graph.partition import auto_axes

            self.config = config = replace(config,
                                           mesh=auto_axes(config.mesh))
        self.backend = B.resolve_backend(config.backend)
        if algorithm is None:
            # legacy shim: PageRank from the config's scalar knobs
            algorithm = PageRankAlgorithm(
                beta=config.beta, num_iters=config.num_iters, tol=config.tol
            )
        self.algorithm = make_algorithm(algorithm)
        self._on_start = on_start
        self._before_updates = before_updates
        self._on_query = on_query
        self._on_query_result = on_query_result
        self._on_stop = on_stop

        self.state = G.empty(config.node_capacity, config.edge_capacity)
        self.algo_state: AlgoState = self._init_algo_state()
        # amortized edge-layout cache: sorted once per applied update batch,
        # reused across queries and by every sweep in between
        self._edge_layouts: Optional[Tuple[B.EdgeLayout, ...]] = None
        self.layout_builds = 0  # observability: how many sorts actually ran
        # batch width hint for autotune keys: 1 for single-query engines;
        # the serving engine sets this to its slot count so batched sweeps
        # tune for the [B, chunk] @ [chunk, tile_n] shape they actually run
        self.autotune_batch_hint = 1
        # shard-rebalancing state (mesh engines): the current slot→shard
        # assignment (None = the contiguous cut), how many recuts have
        # happened, and the last measured imbalance
        self._shard_slots = None
        self.rebalances = 0
        self.last_imbalance = 0.0
        self.deg_prev = jnp.zeros((config.node_capacity,), jnp.int32)
        self.active_prev = jnp.zeros((config.node_capacity,), bool)
        self._pending_src: List[np.ndarray] = []
        self._pending_dst: List[np.ndarray] = []
        self._pending_len: List[Optional[np.ndarray]] = []
        self._pending_removals: List = []
        self._pending_count = 0
        self._pending_removal_count = 0
        # closed-loop quality control: host-side SLO controller + fixed
        # on-device probe set (built once; rides the fused step under
        # with_drift=True at zero extra host syncs)
        self.controller = None
        self._probe_ids = None
        if config.quality_target is not None:
            from repro.core.control import (QualityController,
                                            default_probe_ids)

            if not (config.fused and self.algorithm.supports_fused):
                raise ValueError(
                    "quality_target requires the fused query path "
                    f"(fused=True and a supports_fused algorithm; got "
                    f"fused={config.fused}, "
                    f"algorithm={self.algorithm.name!r})")
            self.controller = QualityController(
                config.quality_target,
                r0=config.r, delta0=config.delta,
                adjust_r=config.control_r,
                adjust_delta=config.control_delta,
                contraction=self.algorithm.drift_contraction,
            )
            self._probe_ids = default_probe_ids(
                config.node_capacity, config.drift_probes)
        # epoch-versioned async rebuild (repro.core.epoch): the pipeline
        # holds the served snapshot + the in-flight build; _async_specs is
        # the ordered set of normalized layout specs every new snapshot
        # eagerly dispatches (seeded from the algorithm, extended by the
        # serving engine's per-lane algorithms)
        self._pipeline = None
        self._async_specs: Dict = {}
        if config.async_rebuild:
            if not (config.fused and self.algorithm.supports_fused):
                raise ValueError(
                    "async_rebuild requires the fused query path "
                    f"(fused=True and a supports_fused algorithm; got "
                    f"fused={config.fused}, "
                    f"algorithm={self.algorithm.name!r})")
            for spec in map(B.normalize_layout_spec,
                            self.algorithm.layout_specs):
                self._async_specs[spec] = True
        # updates integrated while serving repeat-last answers — lets
        # policies threshold on staleness, not just the current batch
        self._stale_updates = 0
        self.stats_log: List[QueryStats] = []
        self._query_id = 0
        self._started = False
        # summary buffer sizes (|K|, |E_K| slots) of the split approximate
        # step, grown by hot_bucket; bucket_growths counts the changes
        # (each one compiles a summarized_step variant)
        self._hot_buckets = (0, 0)
        self.bucket_growths = 0

    @property
    def ranks(self) -> jax.Array:
        """The algorithm's result vector (legacy alias: PageRank's ranks).
        Any dtype — f32 scores, f32 distances, int32 component labels."""
        return self.algorithm.result_view(self.algo_state)

    def _init_algo_state(self) -> AlgoState:
        """init_state + one-time validation against the algorithm's
        declared ``state_dtypes`` (so e.g. an int32 label vector can't
        silently decay to float inside a custom plugin)."""
        state = self.algorithm.init_state(self.state)
        for key, want in self.algorithm.state_dtypes.items():
            if key not in state:
                raise ValueError(
                    f"{self.algorithm.name}.init_state missing declared "
                    f"state key {key!r}")
            got = jnp.asarray(state[key]).dtype
            if got != jnp.dtype(want):
                raise ValueError(
                    f"{self.algorithm.name} state {key!r} declared "
                    f"{want} but init_state produced {got}")
        return state

    # ---- lifecycle -------------------------------------------------------
    def start(self, init_src: np.ndarray, init_dst: np.ndarray) -> QueryStats:
        """OnStart + load the initial graph G and compute the initial exact
        result (the paper's protocol: results already exist for G)."""
        if self._on_start:
            self._on_start(self)
        self.state = G.from_edges(
            init_src, init_dst, self.config.node_capacity, self.config.edge_capacity
        )
        self._invalidate_layouts()
        self.algo_state = self._init_algo_state()
        t0 = time.perf_counter()
        self.algo_state, iters = self.algorithm.exact(
            self.algo_state, self.state,
            layouts=self.edge_layouts(), backend=self.backend)
        self.ranks.block_until_ready()
        wall = time.perf_counter() - t0
        self.deg_prev = self._degree_snapshot()
        self.active_prev = jnp.copy(self.state.node_active)
        if self.config.async_rebuild:
            from repro.core.epoch import AsyncRebuildPipeline

            # epoch 0 = the initial graph; its layouts were just built for
            # the exact pass, so _make_snapshot seeds them without
            # re-sorting.  Epoch 0 is never promoted, so fetch its count
            # vector here (start() is a host boundary anyway).
            snap0 = self._make_snapshot(0)
            self._finalize_promotion(snap0)
            self._pipeline = AsyncRebuildPipeline(snap0)
        self._started = True
        st = QueryStats(
            query_id=-1,
            action="initial-exact",
            wall_time_s=wall,
            num_nodes=int(self.state.num_active_nodes()),
            num_edges=int(self.state.num_live_edges()),
            iterations=int(iters),
            algorithm=self.algorithm.name,
        )
        self.stats_log.append(st)
        return st

    def stop(self):
        """OnStop: fire the shutdown UDF (no device state is torn down)."""
        if self._on_stop:
            self._on_stop(self)

    # ---- stream ingestion --------------------------------------------------
    @staticmethod
    def _check_shapes(src: np.ndarray, dst: np.ndarray):
        # mismatched shapes would broadcast or truncate inside the jitted
        # scatters — fail loudly at ingestion
        if src.ndim != 1 or dst.ndim != 1 or src.shape != dst.shape:
            raise ValueError(
                f"src/dst must be 1-D arrays of equal length; got shapes "
                f"{src.shape} and {dst.shape}")

    def _check_ids(self, src: np.ndarray, dst: np.ndarray):
        # out-of-range ids would silently clamp/drop inside the jitted
        # scatters and corrupt neighbouring vertices' results
        self._check_shapes(src, dst)
        if src.size == 0:
            return
        lo = min(int(src.min()), int(dst.min()))
        hi = max(int(src.max()), int(dst.max()))
        if lo < 0 or hi >= self.config.node_capacity:
            raise ValueError(
                f"edge endpoint id {lo if lo < 0 else hi} outside "
                f"[0, node_capacity={self.config.node_capacity})")

    def register_add_edges(self, src: np.ndarray, dst: np.ndarray,
                           weights: Optional[np.ndarray] = None):
        """Alg. 1 RegisterAddEdge: buffer an edge-addition chunk (validated
        host-side) until the next query's ApplyUpdates stage.

        ``weights`` optionally streams a per-edge length column alongside
        the endpoints (same 1-D shape); it lands in
        ``GraphState.edge_len`` and feeds every ``weight="length"`` layout
        (SSSP).  Omitted, new edges carry unit length — chunks with and
        without weights can be mixed freely on one stream.
        """
        src = np.asarray(src, np.int32)
        dst = np.asarray(dst, np.int32)
        self._check_ids(src, dst)
        if weights is not None:
            weights = np.asarray(weights, np.float32)
            if weights.shape != src.shape:
                raise ValueError(
                    f"weights must match src/dst shape {src.shape}; got "
                    f"{weights.shape}")
        self._pending_src.append(src)
        self._pending_dst.append(dst)
        self._pending_len.append(weights)
        self._pending_count += src.shape[0]

    def register_remove_edges(self, src: np.ndarray, dst: np.ndarray):
        """Alg. 1 RegisterRemoveEdge (the paper evaluates e+ only and leaves
        removals to future work; the engine supports them end-to-end).
        Removals are buffered and resolved to buffer slots at apply time; a
        removal that matches no live slot counts as *requested* but never as
        *resolved* in the query stats."""
        src = np.asarray(src, np.int32)
        dst = np.asarray(dst, np.int32)
        self._check_shapes(src, dst)
        self._pending_removals.append((src, dst))
        self._pending_count += len(src)
        self._pending_removal_count += len(src)

    @property
    def pending_updates(self) -> int:
        """Buffered updates (additions + removals) not yet applied."""
        return self._pending_count

    # ---- internals -----------------------------------------------------------
    def edge_layouts(self) -> Tuple[B.AnyEdgeLayout, ...]:
        """Sorted edge layouts per ``algorithm.layout_specs`` — built at most
        once per applied update batch (graph mutations invalidate them).

        With ``config.mesh`` set, each cached entry is a
        :class:`~repro.core.backend.ShardedEdgeLayout` — one locally-sorted
        stream *per shard*, so the amortized sort cost is paid (and cached)
        per shard, never across shards — and every consuming sweep runs
        through the shard_map-ed push automatically.
        """
        if self._edge_layouts is None:
            self._edge_layouts = tuple(
                self._build_spec_layout(self.state, spec)
                for spec in map(B.normalize_layout_spec,
                                self.algorithm.layout_specs)
            )
            self.layout_builds += 1
        return self._edge_layouts

    def _build_spec_layout(self, state: G.GraphState,
                           spec: Tuple) -> B.AnyEdgeLayout:
        """Build (dispatch) the sorted layout for one *normalized* spec
        against an explicit graph state — the single layout constructor
        shared by the sync cache (:meth:`edge_layouts`), the serving
        engine's per-lane cache, and :class:`~repro.core.epoch.
        EpochSnapshot` builds (which pass a frozen snapshot state rather
        than ``self.state``).  Mesh engines get a placed
        ``ShardedEdgeLayout`` cut at the current slot assignment."""
        w, rev, s = spec
        tile_n, chunk = self._tuned_geometry(s)
        if self.config.mesh is not None:
            from repro.graph.partition import (build_sharded_layout,
                                               place_sharded_layout)

            return place_sharded_layout(
                build_sharded_layout(
                    state, mesh=self.config.mesh,
                    axes=self.config.mesh_axes,
                    num_shards=self.config.num_shards,
                    weight=w, reverse=rev,
                    semiring=s, slots=self._shard_slots,
                    chunk=chunk, tile_n=tile_n,
                    weight_dtype=self._weight_dtype_for(s)))
        return B.build_layout(
            state, weight=w, reverse=rev, semiring=s,
            chunk=B.CHUNK if chunk is None else chunk,
            tile_n=tile_n,
            weight_dtype=self._weight_dtype_for(s))

    def _tuned_geometry(self, semiring) -> Tuple[Optional[int], Optional[int]]:
        """Autotuned ``(tile_n, chunk)`` for one layout spec, resolved at
        layout-build time so every consuming sweep (exact, summarized,
        batched) inherits it through the layout meta; ``(None, None)`` when
        autotuning is off (push then uses the hardcoded defaults)."""
        cfg = self.config
        if cfg.autotune == "off":
            return None, None
        from repro.core.semiring import resolve_semiring
        from repro.kernels.spmv import autotune as AT

        s = resolve_semiring(semiring)
        e_cap = cfg.edge_capacity
        if cfg.mesh is not None:
            from repro.graph.partition import mesh_shard_count

            num_shards = (cfg.num_shards if cfg.num_shards is not None
                          else mesh_shard_count(cfg.mesh, cfg.mesh_axes))
            e_cap = -(-e_cap // num_shards)  # per-shard stream length
        return AT.tune_for_push(
            edge_capacity=e_cap,
            num_segments=cfg.node_capacity,
            batch=self.autotune_batch_hint,
            dtype=s.dtype,
            reduce=s.add,
            mode=cfg.autotune)

    def _weight_dtype_for(self, semiring) -> Optional[str]:
        """Engine-level weight compression applies only to f32 semirings;
        integer algebras (min_min labels) keep their native dtype rather
        than erroring out of a mixed-algebra algorithm."""
        wd = self.config.weight_dtype
        if wd is None:
            return None
        from repro.core.semiring import resolve_semiring

        if jnp.dtype(resolve_semiring(semiring).dtype) != jnp.float32:
            return None
        return wd

    @property
    def autotune_runs(self) -> int:
        """Measured (timed) autotune searches so far — cache hits and
        analytic-only resolutions excluded."""
        from repro.kernels.spmv import autotune as AT

        return AT.run_count()

    def _invalidate_layouts(self):
        self._edge_layouts = None

    def _maybe_rebalance(self) -> bool:
        """Recut the edge partition when streaming has skewed per-shard
        live-edge counts past ``config.rebalance_threshold``.

        Runs once per applied update batch (never in the query hot loop),
        only on mesh-configured engines.  On a recut the cached layouts are
        invalidated so the next :meth:`edge_layouts` build migrates every
        stream to the balanced assignment with one static-shaped gather;
        ``engine.rebalances`` counts the recuts and
        ``engine.last_imbalance`` records the most recent measurement.
        """
        cfg = self.config
        if cfg.mesh is None or cfg.rebalance_threshold is None:
            return False
        from repro.graph.partition import (balanced_shard_slots,
                                           mesh_shard_count,
                                           rebalance_decision,
                                           shard_slots)

        num_shards = (cfg.num_shards if cfg.num_shards is not None
                      else mesh_shard_count(cfg.mesh, cfg.mesh_axes))
        slots = self._shard_slots
        if slots is None:
            slots = jnp.asarray(
                shard_slots(self.state.edge_capacity, num_shards))
        # the measurement, the threshold compare and the recut signal all
        # stay on device; exactly one (bool, f32) pair crosses to host per
        # applied batch
        with spans.span(spans.SYNC_REBALANCE):
            should, imbalance = jax.device_get(rebalance_decision(
                self.state, slots, jnp.float32(cfg.rebalance_threshold)))
        self.last_imbalance = float(imbalance)
        rebalanced = bool(should)
        if rebalanced:
            self._shard_slots = balanced_shard_slots(
                self.state, num_shards=num_shards)
            self.rebalances += 1
            self._invalidate_layouts()
        return rebalanced

    def _degree_snapshot(self) -> jax.Array:
        # NOTE: must copy — add_edges donates the state buffers, so an alias
        # into the old state would be deleted by the next update.
        if self.config.degree_mode == "out":
            return jnp.copy(self.state.out_deg)
        if self.config.degree_mode == "in":
            return jnp.copy(self.state.in_deg)
        return self.state.out_deg + self.state.in_deg

    def _apply_pending(self, preserve: bool = False) -> Tuple[int, int, int]:
        """Apply buffered updates.  Returns
        ``(applied, removals_requested, removals_resolved)`` where
        ``applied`` counts additions + resolved removals.

        ``preserve=True`` (the async pipeline) applies through the
        non-donating mutation variants so the served snapshot's buffers —
        which alias the pre-update state — stay valid."""
        if not self._pending_count:
            return 0, 0, 0
        remove_fn = (G.remove_edges_by_slot_preserving if preserve
                     else G.remove_edges_by_slot)
        add_fn = G.add_edges_preserving if preserve else G.add_edges
        removals_requested = self._pending_removal_count
        removals_resolved = 0
        if self._pending_removals:
            r_src = np.concatenate([a for a, _ in self._pending_removals])
            r_dst = np.concatenate([b for _, b in self._pending_removals])
            slots = G.find_edge_slots(self.state, r_src, r_dst)
            self.state = remove_fn(self.state, jnp.asarray(slots))
            removals_resolved = int((slots >= 0).sum())
            if removals_resolved:
                self._invalidate_layouts()
            self._pending_removals.clear()
            self._pending_removal_count = 0
        applied = removals_resolved
        if not self._pending_src:
            self._pending_count = 0
            return applied, removals_requested, removals_resolved
        src = np.concatenate(self._pending_src)
        dst = np.concatenate(self._pending_dst)
        if any(w is not None for w in self._pending_len):
            # mixed weighted/unweighted chunks: unweighted ones take the
            # unit length explicitly so the concatenation lines up
            lens = np.concatenate([
                w if w is not None else np.ones(s.shape[0], np.float32)
                for s, w in zip(self._pending_src, self._pending_len)])
        else:
            lens = None
        self._invalidate_layouts()
        pad = self.config.update_pad
        k = src.shape[0]
        # pad slots must not change degrees, so updates are split into
        # pad-sized exact chunks plus one remainder chunk whose shape
        # recompiles at most `update_pad` distinct sizes.
        for lo in range(0, k, pad):
            hi = min(lo + pad, k)
            self.state = add_fn(
                self.state, jnp.asarray(src[lo:hi]), jnp.asarray(dst[lo:hi]),
                None if lens is None else jnp.asarray(lens[lo:hi]),
            )
            applied += hi - lo
        self._pending_src.clear()
        self._pending_dst.clear()
        self._pending_len.clear()
        self._pending_count = 0
        return applied, removals_requested, removals_resolved

    def _stats_view(self, pending: int, applied: int) -> Dict:
        with spans.span(spans.SYNC_VIEW):
            num_nodes = int(self.state.num_active_nodes())
        with spans.span(spans.SYNC_VIEW):
            num_edges = int(self.state.num_live_edges())
        return {
            "pending": pending,
            "applied": applied,
            # everything not reflected in the current scores: updates
            # integrated under earlier repeat-last answers + this query's
            "since_compute": self._stale_updates + applied + pending,
            "num_nodes": num_nodes,
            "num_edges": num_edges,
            "algorithm": self.algorithm.name,
        }

    def _run_exact(self, st: QueryStats, layouts):
        self.algo_state, iters = self.algorithm.exact(
            self.algo_state, self.state, layouts=layouts,
            backend=self.backend)
        with spans.span(spans.SYNC_ITERS):
            st.iterations = int(iters)

    # ---- epoch-versioned async rebuild -----------------------------------
    def _make_snapshot(self, epoch: int, *, applied: int = 0,
                       removals_requested: int = 0,
                       removals_resolved: int = 0):
        """Freeze the current state as :class:`EpochSnapshot` ``epoch`` and
        *dispatch* everything the snapshot serves from: layout sorts for
        every spec the engine has ever served, the count vector, the
        hot-set baselines, and (mesh engines, post-update epochs) the
        rebalance verdict.  Nothing here is awaited — the snapshot's
        device work overlaps with whatever the host does next."""
        from repro.core.epoch import EpochSnapshot, snapshot_counts

        snap = EpochSnapshot(
            epoch=epoch,
            state=self.state,
            deg=self._degree_snapshot(),
            active=jnp.copy(self.state.node_active),
            counts=snapshot_counts(self.state),
            applied=applied,
            removals_requested=removals_requested,
            removals_resolved=removals_resolved,
            rebalance_probe=(self._dispatch_rebalance_probe()
                             if applied else None),
        )
        if self._edge_layouts is not None:
            # the sync cache is valid for this exact state (start() path):
            # seed it into the snapshot instead of re-sorting
            for spec, layout in zip(
                    map(B.normalize_layout_spec, self.algorithm.layout_specs),
                    self._edge_layouts):
                snap.layouts[spec] = layout
        built = False
        for spec in self._async_specs:
            if spec not in snap.layouts:
                snap.layout_for(spec, self._build_spec_layout)
                built = True
        if built:
            self.layout_builds += 1
        return snap

    def _snapshot_layouts(self, snap) -> Tuple[B.AnyEdgeLayout, ...]:
        """The snapshot-bound equivalent of :meth:`edge_layouts`: this
        epoch's sorted layouts per ``algorithm.layout_specs``."""
        return tuple(
            snap.layout_for(spec, self._build_spec_layout)
            for spec in map(B.normalize_layout_spec,
                            self.algorithm.layout_specs))

    def _dispatch_rebalance_probe(self):
        """Dispatch (never await) the on-device rebalance verdict for the
        state being snapshotted; the (bool, f32) pair is fetched once at
        promotion by :meth:`_finalize_promotion` — the async replacement
        for the sync path's per-batch :meth:`_maybe_rebalance` sync."""
        cfg = self.config
        if cfg.mesh is None or cfg.rebalance_threshold is None:
            return None
        from repro.graph.partition import (mesh_shard_count,
                                           rebalance_decision, shard_slots)

        num_shards = (cfg.num_shards if cfg.num_shards is not None
                      else mesh_shard_count(cfg.mesh, cfg.mesh_axes))
        slots = self._shard_slots
        if slots is None:
            slots = jnp.asarray(
                shard_slots(self.state.edge_capacity, num_shards))
        return rebalance_decision(
            self.state, slots, jnp.float32(cfg.rebalance_threshold))

    def _finalize_promotion(self, snap) -> bool:
        """Host-side bookkeeping for a freshly promoted snapshot: fetch its
        dispatched count vector (the per-epoch replacement for the sync
        path's per-query ``int(num_active_nodes())``) and, on mesh
        engines, its rebalance verdict — recutting the slot partition for
        the *next* epoch's builds when streaming has skewed the shards.
        Returns True when a recut happened."""
        with spans.span(spans.SYNC_VIEW):
            counts = np.asarray(jax.device_get(snap.counts))
        snap.num_nodes = int(counts[0])
        snap.num_edges = int(counts[1])
        if snap.rebalance_probe is None:
            return False
        with spans.span(spans.SYNC_REBALANCE):
            should, imbalance = jax.device_get(snap.rebalance_probe)
        snap.rebalance_probe = None
        self.last_imbalance = float(imbalance)
        if not bool(should):
            return False
        from repro.graph.partition import (balanced_shard_slots,
                                           mesh_shard_count)

        cfg = self.config
        num_shards = (cfg.num_shards if cfg.num_shards is not None
                      else mesh_shard_count(cfg.mesh, cfg.mesh_axes))
        self._shard_slots = balanced_shard_slots(
            self.state, num_shards=num_shards)
        self.rebalances += 1
        self._invalidate_layouts()
        return True

    def _async_integrate(self) -> Tuple[int, int, int]:
        """ApplyUpdates, async flavour: apply buffered updates through the
        non-donating variants and dispatch the next epoch's snapshot build
        (the served snapshot keeps its buffers).  Called *after* the
        query's compute has been dispatched against the served snapshot,
        so the result fetch never waits on this work.  Returns the applied
        counts; an all-unresolved removal batch mutates nothing and
        dispatches no epoch."""
        pipe = self._pipeline
        applied, requested, resolved = self._apply_pending(preserve=True)
        if applied:
            pipe.dispatch(self._make_snapshot(
                pipe.latest_epoch + 1, applied=applied,
                removals_requested=requested, removals_resolved=resolved))
        return applied, requested, resolved

    def _run_exact_on(self, snap, st: QueryStats, layouts):
        """Exact recompute pinned to the served snapshot (refresh/fallback
        in the async path must not leak the in-flight epoch's graph);
        ``layouts`` are the snapshot's (:meth:`_snapshot_layouts`)."""
        self.algo_state, iters = self.algorithm.exact(
            self.algo_state, snap.state, layouts=layouts,
            backend=self.backend)
        with spans.span(spans.SYNC_ITERS):
            st.iterations = int(iters)

    def _approximate_step(self, graph: G.GraphState, layouts, r_now: float,
                          delta_now: float):
        """Dispatch one approximate step on ``graph`` (the live state, or
        the served snapshot's): the one place both query paths run it.

        The hot set runs first (:func:`~repro.core.fused.hot_set_step`),
        and its two counts, |K| and |E_K|, come to the host in one read.
        The summaries and the sweep
        (:func:`~repro.core.fused.summarized_step`) then run at the sizes
        :func:`hot_bucket` picks from them, so the summary's compaction,
        sort and sweep span the hot set, not the configured capacities;
        they take E_K from the hot set step's edge mask.
        Mesh engines keep the configured capacities (the sharded summary's
        per-shard slots derive from them) in the one-program
        :func:`~repro.core.fused.fused_query_step`.

        Returns ``(new_algo_state, QueryStepStats, (node_bucket,
        edge_bucket))``; the stats stay on the device.
        """
        from repro.core.fused import (fused_query_step, hot_set_step,
                                      summarized_step)

        cfg = self.config
        args = (graph, self.algo_state, self.deg_prev, self.active_prev,
                jnp.float32(r_now), jnp.float32(delta_now))
        select = dict(algo=self.algorithm, n=cfg.n,
                      delta_hop_cap=cfg.delta_hop_cap,
                      degree_mode=cfg.degree_mode,
                      expand_both=cfg.expand_both)
        with_drift = self.controller is not None
        if cfg.mesh is not None:
            caps = (cfg.hot_node_capacity, cfg.hot_edge_capacity)
            with spans.span(spans.STEP):
                new_state, qs = fused_query_step(
                    *args, self._probe_ids,
                    hot_node_capacity=caps[0], hot_edge_capacity=caps[1],
                    layouts=layouts, backend=self.backend,
                    shard_bucket_capacity=cfg.shard_hot_edge_capacity,
                    with_drift=with_drift, **select)
            return new_state, qs, caps
        with spans.span(spans.STEP):
            hot, hstats, counts, ek = hot_set_step(*args, **select)
        with spans.span(spans.SYNC_COUNTS):
            num_hot, num_ek = (int(c) for c in jax.device_get(counts))
        caps = (hot_bucket(num_hot, self._hot_buckets[0],
                           cfg.hot_node_capacity),
                hot_bucket(num_ek, self._hot_buckets[1],
                           cfg.hot_edge_capacity))
        if caps != self._hot_buckets:
            self._hot_buckets = caps
            self.bucket_growths += 1
        with spans.span(spans.STEP):
            new_state, qs = summarized_step(
                graph, self.algo_state, hot, hstats, self._probe_ids,
                algo=self.algorithm,
                hot_node_capacity=caps[0], hot_edge_capacity=caps[1],
                layouts=layouts, backend=self.backend,
                with_drift=with_drift, ek_mask=ek)
        return new_state, qs, caps

    def _query_async(self) -> QueryStats:
        """Serve one query from the epoch pipeline (the scores are fetched
        by :meth:`query`).

        The wave order is what buys the overlap: (1) promote the finished
        build at the boundary, (2) dispatch this query's compute against
        the served snapshot, (3) integrate pending updates + dispatch the
        next epoch, and only then (4) fetch the result — which was
        enqueued before the rebuild work, so the fetch waits on the query
        compute alone.  Updates integrated at query q become visible at
        q+1's promotion and are charged to that promoted epoch's stats
        row."""
        qid = self._query_id
        self._query_id += 1
        cfg = self.config
        pipe = self._pipeline

        # (1) wave boundary: flip in the finished build, if any
        promoted = pipe.promote()
        rebalanced = False
        if promoted is not None:
            rebalanced = self._finalize_promotion(promoted)
        snap = pipe.current
        applied = promoted.applied if promoted is not None else 0
        removals_requested = (promoted.removals_requested
                              if promoted is not None else 0)
        removals_resolved = (promoted.removals_resolved
                             if promoted is not None else 0)

        view = {
            "pending": self._pending_count,
            "applied": applied,
            "since_compute": (self._stale_updates + applied
                              + self._pending_count),
            "num_nodes": snap.num_nodes,
            "num_edges": snap.num_edges,
            "algorithm": self.algorithm.name,
            "epoch": snap.epoch,
        }
        integrate = self._before_updates(self._pending_count, view)
        action = self._on_query(qid, view)
        st = QueryStats(
            query_id=qid,
            action=action.value,
            wall_time_s=0.0,
            num_nodes=snap.num_nodes,
            num_edges=snap.num_edges,
            pending_applied=applied,
            removals_requested=removals_requested,
            removals_resolved=removals_resolved,
            rebalanced=rebalanced,
            algorithm=self.algorithm.name,
            epoch=snap.epoch,
        )

        # (2) dispatch this query's compute on the served snapshot — no
        # block_until_ready, and no host transfer until step (4) but the
        # two counts that size the summary
        ctl = self.controller
        new_state = qs = None
        if action == Action.APPROXIMATE:
            r_now = ctl.r_eff if ctl is not None else cfg.r
            delta_now = ctl.delta_eff if ctl is not None else cfg.delta
            with spans.span(spans.LAYOUT):
                layouts = self._snapshot_layouts(snap)
            new_state, qs, (st.hot_node_bucket, st.hot_edge_bucket) = (
                self._approximate_step(snap.state, layouts, r_now,
                                       delta_now))
        elif action == Action.EXACT:
            with spans.span(spans.LAYOUT):
                layouts = self._snapshot_layouts(snap)
            with spans.span(spans.STEP):
                self._run_exact_on(snap, st, layouts)

        # (3) integrate buffered updates and dispatch epoch N+1; its sorts
        # and probe overlap with the compute already in the device queue
        if integrate and self._pending_count:
            with spans.span(spans.APPLY):
                _, extra_req, extra_res = self._async_integrate()
            if pipe.building is None and extra_req:
                # nothing mutated (all removals unresolved): no new epoch,
                # so the request is only observable on this row
                st.removals_requested += extra_req - extra_res
        st.snapshot_lag = pipe.snapshot_lag

        # (4) fetch — waits on the query compute dispatched in step (2)
        if action == Action.REPEAT_LAST:
            self._stale_updates += applied
        elif action == Action.EXACT:
            self.deg_prev = snap.deg
            self.active_prev = snap.active
            if ctl is not None:
                ctl.refreshed()
                st.refreshed = True
        elif qs is not None:
            with spans.span(spans.SYNC_STATS):
                qs = jax.device_get(qs)  # one host transfer for all stats
            if bool(qs.used_fallback):
                # capacities exceeded: the summarized state is invalid;
                # recompute exactly on the *served* snapshot
                self._run_exact_on(snap, st, layouts)
                qs = qs._replace(iterations=st.iterations)
                if ctl is not None:
                    ctl.refreshed()
                    st.refreshed = True
            else:
                self.algo_state = new_state
            st.num_hot = int(qs.num_hot)
            st.num_kr = int(qs.num_kr)
            st.num_kn = int(qs.num_kn)
            st.num_kdelta = int(qs.num_kdelta)
            st.num_ek = int(qs.num_ek)
            st.num_eb = int(qs.num_eb)
            st.iterations = int(qs.iterations)
            st.overflow_fallback = bool(qs.used_fallback)
            if ctl is not None and not st.overflow_fallback:
                dec = ctl.observe(float(qs.drift_probe),
                                  float(qs.drift_cold))
                st.drift = max(float(qs.drift_probe), float(qs.drift_cold))
                st.r_eff = float(r_now)
                st.delta_eff = float(delta_now)
                st.quality_est = dec.quality_est
                if dec.refresh:
                    self._run_exact_on(snap, st, layouts)
                    ctl.refreshed()
                    st.refreshed = True
                    st.quality_est = 1.0
            elif ctl is not None:
                st.r_eff = float(r_now)
                st.delta_eff = float(delta_now)
                st.quality_est = 1.0
            # the epoch's own baselines become the next query's deg_prev/
            # active_prev, so drift is always measured across whole epochs
            self.deg_prev = snap.deg
            self.active_prev = snap.active

        if action != Action.REPEAT_LAST:
            self._stale_updates = 0
        return st

    # ---- query serving ---------------------------------------------------
    def query(self, msg: Optional[Dict] = None) -> Tuple[np.ndarray, QueryStats]:
        """Serve one query (Alg. 1 lines 6-21). Returns (scores, stats).

        ``stats.wall_time_s`` is the call's time up to the scores on the
        host — update integration, the step and every host sync included;
        only the OnQueryResult UDF runs after it.  It is the interval of
        the ``veilgraph.query`` profiler span (:mod:`repro.core.spans`).
        """
        assert self._started, "call start() first"
        t0 = time.perf_counter()
        with spans.span(spans.QUERY):
            if self._pipeline is not None:
                st = self._query_async()
            else:
                st = self._query_sync()
            with spans.span(spans.SYNC_FETCH):
                scores = np.asarray(jax.device_get(self.ranks))
            st.wall_time_s = time.perf_counter() - t0
        self.stats_log.append(st)
        if self._on_query_result:
            self._on_query_result(st.query_id, msg, Action(st.action),
                                  self.ranks, st)
        return scores, st

    def _query_sync(self) -> QueryStats:
        """Serve one query on the synchronous path (the scores are fetched
        by :meth:`query`)."""
        qid = self._query_id
        self._query_id += 1
        cfg = self.config

        applied = removals_requested = removals_resolved = 0
        rebalanced = False
        view = self._stats_view(self._pending_count, 0)
        if self._before_updates(self._pending_count, view):
            with spans.span(spans.APPLY):
                applied, removals_requested, removals_resolved = (
                    self._apply_pending())
            if applied:
                rebalanced = self._maybe_rebalance()
            # the OnQuery policy must see the post-update graph: refresh the
            # node/edge counts snapshotted before _apply_pending
            view = self._stats_view(self._pending_count, applied)

        action = self._on_query(qid, view)
        st = QueryStats(
            query_id=qid,
            action=action.value,
            wall_time_s=0.0,
            num_nodes=view["num_nodes"],
            num_edges=view["num_edges"],
            pending_applied=applied,
            removals_requested=removals_requested,
            removals_resolved=removals_resolved,
            rebalanced=rebalanced,
            algorithm=self.algorithm.name,
        )

        if action == Action.REPEAT_LAST:
            self._stale_updates += applied  # previous scores returned as-is
        elif action == Action.EXACT:
            with spans.span(spans.LAYOUT):
                layouts = self.edge_layouts()
            with spans.span(spans.STEP):
                self._run_exact(st, layouts)
            with spans.span(spans.SYNC_READY):
                self.ranks.block_until_ready()
            self.deg_prev = self._degree_snapshot()
            self.active_prev = jnp.copy(self.state.node_active)
            if self.controller is not None:
                # an exact recompute is a refresh: accumulated drift resets
                self.controller.refreshed()
                st.refreshed = True
        elif cfg.fused and self.algorithm.supports_fused:
            # APPROXIMATE through the fused step, for any algorithm
            ctl = self.controller
            r_now = ctl.r_eff if ctl is not None else cfg.r
            delta_now = ctl.delta_eff if ctl is not None else cfg.delta
            with spans.span(spans.LAYOUT):
                layouts = self.edge_layouts()
            new_state, qs, (st.hot_node_bucket, st.hot_edge_bucket) = (
                self._approximate_step(self.state, layouts, r_now,
                                       delta_now))
            with spans.span(spans.SYNC_FALLBACK):
                fallback = bool(qs.used_fallback)
            if fallback:
                # capacities exceeded: the summarized state is invalid;
                # discard it and recompute exactly (graceful degradation)
                self._run_exact(st, layouts)
                qs = qs._replace(iterations=st.iterations)
                if ctl is not None:
                    ctl.refreshed()  # exact fallback = accurate baseline
                    st.refreshed = True
            else:
                self.algo_state = new_state
            with spans.span(spans.SYNC_READY):
                self.ranks.block_until_ready()
            with spans.span(spans.SYNC_STATS):
                qs = jax.device_get(qs)  # one host transfer for all stats
            st.num_hot = int(qs.num_hot)
            st.num_kr = int(qs.num_kr)
            st.num_kn = int(qs.num_kn)
            st.num_kdelta = int(qs.num_kdelta)
            st.num_ek = int(qs.num_ek)
            st.num_eb = int(qs.num_eb)
            st.iterations = int(qs.iterations)
            st.overflow_fallback = bool(qs.used_fallback)
            if ctl is not None and not st.overflow_fallback:
                # fold the drift reading (rode the stats transfer above)
                # into the loop: knobs for the *next* query, and possibly
                # an exact refresh to pull the state back inside the SLO
                dec = ctl.observe(float(qs.drift_probe),
                                  float(qs.drift_cold))
                st.drift = max(float(qs.drift_probe), float(qs.drift_cold))
                st.r_eff = float(r_now)
                st.delta_eff = float(delta_now)
                st.quality_est = dec.quality_est
                if dec.refresh:
                    self._run_exact(st, layouts)
                    with spans.span(spans.SYNC_READY):
                        self.ranks.block_until_ready()
                    ctl.refreshed()
                    st.refreshed = True
                    st.quality_est = 1.0
            elif ctl is not None:
                st.r_eff = float(r_now)
                st.delta_eff = float(delta_now)
                st.quality_est = 1.0
            self.deg_prev = self._degree_snapshot()
            self.active_prev = jnp.copy(self.state.node_active)
        else:  # APPROXIMATE — unfused reference path
            with spans.span(spans.LAYOUT):
                layouts = self.edge_layouts()
            with spans.span(spans.STEP):
                hot, hstats = select_hot_set(
                    self.state,
                    self.deg_prev,
                    self.algorithm.selection_view(self.algo_state),
                    jnp.float32(cfg.r),
                    jnp.float32(cfg.delta),
                    active_prev=self.active_prev,
                    n=cfg.n,
                    delta_hop_cap=cfg.delta_hop_cap,
                    degree_mode=cfg.degree_mode,
                    expand_both=cfg.expand_both,
                    normalize_scores=self.algorithm.normalize_selection_scores,
                )
                # forwarded only when set: legacy plugin build_summaries
                # overrides may predate the shard_bucket_capacity keyword
                extra = ({} if cfg.shard_hot_edge_capacity is None else
                         {"shard_bucket_capacity":
                          cfg.shard_hot_edge_capacity})
                summaries = self.algorithm.build_summaries(
                    self.algo_state,
                    self.state,
                    hot,
                    hot_node_capacity=cfg.hot_node_capacity,
                    hot_edge_capacity=cfg.hot_edge_capacity,
                    layouts=layouts,
                    backend=self.backend,
                    **extra,
                )
            with spans.span(spans.SYNC_STATS):
                st.num_hot = int(hstats.num_hot)
                st.num_kr = int(hstats.num_kr)
                st.num_kn = int(hstats.num_kn)
                st.num_kdelta = int(hstats.num_kdelta)
                st.num_ek = int(summaries[0].num_ek)
                st.num_eb = int(sum(int(s.num_eb) for s in summaries))
            with spans.span(spans.SYNC_FALLBACK):
                fallback = bool(summaries_overflow(summaries))
            if fallback:
                # graceful degradation: capacities exceeded -> exact recompute
                st.overflow_fallback = True
                self._run_exact(st, layouts)
            else:
                with spans.span(spans.STEP):
                    self.algo_state, iters = self.algorithm.summarized(
                        self.algo_state, self.state, summaries,
                        backend=self.backend,
                    )
                with spans.span(spans.SYNC_ITERS):
                    st.iterations = int(iters)
            with spans.span(spans.SYNC_READY):
                self.ranks.block_until_ready()
            self.deg_prev = self._degree_snapshot()
            self.active_prev = jnp.copy(self.state.node_active)

        if action != Action.REPEAT_LAST:
            self._stale_updates = 0
        return st
