"""Multi-tenant graph-query serving: slot-based continuous batching.

The engine keeps a *static-slot wave* discipline (a fixed-capacity batch
stepped in lockstep, finished entries swapped for queued ones at wave
boundaries), as continuous batching does, and its unit of work is a
**summarized graph query**:

- A :class:`GraphServingEngine` wraps one started
  :class:`~repro.core.engine.VeilGraphEngine` — one shared graph, one
  shared hot-set/summary per wave, many concurrent queries.
- Requests arrive via :meth:`GraphServingEngine.submit` (e.g. B different
  personalized-PageRank seed sets, B different SSSP sources) and return a
  :class:`QueryTicket` handle immediately.
- Queries of one algorithm *family* share a **lane**: a bank of ``slots``
  static state rows (``[S, ...]`` leaves, the
  :meth:`~repro.core.algorithm.StreamingAlgorithm.init_state` pytree with
  a leading slot axis).  Per-query identity (teleport vectors, source
  masks) lives in the rows, never in the jit-static algorithm instance —
  see ``StreamingAlgorithm.per_query_params`` — so a lane compiles ONE
  batched XLA program (:func:`repro.core.fused.fused_query_step_batched`)
  and reuses it for every wave and every request mix.
- Each :meth:`step` (wave) applies pending graph updates, refills vacant
  slots from the queue, runs one batched fused step per non-empty lane
  with a ``row_mask`` that freezes finished/vacant rows (they stop
  contributing work), then harvests rows whose per-slot convergence
  signal dropped below the request's tolerance (or whose wave budget is
  exhausted) and frees their slots.
- Summary overflow keeps the engine's graceful-degradation contract: the
  batch result of the overflowing wave is discarded and every live row is
  recomputed exactly, row by row, completing those requests.

Observability is a :class:`ServeStats` snapshot: queries served per
second, wave count, mean slot occupancy, and p50/p95 wave latency.

Construct via :func:`repro.api.serve_session`, or wrap an existing
engine directly::

    srv = GraphServingEngine(session.engine, slots=4)
    t1 = srv.submit("personalized-pagerank", seeds=(3,))
    t2 = srv.submit("sssp", sources=(17,))
    srv.run()
    t1.result, t2.result
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import backend as B
from repro.core import spans
from repro.core.algorithm import (AlgoState, StreamingAlgorithm,
                                  make_algorithm)
from repro.core.engine import VeilGraphEngine
from repro.core.fused import fused_query_step_batched


@dataclass
class QueryTicket:
    """Handle for one submitted query — returned by ``submit`` immediately.

    ``tol`` is the completion threshold on the per-slot convergence
    signal (L1 change of the last inner iteration for the ranking family,
    changed-entry count for the min-semiring relaxations); ``max_waves``
    bounds how many waves the query may occupy a slot.  The defaults
    (``tol=0.0, max_waves=1``) complete every query after one summarized
    sweep — the batched equivalent of one ``engine.query()`` — while
    ``max_waves > 1`` opts into multi-wave refinement until the signal
    reaches ``tol``.

    ``result`` is the algorithm's ``result_view`` row (own dtype:
    f32 ranks/distances, int32 labels) once ``done``; ``converged``
    records whether the tolerance was actually met (False = wave budget
    exhausted or exact fallback served it).
    """

    ticket_id: int
    algorithm: str
    params: Dict
    tol: float = 0.0
    max_waves: int = 1
    # filled in by the engine
    done: bool = False
    converged: bool = False
    exact_fallback: bool = False
    waves_run: int = 0
    last_delta: float = float("inf")
    result: Optional[np.ndarray] = None
    _instance: Optional[StreamingAlgorithm] = None


@dataclass
class ServeStats:
    """Aggregate serving metrics, updated once per wave.

    ``occupancy_sum`` accumulates the per-wave fraction of occupied
    slots (across all lanes), so :attr:`mean_occupancy` is the average
    slot utilization over the engine's lifetime; wave latencies feed the
    p50/p95 percentiles.
    """

    queries_submitted: int = 0
    queries_completed: int = 0
    waves: int = 0
    wall_s: float = 0.0
    overflow_fallbacks: int = 0
    occupancy_sum: float = 0.0
    wave_latencies_s: List[float] = field(default_factory=list)
    # closed-loop quality columns (quality_target engines only): refresh
    # count across lanes, the last wave's worst-slot drift reading, and
    # the controller's current/worst-case quality estimate
    refreshes: int = 0
    last_drift: float = 0.0
    quality_est: float = 1.0
    min_quality_est: float = 1.0
    # async-pipeline staleness columns (async_rebuild engines; sync
    # engines keep the zeros): the epoch the last wave served from, and
    # how far it trailed the newest dispatched build (0 or 1)
    epoch: int = 0
    snapshot_lag: int = 0

    @property
    def queries_per_s(self) -> float:
        """Completed queries per second of wave wall time.  Guarded: a
        run with zero waves (or waves too fast for the clock to resolve)
        reports 0.0 rather than dividing by zero."""
        return self.queries_completed / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def mean_occupancy(self) -> float:
        """Mean fraction of slots occupied per wave, in [0, 1].  0.0
        before the first wave (never a division by zero)."""
        return self.occupancy_sum / self.waves if self.waves else 0.0

    def _latency_quantile(self, q: float) -> float:
        """Nearest-rank quantile of the wave latencies.

        Guarded for the empty/single-sample runs that used to misbehave:
        no samples -> 0.0, one sample -> that sample for every q.  The
        previous ``int(q * len)`` rank was also off by one — p95 of 20
        samples indexed element 19 (the maximum, i.e. p100); nearest
        rank is ``ceil(q * len)`` 1-indexed, so p95 of 20 reads the 19th
        order statistic."""
        lat = sorted(self.wave_latencies_s)
        if not lat:
            return 0.0
        q = min(max(q, 0.0), 1.0)
        idx = min(max(int(np.ceil(q * len(lat))) - 1, 0), len(lat) - 1)
        return lat[idx]

    @property
    def p50_wave_latency_s(self) -> float:
        """Median wall-clock latency of one wave, in seconds."""
        return self._latency_quantile(0.50)

    @property
    def p95_wave_latency_s(self) -> float:
        """95th-percentile wall-clock latency of one wave, in seconds."""
        return self._latency_quantile(0.95)


@dataclass
class _Lane:
    """One algorithm family's slot bank (internal).

    ``template`` is the jit-static algorithm instance shared by every
    request in the lane (requests differing only in
    ``per_query_params`` batch together); ``bank`` is the ``[S, ...]``
    state pytree; ``tickets[i]`` is the request occupying slot i (None =
    vacant); ``waves[i]`` counts waves the current occupant has run.
    """

    template: StreamingAlgorithm
    bank: AlgoState
    tickets: List[Optional[QueryTicket]]
    waves: List[int]
    # cold[i]: slot i's occupant has never yet converged — its waves need
    # full hot-set coverage (the batched analogue of the single-query
    # protocol's initial exact compute); cleared the first time the row's
    # convergence signal reaches its tolerance
    cold: List[bool] = field(default_factory=list)
    queue: List[QueryTicket] = field(default_factory=list)
    # per-lane SLO controller (quality_target engines only): each lane
    # runs its own accuracy loop, since lanes disagree on residual scale
    controller: Optional["QualityController"] = None

    @property
    def row_mask(self) -> jax.Array:
        return jnp.asarray([t is not None for t in self.tickets], bool)

    @property
    def occupied(self) -> int:
        return sum(t is not None for t in self.tickets)


def _lane_key(algo: StreamingAlgorithm) -> Tuple:
    """Requests batch into one lane when they differ only in the knobs
    :attr:`~repro.core.algorithm.StreamingAlgorithm.per_query_params`
    declares state-carried (seed sets, source sets) — everything else
    (iteration budgets, damping factors, the algorithm itself) is part of
    the jit-static template and therefore of the key."""
    import dataclasses

    skip = set(algo.per_query_params)
    knobs = tuple(
        (f.name, getattr(algo, f.name))
        for f in dataclasses.fields(algo) if f.name not in skip)
    return (type(algo).__name__, algo.name) + knobs


class GraphServingEngine:
    """Continuous-batching front door over one VeilGraph engine.

    ``slots`` is the static batch width *per lane* (one lane per
    algorithm family — mixed workloads, e.g. personalized PageRank plus
    SSSP, get one lane each over the same shared graph).  Slot banks and
    the batched fused step compile once per (lane, capacities) pair;
    submitting, refilling and harvesting never recompile.

    Graph updates stream through :meth:`add_edges` /
    :meth:`remove_edges` (buffered in the wrapped engine) and are
    applied at the next wave boundary, so every query in a wave sees one
    consistent graph snapshot.
    """

    def __init__(self, engine: VeilGraphEngine, *, slots: int = 4):
        if slots < 1:
            raise ValueError(f"slots must be >= 1; got {slots}")
        if not getattr(engine, "_started", False):
            raise ValueError(
                "GraphServingEngine wraps a *started* engine — call "
                "engine.start(...) (or build via repro.api.serve_session)")
        self.engine = engine
        self.slots = slots
        # batched sweeps run [slots, N] pushes — tune for that batch width
        engine.autotune_batch_hint = slots
        self.stats = ServeStats()
        self._lanes: Dict[Tuple, _Lane] = {}
        # shared edge-layout cache across lanes, keyed by normalized
        # (weight, reverse, semiring) spec; invalidated when the graph
        # mutates at a wave boundary
        self._layouts: Dict[Tuple, B.AnyEdgeLayout] = {}
        self._next_ticket = 0

    # ---- submission ------------------------------------------------------
    def submit(
        self,
        algorithm: Union[StreamingAlgorithm, str],
        *,
        tol: float = 0.0,
        max_waves: int = 1,
        **params,
    ) -> QueryTicket:
        """Enqueue one query; returns its :class:`QueryTicket` handle.

        ``algorithm`` is a registry name with factory kwargs (e.g.
        ``submit("personalized-pagerank", seeds=(3,))``) or a prebuilt
        instance.  The algorithm must implement ``summarized_batched``
        (all shipped algorithms do); the request joins the lane of its
        family and starts at the next wave boundary with a free slot.
        """
        if max_waves < 1:
            raise ValueError(f"max_waves must be >= 1; got {max_waves}")
        algo = make_algorithm(algorithm, **params)
        if (type(algo).summarized_batched
                is StreamingAlgorithm.summarized_batched):
            raise TypeError(
                f"algorithm {algo.name!r} does not implement "
                "summarized_batched — it cannot be served in a batched "
                "lane (run it through engine.query() instead)")
        ticket = QueryTicket(
            ticket_id=self._next_ticket,
            algorithm=algo.name,
            params=dict(params),
            tol=float(tol),
            max_waves=int(max_waves),
            _instance=algo,
        )
        self._next_ticket += 1
        self.stats.queries_submitted += 1
        self._lane_for(algo).queue.append(ticket)
        return ticket

    @property
    def pending(self) -> int:
        """Queries submitted but not yet done (queued or in a slot)."""
        return sum(
            len(lane.queue) + lane.occupied
            for lane in self._lanes.values())

    # ---- streaming passthrough -------------------------------------------
    def add_edges(self, src, dst, weights=None) -> "GraphServingEngine":
        """Buffer edge additions (optionally with a per-edge length
        column); applied at the next wave boundary."""
        self.engine.register_add_edges(
            np.asarray(src), np.asarray(dst),
            None if weights is None else np.asarray(weights))
        return self

    def remove_edges(self, src, dst) -> "GraphServingEngine":
        """Buffer edge removals; applied at the next wave boundary."""
        self.engine.register_remove_edges(np.asarray(src), np.asarray(dst))
        return self

    # ---- internals -------------------------------------------------------
    def _lane_for(self, algo: StreamingAlgorithm) -> _Lane:
        key = _lane_key(algo)
        lane = self._lanes.get(key)
        if lane is None:
            proto = algo.init_state(self.engine.state)
            bank = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(
                    a[None], (self.slots,) + a.shape).copy(), proto)
            algo.validate_batch_state(bank, self.slots)
            cfg = self.engine.config
            controller = None
            if cfg.quality_target is not None:
                from repro.core.control import QualityController

                controller = QualityController(
                    cfg.quality_target,
                    r0=cfg.r, delta0=cfg.delta,
                    adjust_r=cfg.control_r,
                    adjust_delta=cfg.control_delta,
                    contraction=algo.drift_contraction,
                )
            lane = _Lane(
                template=algo,
                bank=bank,
                tickets=[None] * self.slots,
                waves=[0] * self.slots,
                cold=[False] * self.slots,
                controller=controller,
            )
            self._lanes[key] = lane
        return lane

    def _spec_layouts(self, algo: StreamingAlgorithm, snap=None) -> Tuple:
        """Cached edge layouts for an algorithm's ``layout_specs`` —
        shared across lanes that declare the same spec, rebuilt only
        after graph mutations (mirrors ``VeilGraphEngine.edge_layouts``
        but keyed by spec, since lanes disagree on which specs they
        need).

        With ``snap`` (the async pipeline's served
        :class:`~repro.core.epoch.EpochSnapshot`), layouts come from the
        snapshot's own epoch-bound cache instead, and the spec is
        registered with the engine so every *future* snapshot pre-sorts
        it at build (dispatch) time."""
        eng = self.engine
        out = []
        for spec in map(B.normalize_layout_spec, algo.layout_specs):
            if snap is not None:
                eng._async_specs[spec] = True
                out.append(snap.layout_for(spec, eng._build_spec_layout))
                continue
            layout = self._layouts.get(spec)
            if layout is None:
                layout = eng._build_spec_layout(eng.state, spec)
                self._layouts[spec] = layout
            out.append(layout)
        return tuple(out)

    def _apply_updates(self):
        """Wave-boundary ApplyUpdates: integrate buffered stream updates
        and invalidate every cached layout (the engine's own cache too —
        it shares the graph)."""
        eng = self.engine
        if not eng._pending_count:
            return
        with spans.span(spans.APPLY):
            applied, _, _ = eng._apply_pending()
        if applied:
            eng._maybe_rebalance()
            self._layouts.clear()

    def _refill(self, lane: _Lane, state=None):
        """Seat queued requests in vacant slots (wave boundary only).

        A fresh occupant's state rows come from *its own* algorithm
        instance (its seeds/sources), written into the shared bank with
        static-shaped row scatters — the bank's pytree structure, and
        therefore the lane's compiled wave program, never changes.
        ``state`` pins the graph the rows initialize against (the async
        pipeline passes the served snapshot's state).
        """
        if state is None:
            state = self.engine.state
        for i in range(self.slots):
            if lane.tickets[i] is not None or not lane.queue:
                continue
            ticket = lane.queue.pop(0)
            row = ticket._instance.init_state(state)
            lane.bank = {
                k: lane.bank[k].at[i].set(row[k]) for k in lane.bank}
            lane.tickets[i] = ticket
            lane.waves[i] = 0
            lane.cold[i] = True

    def _harvest(self, lane: _Lane, row_delta: np.ndarray,
                 *, force: bool = False):
        """Complete finished occupants and free their slots.

        A row finishes when its convergence signal reached the request's
        tolerance, its wave budget is exhausted, or ``force`` is set
        (exact fallback already produced final answers)."""
        results = None
        for i, ticket in enumerate(lane.tickets):
            if ticket is None:
                continue
            ticket.waves_run = lane.waves[i]
            ticket.last_delta = float(row_delta[i])
            # a force-harvest (exact fallback) answers exactly but never
            # *observed* the tolerance being met — converged stays False,
            # per the QueryTicket contract
            converged = (not force) and ticket.last_delta <= ticket.tol
            if converged or force:
                lane.cold[i] = False
            if not (converged or lane.waves[i] >= ticket.max_waves or force):
                continue
            if results is None:  # one device transfer per harvesting wave
                with spans.span(spans.SYNC_FETCH):
                    results = np.asarray(jax.device_get(
                        lane.template.result_view(lane.bank)))
            ticket.result = results[i].copy()
            ticket.converged = converged
            ticket.done = True
            lane.tickets[i] = None
            lane.waves[i] = 0
            lane.cold[i] = False
            self.stats.queries_completed += 1

    def _exact_fallback(self, lane: _Lane, state=None, snap=None):
        """Summary overflow: serve every live row with a per-row exact
        recompute (graceful degradation, same contract as
        ``engine.query``), then harvest them all.  ``state``/``snap``
        pin the recompute to the wave's served snapshot in async mode —
        the fallback must answer at the epoch the wave was serving."""
        eng = self.engine
        if state is None:
            state = eng.state
        deltas = np.zeros((self.slots,), np.float32)
        for i, ticket in enumerate(lane.tickets):
            if ticket is None:
                continue
            row = {k: lane.bank[k][i] for k in lane.bank}
            new_row, _ = ticket._instance.exact(
                row, state,
                layouts=self._spec_layouts(ticket._instance, snap),
                backend=eng.backend)
            lane.bank = {
                k: lane.bank[k].at[i].set(new_row[k]) for k in lane.bank}
            ticket.exact_fallback = True
        self.stats.overflow_fallbacks += 1
        if lane.controller is not None:
            # exact answers = accurate baseline; accumulated drift resets
            lane.controller.refreshed()
        self._harvest(lane, deltas, force=True)

    # ---- the wave loop ---------------------------------------------------
    def step(self) -> int:
        """Run one wave: apply updates, refill, one batched fused step
        per non-empty lane, harvest.  Returns the number of queries
        completed this wave.  The wave is one ``veilgraph.wave`` profiler
        span (:mod:`repro.core.spans`).

        Async engines (``EngineConfig.async_rebuild``) reorder the
        boundary work: the wave *promotes* the finished epoch build,
        serves every lane from the promoted snapshot, and only then
        integrates buffered updates — dispatching (never awaiting) the
        next epoch's apply + sorts + rebalance probe, which overlap with
        the harvest transfers and the next wave's host-side boundary
        work."""
        with spans.span(spans.WAVE):
            return self._step()

    def _step(self) -> int:
        eng = self.engine
        cfg = eng.config
        pipe = eng._pipeline
        t0 = time.perf_counter()
        completed_before = self.stats.queries_completed

        snap = None
        if pipe is not None:
            promoted = pipe.promote()
            if promoted is not None:
                eng._finalize_promotion(promoted)
            snap = pipe.current
            state = snap.state
            self.stats.epoch = snap.epoch
        else:
            self._apply_updates()
            state = eng.state
        occupied = 0
        for lane in self._lanes.values():
            self._refill(lane, state)
            occupied += lane.occupied

        for lane in self._lanes.values():
            if lane.occupied == 0:
                continue
            row_mask = lane.row_mask
            ctl = lane.controller
            r_now = ctl.r_eff if ctl is not None else cfg.r
            delta_now = ctl.delta_eff if ctl is not None else cfg.delta
            # cold-start coverage: rows whose occupant has never converged
            # get seed-local delta expansion inside the fused step (see
            # fused_query_step_batched's cold_rows contract) — no cold
            # rows costs zero extra sweeps
            cold_rows = jnp.asarray(
                [c and t is not None
                 for c, t in zip(lane.cold, lane.tickets)], bool)
            with spans.span(spans.LAYOUT):
                layouts = self._spec_layouts(lane.template, snap)
            with spans.span(spans.STEP):
                out = fused_query_step_batched(
                    state,
                    lane.bank,
                    eng.deg_prev,
                    eng.active_prev,
                    jnp.float32(r_now),
                    jnp.float32(delta_now),
                    row_mask,
                    cold_rows,
                    eng._probe_ids,
                    algo=lane.template,
                    hot_node_capacity=cfg.hot_node_capacity,
                    hot_edge_capacity=cfg.hot_edge_capacity,
                    n=cfg.n,
                    delta_hop_cap=cfg.delta_hop_cap,
                    degree_mode=cfg.degree_mode,
                    expand_both=cfg.expand_both,
                    layouts=layouts,
                    backend=eng.backend,
                    shard_bucket_capacity=cfg.shard_hot_edge_capacity,
                    with_drift=ctl is not None,
                )
            if ctl is not None:
                new_bank, qs, row_delta, row_drift = out
            else:
                new_bank, qs, row_delta = out
                row_drift = None
            with spans.span(spans.SYNC_FALLBACK):
                fallback = bool(qs.used_fallback)
            if fallback:
                # batch result is invalid — discard, serve rows exactly
                # (pinned to this wave's snapshot in async mode)
                self._exact_fallback(lane, state, snap)
                continue
            lane.bank = new_bank
            for i in range(self.slots):
                if lane.tickets[i] is not None:
                    lane.waves[i] += 1
            if ctl is not None:
                # one combined transfer: per-slot deltas + drift columns
                with spans.span(spans.SYNC_STATS):
                    rd, drift = jax.device_get((row_delta, row_drift))
                drift = np.asarray(drift)
                probe = float(drift[:, 0].max(initial=0.0))
                cold_d = float(drift[:, 1].max(initial=0.0))
                dec = ctl.observe(probe, cold_d)
                self.stats.last_drift = max(probe, cold_d)
                self.stats.quality_est = dec.quality_est
                self.stats.min_quality_est = min(
                    self.stats.min_quality_est, dec.quality_est)
                if dec.refresh:
                    # SLO breach: re-mark every live slot cold so the next
                    # wave re-covers them (the batched analogue of the
                    # single-query engine's exact refresh), and reset the
                    # accumulated drift
                    for i, t in enumerate(lane.tickets):
                        if t is not None:
                            lane.cold[i] = True
                    self.stats.refreshes += 1
                    ctl.refreshed()
                self._harvest(lane, np.asarray(rd))
            else:
                with spans.span(spans.SYNC_STATS):
                    rd = np.asarray(jax.device_get(row_delta))
                self._harvest(lane, rd)

        if pipe is not None:
            # every lane's result for this wave is already fetched: apply
            # buffered updates and dispatch epoch N+1's build — it drains
            # behind this wave's compute while the host runs the epilogue
            # and the next wave's boundary work
            if eng._pending_count:
                with spans.span(spans.APPLY):
                    eng._async_integrate()
            self.stats.snapshot_lag = pipe.snapshot_lag
            # the served epoch's own baselines become the next wave's
            # deg_prev/active_prev (drift measured across whole epochs)
            eng.deg_prev = snap.deg
            eng.active_prev = snap.active
        else:
            # hot-set snapshots advance exactly like engine.query()'s
            # epilogue
            eng.deg_prev = eng._degree_snapshot()
            eng.active_prev = jnp.copy(eng.state.node_active)

        wave_s = time.perf_counter() - t0
        self.stats.waves += 1
        self.stats.wall_s += wave_s
        self.stats.wave_latencies_s.append(wave_s)
        total_slots = max(len(self._lanes) * self.slots, 1)
        self.stats.occupancy_sum += occupied / total_slots
        return self.stats.queries_completed - completed_before

    def run(self, max_steps: int = 10_000) -> ServeStats:
        """Drive waves until every submitted query is done (or
        ``max_steps`` waves elapse — a safety valve against a request
        whose tolerance is unreachable within its wave budget, which
        cannot happen with the shipped completion rule).  Returns the
        accumulated :class:`ServeStats`."""
        steps = 0
        while self.pending:
            if steps >= max_steps:
                raise RuntimeError(
                    f"serving did not drain after {max_steps} waves "
                    f"({self.pending} queries still pending)")
            self.step()
            steps += 1
        return self.stats

    # ---- lifecycle -------------------------------------------------------
    def close(self):
        """Fire the wrapped engine's OnStop UDF (``with``-exit calls it)."""
        self.engine.stop()

    def __enter__(self) -> "GraphServingEngine":
        return self

    def __exit__(self, *exc):
        self.close()
        return False
