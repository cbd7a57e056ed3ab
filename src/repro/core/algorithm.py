"""The pluggable algorithm layer: ``StreamingAlgorithm`` + registry.

The paper presents VeilGraph as a *general* model for approximate graph
processing — the five-UDF structure (Alg. 1) and the hot-vertex/big-vertex
summarization (§3) are algorithm-agnostic, with PageRank only the case
study.  This module makes that separation concrete: the engine owns stream
ingestion, update buffering, hot-set selection and the action policy, while
everything rank-computation-specific lives behind :class:`StreamingAlgorithm`:

    init_state(graph)            -> state pytree (dict of arrays, any dtypes
                                    — declared in ``state_dtypes``)
    exact(state, graph)          -> (state', iterations)        # ground truth
    build_summaries(state, graph, hot, caps) -> (SummaryBuffers, ...)
    summarized(state, graph, summaries)      -> (state', iterations)
    summarized_batched(batch_state, graph, summaries, row_mask)
                                 -> (batch', iters, row_delta)  # serving
    result_view(state)           -> dtype[N_cap]  # the query answer
    selection_view(state)        -> f32[N_cap]    # drives the hot-set Δ
                                    policy (defaults to result_view as f32)
    semiring                     -> the (⊕, ⊗) algebra the sweeps run over
    layout_specs                 -> ((weight, reverse, semiring), ...) —
                                    cached edge layouts the sweeps consume

Every sweep runs through the unified propagation primitive in
:mod:`repro.core.backend`, parameterized by an explicit
:class:`~repro.core.semiring.Semiring` — ``plus_times`` sum-of-products for
the ranking family, ``min_plus`` for SSSP relaxations, ``min_min`` label
propagation over int32 state for connected components.  ``layout_specs``
declares which full-graph :class:`~repro.core.backend.EdgeLayout`
orientations/algebras an algorithm needs so the engine can build them once
per applied update batch and pass them into ``exact`` / ``build_summaries``
(the ``layouts`` tuple, same order).  The ``backend`` keyword selects the
implementation (``"pallas"`` MXU/VPU kernels vs ``"segment_sum"`` XLA
fallback); ``None`` resolves per device/env.

The old single ``score_view`` is split in two: :meth:`result_view` is the
query answer in the algorithm's own dtype (ranks, distances, int labels)
while :meth:`selection_view` is the *float* volatility signal the paper's
Δ-dilution bound consumes (Eqs. 4-5) — ranking algorithms use their scores
for both, whereas CC/SSSP expose label-churn / distance-delta indicators.
``score_view`` remains as a deprecated alias of ``result_view``.

Algorithms are **frozen dataclasses** so instances are hashable and can ride
through ``jax.jit`` as static arguments — the generic fused query step in
:mod:`repro.core.fused` traces ``build_summaries`` + ``summarized`` inline
into one XLA program per (algorithm, capacities) pair.

Seven algorithms ship in the registry:

- ``pagerank``  — the paper's case study (Gelly-style normalization);
- ``personalized-pagerank`` — seeded teleport vector, same summarized path;
- ``hits``      — hubs & authorities via a forward + reverse summary pair;
- ``katz``      — attenuated-walk centrality (unit weights, β attraction);
- ``connected-components`` — label-min propagation on ``min_min``/int32;
- ``sssp``      — single-source shortest paths on ``min_plus``;
- ``widest-path`` — most-reliable paths on ``max_times`` (the max-reduce
  kernel path).

Register your own with :func:`register_algorithm` and run it through
``veilgraph``'s session front door (:func:`repro.api.session`).
"""

from __future__ import annotations

import abc
import enum
from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import backend as B
from repro.core.hits import hits as _hits
from repro.core.hits import summarized_hits as _summarized_hits
from repro.core.hits import summarized_hits_batched as _summarized_hits_batched
from repro.core.katz import katz as _katz
from repro.core.katz import summarized_katz as _summarized_katz
from repro.core.katz import summarized_katz_batched as _summarized_katz_batched
from repro.core.pagerank import SummaryBuffers
from repro.core.pagerank import build_summary as _build_summary
from repro.core.pagerank import pagerank as _pagerank
from repro.core.pagerank import summarized_pagerank as _summarized_pagerank
from repro.core.pagerank import \
    summarized_pagerank_batched as _summarized_pagerank_batched
from repro.core.traversal import LABEL_SENTINEL
from repro.core.traversal import connected_components as _cc
from repro.core.traversal import sssp as _sssp
from repro.core.traversal import \
    summarized_connected_components as _summarized_cc
from repro.core.traversal import \
    summarized_connected_components_batched as _summarized_cc_batched
from repro.core.traversal import summarized_sssp as _summarized_sssp
from repro.core.traversal import \
    summarized_sssp_batched as _summarized_sssp_batched
from repro.core.traversal import summarized_widest_path as \
    _summarized_widest_path
from repro.core.traversal import summarized_widest_path_batched as \
    _summarized_widest_path_batched
from repro.core.traversal import widest_path as _widest_path
from repro.graph.graph import GraphState

#: Algorithm state is a flat dict of device arrays — a JAX pytree, so the
#: whole engine step stays jit-compatible and donation-friendly.
AlgoState = Dict[str, jax.Array]


class Action(enum.Enum):
    """The paper's three OnQuery action indicators (Alg. 1 lines 9-19)."""

    REPEAT_LAST = "repeat-last-answer"
    APPROXIMATE = "compute-approximate"
    EXACT = "compute-exact"


class StreamingAlgorithm(abc.ABC):
    """Interface every engine-pluggable algorithm implements.

    Subclasses must be immutable/hashable (use ``@dataclass(frozen=True)``)
    — instances are jit static arguments.  Numeric knobs (β, iteration
    budget, seeds) are dataclass fields; per-vertex state (score vectors,
    personalization vectors) lives in the state dict returned by
    :meth:`init_state`.
    """

    #: registry key; subclasses override.
    name: str = "abstract"
    #: False opts an algorithm out of the single-XLA-program fused query
    #: path (the engine then runs select/summarize/iterate as separate jits).
    supports_fused: bool = True
    #: True rescales selection_view to mean 1 over active vertices inside
    #: the hot-set Δ-dilution bound (Eqs. 4-5 are calibrated against
    #: PageRank-scale scores; L1-normalized algorithms opt in).
    normalize_selection_scores: bool = False
    #: the (⊕, ⊗) algebra the sweeps run over (registry name in
    #: :mod:`repro.core.semiring`); the default :meth:`build_summaries`
    #: bakes ``ek_w``/``b_in`` for it.
    semiring: str = "plus_times"
    #: True: bigger result values rank first (scores).  False: smaller
    #: values rank first (distances, min-labels) — ``QueryResult.top``
    #: orders accordingly.
    rank_descending: bool = True
    #: weight mode of the default single-summary :meth:`build_summaries`
    #: (``"inv_out"``, ``"unit"`` or ``"length"``).
    summary_weight: str = "inv_out"
    #: declared per-key dtypes of the :meth:`init_state` pytree — the
    #: engine validates them once at state initialization so non-float
    #: state (e.g. CC's int32 labels) can't silently decay to float.
    #: Empty (the default) declares nothing: legacy plugins with arbitrary
    #: state keys construct unchecked.
    state_dtypes: Dict[str, str] = {}
    #: normalization mode for the drift estimator's signals
    #: (:func:`repro.core.control.drift_signals`): ``"mass"`` divides the
    #: residual by total |result| mass (scores, distances); ``"count"``
    #: by the active-vertex count — for 0/1 changed-indicator residuals
    #: (connected components' label flips), where result magnitudes are
    #: ids and carry no error meaning.
    drift_normalize: str = "mass"
    #: declared contraction factor of the algorithm's update operator,
    #: consumed by the :class:`~repro.core.control.QualityController` to
    #: calibrate its drift→error gain: an observed residual amplifies to
    #: at most ``residual / (1 - contraction)`` steady-state error, so the
    #: effective gain is ``1 / (1 - contraction)``.  ``None`` (the
    #: default) keeps the conservative legacy ``gain=3`` bound — right
    #: for damped ranking algebras whose contraction (β ≈ 0.85) is weak.
    #: Min-semiring relaxations (CC, SSSP, widest path) converge to their
    #: fixed point in finitely many sweeps with no geometric tail — they
    #: declare ``0.0`` (gain 1) so a quiet stream stops over-refreshing.
    drift_contraction: Optional[float] = None
    #: constructor knobs whose whole effect is captured by
    #: :meth:`init_state` (seed sets, source sets) — the per-query
    #: *identity* as opposed to numeric sweep knobs.  The serving engine
    #: batches requests that differ only in these into one slot lane (the
    #: identity rides in the ``[B, ...]`` batch state; the batched sweep
    #: never reads it from ``self``).
    per_query_params: Tuple[str, ...] = ()
    #: full-graph edge layouts the sweeps consume, as
    #: (weight, reverse, semiring) triples — the engine builds and caches
    #: one EdgeLayout per entry (once per applied update batch) and passes
    #: them as the ``layouts`` tuple.  Two-element (weight, reverse)
    #: entries from the pre-semiring API mean ``plus_times``.
    layout_specs: Tuple[Tuple, ...] = (("inv_out", False, "plus_times"),)

    @abc.abstractmethod
    def init_state(self, graph: GraphState) -> AlgoState:
        """Fresh per-vertex state sized to ``graph.node_capacity``."""

    @abc.abstractmethod
    def exact(
        self, state: AlgoState, graph: GraphState, *,
        layouts=None, backend: Optional[str] = None,
    ) -> Tuple[AlgoState, jax.Array]:
        """Full recomputation over the live graph (the exact reference).

        Implementations may warm-start from ``state`` — every algorithm
        here converges to a unique fixed point, so warm starts only save
        iterations.  ``layouts`` is the cached tuple matching
        :attr:`layout_specs` (or None to let the sweep build/fall back).
        """

    def build_summaries(
        self,
        state: AlgoState,
        graph: GraphState,
        hot_mask: jax.Array,
        *,
        hot_node_capacity: int,
        hot_edge_capacity: int,
        layouts=None,
        backend: Optional[str] = None,
        shard_bucket_capacity: Optional[int] = None,
        ek_mask: Optional[jax.Array] = None,
    ) -> Tuple[SummaryBuffers, ...]:
        """Compacted summary graph(s) the summarized step consumes.

        The default is the paper's single forward big-vertex summary over
        the algorithm's declared :attr:`semiring` and
        :attr:`summary_weight`, frozen from :meth:`result_view`.
        Algorithms needing different frozen vectors or both orientations
        (HITS, connected components) override.  ``layouts`` matches
        :attr:`layout_specs` and accelerates the frozen big-vertex pass.
        ``shard_bucket_capacity`` tightens the mesh-sharded construction's
        per-(shard, bucket) slot count (see
        :func:`repro.core.pagerank.build_summary`).  ``ek_mask`` is the
        E_K edge mask of ``hot_mask`` when the caller has it
        (:func:`repro.core.fused.hot_set_step` does); it serves both
        orientations.  The engine forwards each of the two only when set,
        so legacy overrides without the keywords keep working.

        Handed a *batched* ``[B, ...]`` state (serving lanes), the frozen
        vector :meth:`result_view` returns is ``[B, N]`` and the summary
        comes back with a per-query ``b_in [B, K_cap]`` over one shared
        E_K structure — hot ids, compacted edges and weights depend only
        on the graph and hot mask, never on per-query scores.
        """
        return (
            _build_summary(
                graph,
                self.result_view(state),
                hot_mask,
                hot_node_capacity=hot_node_capacity,
                hot_edge_capacity=hot_edge_capacity,
                weight=self.summary_weight,
                semiring=self.semiring,
                layout=layouts[0] if layouts else None,
                backend=backend,
                shard_bucket_capacity=shard_bucket_capacity,
                ek_mask=ek_mask,
            ),
        )

    @abc.abstractmethod
    def summarized(
        self,
        state: AlgoState,
        graph: GraphState,
        summaries: Tuple[SummaryBuffers, ...],
        *,
        backend: Optional[str] = None,
    ) -> Tuple[AlgoState, jax.Array]:
        """Approximate update restricted to the hot set (§3.1)."""

    def summarized_batched(
        self,
        batch_state: AlgoState,
        graph: GraphState,
        summaries: Tuple[SummaryBuffers, ...],
        *,
        row_mask: Optional[jax.Array] = None,
        backend: Optional[str] = None,
    ) -> Tuple[AlgoState, jax.Array, jax.Array]:
        """Batched summarized sweep for B concurrent queries (serving).

        ``batch_state`` is the :meth:`init_state` pytree with a leading
        batch axis on every leaf (``[B, ...]``, see
        :meth:`validate_batch_state`); ``summaries`` shares one E_K
        structure across all rows, with ``b_in`` either ``[K_cap]``
        (identical frozen boundary) or ``[B, K_cap]`` (per-query
        boundary from a batched :meth:`build_summaries`).  ``row_mask``
        (bool[B], True = live) freezes converged/vacant serving slots:
        masked rows carry through unchanged and report zero delta.

        Returns ``(batch_state', iterations, row_delta f32[B])`` where
        ``row_delta`` is the per-row convergence signal of the *last*
        inner iteration (L1 change for the ranking family, changed-entry
        count for the min-semiring relaxations).  The shipped algorithms
        all implement this; plugins that don't are rejected by the
        serving engine at submit time.
        """
        raise NotImplementedError(
            f"{type(self).__name__} does not implement summarized_batched; "
            "multi-tenant serving needs the batched [B, N] sweep")

    def validate_batch_state(self, batch_state: AlgoState,
                             batch: int) -> None:
        """Validate a serving slot bank against :attr:`state_dtypes`.

        Every declared key must be present with its declared dtype and a
        leading axis of exactly ``batch`` rows.  Algorithms with an empty
        ``state_dtypes`` declaration (legacy plugins) validate nothing.
        """
        if not self.state_dtypes:
            return
        missing = sorted(set(self.state_dtypes) - set(batch_state))
        if missing:
            raise ValueError(
                f"{self.name}: batch state is missing declared keys "
                f"{missing}")
        for key, want in self.state_dtypes.items():
            arr = batch_state[key]
            if jnp.dtype(arr.dtype) != jnp.dtype(want):
                raise ValueError(
                    f"{self.name}: batch state[{key!r}] has dtype "
                    f"{arr.dtype}, declared {want}")
            if arr.ndim < 2 or arr.shape[0] != batch:
                raise ValueError(
                    f"{self.name}: batch state[{key!r}] must have a "
                    f"leading batch axis of {batch} rows; got shape "
                    f"{tuple(arr.shape)}")

    def drift_residual(
        self,
        state: AlgoState,
        graph: GraphState,
        *,
        layouts=None,
        backend: Optional[str] = None,
    ) -> Optional[jax.Array]:
        """f32[N_cap] fixed-point residual of ``state`` on the *live*
        graph — the quality controller's drift signal (see
        :mod:`repro.core.control`).

        The residual is ``|F(x) − x|`` for one application of the
        algorithm's exact update F over the full graph: zero everywhere
        at the true fixed point, and concentrated on the vertices a
        summarized sweep froze (or whose inputs the stream changed)
        otherwise.  One O(E) push per query, computed inside the fused
        step only when the controller is armed.

        ``layouts`` is the cached tuple matching :attr:`layout_specs`.
        Implementations must be pure gathers/pushes/elementwise ops (no
        host syncs) and must accept batched ``[B, N]`` state leaves
        unchanged (``push`` is batch-polymorphic).  The default returns
        ``None`` — the fused step then falls back to the per-query churn
        of :meth:`result_view` as a (weaker) drift proxy.
        """
        return None

    def batched_cold_seeds(
        self, batch_state: AlgoState,
    ) -> Optional[jax.Array]:
        """bool[B, N] seed masks for cold-start coverage, or ``None``.

        A freshly seated serving slot has no churn history, so its first
        waves need coverage beyond the churn-driven hot set.  Algorithms
        whose results are nonzero/finite only on the set reachable from a
        per-query seed (personalized PageRank's teleport support, the
        traversal sources) return those seed masks here: the batched
        fused step expands them to the reachability fixpoint and runs the
        cold wave on that — *seed-local* instead of the whole active set.
        The default ``None`` keeps full-active cold coverage (global
        algorithms: PageRank, HITS, Katz, connected components).
        """
        return None

    def batched_selection_scores(
        self,
        batch_state: AlgoState,
        row_mask: Optional[jax.Array] = None,
    ) -> jax.Array:
        """Aggregate f32[N_cap] hot-set signal for a ``[B, ...]`` bank.

        The serving engine picks *one* shared hot set per wave, so the B
        per-query :meth:`selection_view` signals collapse to their
        element-wise maximum — a vertex volatile for any live query stays
        hot for the whole wave.  ``row_mask`` rows that are False (vacant
        or finished slots) are excluded; if every row is masked the
        signal is all-zero.
        """
        scores = jax.vmap(self.selection_view)(batch_state)
        if row_mask is not None:
            scores = jnp.where(row_mask[:, None], scores, -jnp.inf)
        agg = jnp.max(scores, axis=0)
        return jnp.where(jnp.isfinite(agg), agg, 0.0)

    def __init_subclass__(cls, **kwargs):
        """Legacy-plugin dispatch, resolved once at class creation.

        A pre-semiring plugin overrides ``score_view``; the engine now
        reads ``result_view``.  Whenever a class (re-)defines
        ``score_view`` *below* the most-derived ``result_view`` in its MRO
        — a fresh old-style plugin, or a subclass of a shipped algorithm
        that customizes only ``score_view`` — the override is what the
        author meant the engine to see, so ``result_view`` is rerouted
        through it.  Classes defining both at the same level (the new API)
        are left alone.  Rerouted methods are tagged so the base
        ``score_view`` alias can skip them when a legacy override chains
        up via ``super().score_view(...)`` (no mutual recursion).
        """
        super().__init_subclass__(**kwargs)

        def defining(name):
            for klass in cls.__mro__:
                if name in vars(klass):
                    return klass
            return None

        sv, rv = defining("score_view"), defining("result_view")
        if (sv not in (None, StreamingAlgorithm) and rv is not None
                and sv is not rv
                # MRO position, not issubclass: a score_view supplied by a
                # mixin precedes the algorithm base without subclassing it
                and cls.__mro__.index(sv) < cls.__mro__.index(rv)):
            orig = vars(sv)["score_view"]

            def _rerouted(self, state, _orig=orig):
                return _orig(self, state)

            _rerouted._legacy_reroute = True
            _rerouted.__doc__ = (
                f"result_view rerouted through the legacy "
                f"{sv.__name__}.score_view override.")
            cls.result_view = _rerouted

    @abc.abstractmethod
    def result_view(self, state: AlgoState) -> jax.Array:
        """dtype[N_cap] query answer — PageRank/Katz scores, HITS
        authorities, int32 component labels, f32 distances, …

        Subclasses must override this (or, legacy pre-semiring plugins,
        ``score_view`` — :meth:`__init_subclass__` reroutes *before*
        ``__abstractmethods__`` is computed, so old plugins stay
        instantiable while a class implementing neither view still fails
        at construction).
        """

    def selection_view(self, state: AlgoState) -> jax.Array:
        """f32[N_cap] volatility signal: the v_s term in the hot-set
        Δ-expansion (Eqs. 4-5).  Ranking algorithms default to their
        scores; algorithms with non-score state (CC, SSSP) override with
        churn indicators (recent label flips / distance deltas)."""
        return self.result_view(state).astype(jnp.float32)

    def score_view(self, state: AlgoState) -> jax.Array:
        """Deprecated pre-semiring alias of :meth:`result_view` (the
        engine's selection now reads :meth:`selection_view` instead).

        Resolves to the first *non-rerouted* ``result_view`` in the MRO so
        a legacy override calling ``super().score_view(...)`` gets its
        parent's answer (the pre-split behaviour), not itself back.
        """
        for klass in type(self).__mro__:
            rv = vars(klass).get("result_view")
            if rv is not None and not getattr(rv, "_legacy_reroute", False):
                return rv(self, state)
        raise NotImplementedError(
            f"{type(self).__name__} implements neither result_view nor the "
            "legacy score_view")


def summaries_overflow(summaries: Tuple[SummaryBuffers, ...]) -> jax.Array:
    """True if any summary exceeded its capacities (caller must fall back)."""
    flag = summaries[0].overflow
    for s in summaries[1:]:
        flag = flag | s.overflow
    return flag


# ---------------------------------------------------------------------------
# PageRank — the paper's case study
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PageRankAlgorithm(StreamingAlgorithm):
    """Gelly-style PageRank (§2) on the five-UDF engine.

    ``warm_start=False`` (default) keeps the paper protocol: every EXACT
    action recomputes from the uniform start, so ground-truth wall times are
    comparable across queries and to prior sweep artifacts.  Set True to
    seed the power iteration from the previous ranks (fewer iterations, same
    fixed point — PageRank is a contraction).
    """

    beta: float = 0.85
    num_iters: int = 30
    tol: float = 0.0
    teleport_by_n: bool = False
    dangling: bool = False
    warm_start: bool = False

    name = "pagerank"
    state_dtypes = {"ranks": "float32"}

    def init_state(self, graph: GraphState) -> AlgoState:
        init = 1.0 / jnp.maximum(
            graph.num_active_nodes().astype(jnp.float32), 1.0
        ) if self.teleport_by_n else 1.0
        return {"ranks": jnp.where(graph.node_active, init, 0.0).astype(jnp.float32)}

    def exact(self, state, graph, *, layouts=None, backend=None):
        ranks, iters = _pagerank(
            graph,
            state["ranks"] if self.warm_start else None,
            beta=self.beta,
            num_iters=self.num_iters,
            tol=self.tol,
            teleport_by_n=self.teleport_by_n,
            dangling=self.dangling,
            layout=layouts[0] if layouts else None,
            backend=backend,
        )
        return {"ranks": ranks}, iters

    def summarized(self, state, graph, summaries, *, backend=None):
        (summary,) = summaries
        ranks, iters = _summarized_pagerank(
            summary,
            state["ranks"],
            beta=self.beta,
            num_iters=self.num_iters,
            tol=self.tol,
            backend=backend,
        )
        return {"ranks": ranks}, iters

    def summarized_batched(self, batch_state, graph, summaries, *,
                           row_mask=None, backend=None):
        (summary,) = summaries
        ranks, iters, row_delta = _summarized_pagerank_batched(
            summary,
            batch_state["ranks"],
            beta=self.beta,
            num_iters=self.num_iters,
            tol=self.tol,
            row_mask=row_mask,
            backend=backend,
        )
        return {"ranks": ranks}, iters, row_delta

    def drift_residual(self, state, graph, *, layouts=None, backend=None):
        # |(1-β)·t + β·push(r) − r| — zero at pagerank()'s fixed point.
        # Matches the exact update including the teleport normalization;
        # the rarely-used dangling redistribution is omitted (it only
        # shifts the residual by the dangling mass, same order as the
        # drift being measured).
        if layouts is None:
            return None
        r = state["ranks"]
        incoming = B.push(r, layouts[0], backend=backend)
        n_active = jnp.maximum(
            graph.num_active_nodes().astype(jnp.float32), 1.0)
        tele = jnp.where(self.teleport_by_n,
                         (1.0 - self.beta) / n_active, 1.0 - self.beta)
        new_r = jnp.where(graph.node_active,
                          tele + self.beta * incoming, 0.0)
        return jnp.abs(new_r - r)

    def result_view(self, state):
        return state["ranks"]


# ---------------------------------------------------------------------------
# Personalized PageRank — seeded teleport vector
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PersonalizedPageRankAlgorithm(StreamingAlgorithm):
    """PageRank with teleport mass restricted to a seed set.

    ``seeds`` is a (hashable) tuple of vertex ids; the teleport vector is
    uniform over the seeds and lives in the state dict (it is data, not a
    static knob).  Rankings are localized around the seeds — the streaming
    scenario is e.g. per-user recommendation feeds over a shared engine.
    """

    seeds: Tuple[int, ...] = (0,)
    beta: float = 0.85
    num_iters: int = 30
    tol: float = 0.0
    # False = EXACT recomputes from the teleport vector (protocol-faithful
    # baseline); True = seed from previous ranks (same fixed point, faster)
    warm_start: bool = False

    name = "personalized-pagerank"
    normalize_selection_scores = True
    state_dtypes = {"ranks": "float32", "teleport": "float32"}
    per_query_params = ("seeds",)  # identity lives in state["teleport"]

    def __post_init__(self):
        if not self.seeds:
            raise ValueError("personalized-pagerank needs >= 1 seed vertex")

    def _teleport(self, n_cap: int) -> jax.Array:
        seeds = jnp.asarray(self.seeds, jnp.int32)
        if int(seeds.min()) < 0:  # negative ids would wrap via jax indexing
            raise ValueError(f"seed {int(seeds.min())} is negative")
        if int(seeds.max()) >= n_cap:
            raise ValueError(
                f"seed {int(seeds.max())} >= node_capacity {n_cap}")
        t = jnp.zeros((n_cap,), jnp.float32)
        return t.at[seeds].add(1.0 / len(self.seeds))

    def init_state(self, graph: GraphState) -> AlgoState:
        t = self._teleport(graph.node_capacity)
        return {"ranks": t, "teleport": t}

    def exact(self, state, graph, *, layouts=None, backend=None):
        ranks, iters = _pagerank(
            graph,
            state["ranks"] if self.warm_start else None,
            beta=self.beta,
            num_iters=self.num_iters,
            tol=self.tol,
            teleport_v=state["teleport"],
            layout=layouts[0] if layouts else None,
            backend=backend,
        )
        return {"ranks": ranks, "teleport": state["teleport"]}, iters

    def summarized(self, state, graph, summaries, *, backend=None):
        (summary,) = summaries
        ranks, iters = _summarized_pagerank(
            summary,
            state["ranks"],
            beta=self.beta,
            num_iters=self.num_iters,
            tol=self.tol,
            teleport_v=state["teleport"],
            backend=backend,
        )
        return {"ranks": ranks, "teleport": state["teleport"]}, iters

    def summarized_batched(self, batch_state, graph, summaries, *,
                           row_mask=None, backend=None):
        # one engine lane serves B different seed sets: the teleport
        # vectors ride in the batch state ([B, N]), not in `self`
        (summary,) = summaries
        ranks, iters, row_delta = _summarized_pagerank_batched(
            summary,
            batch_state["ranks"],
            beta=self.beta,
            num_iters=self.num_iters,
            tol=self.tol,
            teleport_v=batch_state["teleport"],
            row_mask=row_mask,
            backend=backend,
        )
        return {"ranks": ranks, "teleport": batch_state["teleport"]}, \
            iters, row_delta

    def drift_residual(self, state, graph, *, layouts=None, backend=None):
        # |(1-β)·t(v) + β·push(r) − r|: the personalized-teleport fixed
        # point.  Batched states carry [B, N] ranks/teleports — push and
        # the elementwise ops are batch-polymorphic.
        if layouts is None:
            return None
        r = state["ranks"]
        incoming = B.push(r, layouts[0], backend=backend)
        new_r = jnp.where(graph.node_active,
                          (1.0 - self.beta) * state["teleport"]
                          + self.beta * incoming, 0.0)
        return jnp.abs(new_r - r)

    def batched_cold_seeds(self, batch_state):
        # ranks are nonzero only on the set reachable from the teleport
        # support — seed-local cold coverage suffices
        return batch_state["teleport"] > 0.0

    def result_view(self, state):
        return state["ranks"]


# ---------------------------------------------------------------------------
# HITS — hubs & authorities through a forward + reverse summary pair
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class HITSAlgorithm(StreamingAlgorithm):
    """Kleinberg's HITS with per-iteration L1 normalization.

    State carries both vectors plus the tracked global-σ estimate
    (``sigma`` — ``f32[2]``, one per direction); :meth:`result_view`
    exposes authorities (the usual query answer — swap for hubs with
    ``rank_by="hub"``).  The summarized path freezes cold contributions in
    *both* directions, which needs the forward and the reverse (transposed)
    big-vertex summary; its normalization is anchored to ``sigma``, which
    exact computations measure and summarized sweeps refresh (see
    :func:`repro.core.hits.summarized_hits`).

    EXACT actions warm-start from the previous vectors: HITS converges to
    the principal singular pair from any positive start, so unlike
    PageRank's protocol there is no canonical cold baseline to preserve and
    the warm start only saves iterations.
    """

    num_iters: int = 30
    tol: float = 0.0
    rank_by: str = "auth"

    name = "hits"
    normalize_selection_scores = True
    summary_weight = "unit"
    state_dtypes = {"auth": "float32", "hub": "float32", "sigma": "float32"}
    layout_specs = (("unit", False, "plus_times"), ("unit", True, "plus_times"))

    def __post_init__(self):
        if self.rank_by not in ("auth", "hub"):
            raise ValueError(
                f"rank_by must be 'auth' or 'hub', got {self.rank_by!r}")

    def init_state(self, graph: GraphState) -> AlgoState:
        n = jnp.maximum(graph.num_active_nodes().astype(jnp.float32), 1.0)
        uniform = jnp.where(graph.node_active, 1.0 / n, 0.0).astype(jnp.float32)
        return {"auth": uniform, "hub": uniform,
                "sigma": jnp.ones((2,), jnp.float32)}

    def exact(self, state, graph, *, layouts=None, backend=None):
        auth, hub, iters, sigma = _hits(
            graph,
            state["auth"],
            state["hub"],
            num_iters=self.num_iters,
            tol=self.tol,
            fwd_layout=layouts[0] if layouts else None,
            rev_layout=layouts[1] if layouts else None,
            backend=backend,
        )
        return {"auth": auth, "hub": hub, "sigma": sigma}, iters

    def build_summaries(
        self, state, graph, hot_mask, *, hot_node_capacity, hot_edge_capacity,
        layouts=None, backend=None, shard_bucket_capacity=None,
        ek_mask=None,
    ):
        fwd = _build_summary(
            graph, state["hub"], hot_mask,
            hot_node_capacity=hot_node_capacity,
            hot_edge_capacity=hot_edge_capacity,
            weight="unit",
            layout=layouts[0] if layouts else None,
            backend=backend,
            shard_bucket_capacity=shard_bucket_capacity,
            ek_mask=ek_mask,
        )
        rev = _build_summary(
            graph, state["auth"], hot_mask,
            hot_node_capacity=hot_node_capacity,
            hot_edge_capacity=hot_edge_capacity,
            weight="unit", reverse=True,
            layout=layouts[1] if layouts else None,
            backend=backend,
            shard_bucket_capacity=shard_bucket_capacity,
            ek_mask=ek_mask,
        )
        return (fwd, rev)

    def summarized(self, state, graph, summaries, *, backend=None):
        fwd, rev = summaries
        auth, hub, iters, sigma = _summarized_hits(
            fwd, rev, state["auth"], state["hub"], state["sigma"],
            num_iters=self.num_iters, tol=self.tol,
            backend=backend,
        )
        return {"auth": auth, "hub": hub, "sigma": sigma}, iters

    def summarized_batched(self, batch_state, graph, summaries, *,
                           row_mask=None, backend=None):
        fwd, rev = summaries
        auth, hub, iters, row_delta, sigma = _summarized_hits_batched(
            fwd, rev, batch_state["auth"], batch_state["hub"],
            batch_state["sigma"],
            num_iters=self.num_iters, tol=self.tol,
            row_mask=row_mask, backend=backend,
        )
        return {"auth": auth, "hub": hub, "sigma": sigma}, iters, row_delta

    def result_view(self, state):
        return state["auth"] if self.rank_by == "auth" else state["hub"]


# ---------------------------------------------------------------------------
# Katz centrality — attenuated walk counts (plus_times, unit weights)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class KatzAlgorithm(StreamingAlgorithm):
    """Katz centrality ``c = Σ_k α^k (Aᵀ)^k β·1`` on the five-UDF engine.

    The sweep contracts (and the fixed point exists) only while
    ``α < 1/σ_max(A)`` — keep ``alpha`` small on hub-heavy graphs.  EXACT
    actions warm-start from the previous scores by default (same fixed
    point, fewer iterations); ``warm_start=False`` restores the
    cold-baseline protocol.
    """

    alpha: float = 0.05
    beta: float = 1.0
    num_iters: int = 30
    tol: float = 0.0
    warm_start: bool = True

    name = "katz"
    normalize_selection_scores = True
    summary_weight = "unit"
    state_dtypes = {"katz": "float32"}
    layout_specs = (("unit", False, "plus_times"),)

    def __post_init__(self):
        if not 0.0 < self.alpha < 1.0:
            raise ValueError(f"alpha must be in (0, 1), got {self.alpha}")

    def init_state(self, graph: GraphState) -> AlgoState:
        return {"katz": jnp.where(graph.node_active, self.beta, 0.0).astype(
            jnp.float32)}

    def exact(self, state, graph, *, layouts=None, backend=None):
        c, iters = _katz(
            graph,
            state["katz"] if self.warm_start else None,
            alpha=self.alpha,
            beta=self.beta,
            num_iters=self.num_iters,
            tol=self.tol,
            layout=layouts[0] if layouts else None,
            backend=backend,
        )
        return {"katz": c}, iters

    def summarized(self, state, graph, summaries, *, backend=None):
        (summary,) = summaries
        c, iters = _summarized_katz(
            summary,
            state["katz"],
            alpha=self.alpha,
            beta=self.beta,
            num_iters=self.num_iters,
            tol=self.tol,
            backend=backend,
        )
        return {"katz": c}, iters

    def summarized_batched(self, batch_state, graph, summaries, *,
                           row_mask=None, backend=None):
        (summary,) = summaries
        c, iters, row_delta = _summarized_katz_batched(
            summary,
            batch_state["katz"],
            alpha=self.alpha,
            beta=self.beta,
            num_iters=self.num_iters,
            tol=self.tol,
            row_mask=row_mask,
            backend=backend,
        )
        return {"katz": c}, iters, row_delta

    def drift_residual(self, state, graph, *, layouts=None, backend=None):
        # |β + α·push(c) − c| — zero at katz()'s fixed point
        if layouts is None:
            return None
        c = state["katz"]
        incoming = B.push(c, layouts[0], backend=backend)
        new_c = jnp.where(graph.node_active,
                          self.beta + self.alpha * incoming, 0.0)
        return jnp.abs(new_c - c)

    def result_view(self, state):
        return state["katz"]


# ---------------------------------------------------------------------------
# Connected components — label-min propagation (min_min, int32 state)
# ---------------------------------------------------------------------------


def _finite_churn(new: jax.Array, old: jax.Array) -> jax.Array:
    """f32 per-vertex change indicator robust to ±∞/sentinel state:
    |new − old| where both are finite, 1.0 where exactly one is, 0 else."""
    new_f = new.astype(jnp.float32)
    old_f = old.astype(jnp.float32)
    both = jnp.isfinite(new_f) & jnp.isfinite(old_f)
    return jnp.where(both, jnp.abs(new_f - old_f),
                     jnp.where(new_f != old_f, 1.0, 0.0))


@dataclass(frozen=True)
class ConnectedComponentsAlgorithm(StreamingAlgorithm):
    """Weakly-connected components via min-label propagation.

    The first non-float workload on the engine: state is *int32* labels
    (every vertex converges to the minimum vertex id in its weakly
    connected component; inactive vertices hold the int32-max sentinel),
    propagated over the ``min_min`` semiring in both edge orientations.
    :meth:`selection_view` is the label-*churn* indicator — 1.0 where the
    last sweep changed a vertex's label — so the Δ-expansion grows the hot
    set around recently-merged regions rather than around big labels.

    EXACT actions recompute labels from scratch by default (correct under
    removals); ``warm_start=True`` reuses previous labels, which is exact
    for the paper's addition-only streams and converges faster.
    """

    num_iters: int = 30
    warm_start: bool = False

    name = "connected-components"
    normalize_selection_scores = True
    rank_descending = False  # smaller labels first (component min ids)
    drift_normalize = "count"  # residual = label flips, not id magnitudes
    drift_contraction = 0.0  # label relaxation has no geometric tail
    semiring = "min_min"
    summary_weight = "unit"
    state_dtypes = {"labels": "int32", "churn": "float32"}
    layout_specs = (("unit", False, "min_min"), ("unit", True, "min_min"))

    def init_state(self, graph: GraphState) -> AlgoState:
        ids = jnp.arange(graph.node_capacity, dtype=jnp.int32)
        return {
            "labels": jnp.where(graph.node_active, ids, LABEL_SENTINEL),
            "churn": jnp.zeros((graph.node_capacity,), jnp.float32),
        }

    def exact(self, state, graph, *, layouts=None, backend=None):
        labels, iters = _cc(
            graph,
            state["labels"] if self.warm_start else None,
            num_iters=self.num_iters,
            fwd_layout=layouts[0] if layouts else None,
            rev_layout=layouts[1] if layouts else None,
            backend=backend,
        )
        return {"labels": labels,
                "churn": (labels != state["labels"]).astype(jnp.float32)}, \
            iters

    def build_summaries(
        self, state, graph, hot_mask, *, hot_node_capacity, hot_edge_capacity,
        layouts=None, backend=None, shard_bucket_capacity=None,
        ek_mask=None,
    ):
        common = dict(hot_node_capacity=hot_node_capacity,
                      hot_edge_capacity=hot_edge_capacity,
                      weight="unit", semiring="min_min", backend=backend,
                      shard_bucket_capacity=shard_bucket_capacity,
                      ek_mask=ek_mask)
        fwd = _build_summary(
            graph, state["labels"], hot_mask,
            layout=layouts[0] if layouts else None, **common)
        rev = _build_summary(
            graph, state["labels"], hot_mask, reverse=True,
            layout=layouts[1] if layouts else None, **common)
        return (fwd, rev)

    def summarized(self, state, graph, summaries, *, backend=None):
        fwd, rev = summaries
        labels, iters = _summarized_cc(
            fwd, rev, state["labels"],
            num_iters=self.num_iters, backend=backend,
        )
        return {"labels": labels,
                "churn": (labels != state["labels"]).astype(jnp.float32)}, \
            iters

    def summarized_batched(self, batch_state, graph, summaries, *,
                           row_mask=None, backend=None):
        fwd, rev = summaries
        labels, iters, changed = _summarized_cc_batched(
            fwd, rev, batch_state["labels"],
            num_iters=self.num_iters, row_mask=row_mask, backend=backend,
        )
        churn = (labels != batch_state["labels"]).astype(jnp.float32)
        return {"labels": labels, "churn": churn}, iters, \
            changed.astype(jnp.float32)

    def drift_residual(self, state, graph, *, layouts=None, backend=None):
        # 1.0 where one more min-label relaxation would still change a
        # vertex (the fixpoint test of connected_components's relax step)
        if layouts is None or len(layouts) < 2:
            return None
        lab = state["labels"]
        relaxed = jnp.minimum(
            lab,
            jnp.minimum(
                B.push(lab, layouts[0], semiring="min_min",
                       backend=backend),
                B.push(lab, layouts[1], semiring="min_min",
                       backend=backend)))
        changed = graph.node_active & (relaxed != lab)
        return changed.astype(jnp.float32)

    def result_view(self, state):
        return state["labels"]

    def selection_view(self, state):
        return state["churn"]


# ---------------------------------------------------------------------------
# SSSP — single-source shortest paths (min_plus)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class SSSPAlgorithm(StreamingAlgorithm):
    """Streaming single-source shortest paths (Bellman-Ford on min-plus).

    ``sources`` is a (hashable) tuple of vertex ids whose distances are
    pinned to 0; unreachable vertices hold +∞.  Edge lengths default to
    the unit hop count; streams that register edges with a per-edge
    ``weights`` column (``GraphState.edge_len``) feed real lengths into
    every ``weight="length"`` layout automatically.  :meth:`selection_view`
    is the
    distance-*delta* indicator of the last sweep, so the Δ-expansion
    follows shortest-path churn instead of raw distance magnitude.

    EXACT actions recompute from the sources by default (correct under
    removals); ``warm_start=True`` relaxes from the previous distances,
    exact for addition-only streams (distances are monotone
    non-increasing) and typically far fewer iterations.
    """

    sources: Tuple[int, ...] = (0,)
    num_iters: int = 30
    warm_start: bool = False

    name = "sssp"
    normalize_selection_scores = True
    rank_descending = False  # nearest vertices first
    drift_contraction = 0.0  # Bellman-Ford settles, no geometric tail
    semiring = "min_plus"
    summary_weight = "length"
    state_dtypes = {"dist": "float32", "source": "bool",
                    "delta": "float32"}
    per_query_params = ("sources",)  # identity lives in state["source"]
    layout_specs = (("length", False, "min_plus"),)

    def __post_init__(self):
        if not self.sources:
            raise ValueError("sssp needs >= 1 source vertex")

    def _source_mask(self, n_cap: int) -> jax.Array:
        src = jnp.asarray(self.sources, jnp.int32)
        if int(src.min()) < 0:
            raise ValueError(f"source {int(src.min())} is negative")
        if int(src.max()) >= n_cap:
            raise ValueError(
                f"source {int(src.max())} >= node_capacity {n_cap}")
        return jnp.zeros((n_cap,), bool).at[src].set(True)

    def init_state(self, graph: GraphState) -> AlgoState:
        source = self._source_mask(graph.node_capacity)
        return {
            "dist": jnp.where(source, 0.0, jnp.inf).astype(jnp.float32),
            "source": source,
            "delta": jnp.zeros((graph.node_capacity,), jnp.float32),
        }

    def exact(self, state, graph, *, layouts=None, backend=None):
        dist, iters = _sssp(
            graph,
            state["source"],
            state["dist"] if self.warm_start else None,
            num_iters=self.num_iters,
            layout=layouts[0] if layouts else None,
            backend=backend,
        )
        return {"dist": dist, "source": state["source"],
                "delta": _finite_churn(dist, state["dist"])}, iters

    # build_summaries: the inherited default — one forward summary frozen
    # from result_view (= dist) over summary_weight/semiring declared above

    def summarized(self, state, graph, summaries, *, backend=None):
        (summary,) = summaries
        dist, iters = _summarized_sssp(
            summary, state["dist"], state["source"],
            num_iters=self.num_iters, backend=backend,
        )
        return {"dist": dist, "source": state["source"],
                "delta": _finite_churn(dist, state["dist"])}, iters

    def summarized_batched(self, batch_state, graph, summaries, *,
                           row_mask=None, backend=None):
        # one engine lane serves B different source sets: the pinned-0
        # masks ride in the batch state ([B, N]), not in `self`
        (summary,) = summaries
        dist, iters, changed = _summarized_sssp_batched(
            summary, batch_state["dist"], batch_state["source"],
            num_iters=self.num_iters, row_mask=row_mask, backend=backend,
        )
        return {"dist": dist, "source": batch_state["source"],
                "delta": _finite_churn(dist, batch_state["dist"])}, \
            iters, changed.astype(jnp.float32)

    def drift_residual(self, state, graph, *, layouts=None, backend=None):
        # how much one more full-graph relaxation would still lower the
        # distances (finite-churn encoded: a reachability flip counts 1.0)
        if layouts is None:
            return None
        dist = state["dist"]
        incoming = B.push(dist, layouts[0], semiring="min_plus",
                          backend=backend)
        relaxed = jnp.where(state["source"], 0.0,
                            jnp.minimum(dist, incoming))
        return _finite_churn(relaxed, dist)

    def batched_cold_seeds(self, batch_state):
        # distances are finite only on the set reachable from the sources
        return batch_state["source"]

    def result_view(self, state):
        return state["dist"]

    def selection_view(self, state):
        return state["delta"]


# ---------------------------------------------------------------------------
# Widest path — most-reliable paths (max_times)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class WidestPathAlgorithm(StreamingAlgorithm):
    """Streaming widest (most-reliable) paths on the ``max_times`` semiring.

    ``sources`` is a (hashable) tuple of vertex ids whose widths are pinned
    to 1; unreachable vertices hold 0.  Edge lengths act as multiplicative
    reliabilities/capacities and must be **non-negative** — streams that
    register edges with a ``weights`` column feed them into the
    ``weight="length"`` layout automatically; unit lengths make every
    reachable vertex width 1.  This is the seventh registry algorithm and
    the one exercising the masked-reduce *max* kernel path end to end
    (exact, summarized, and batched serving sweeps).

    EXACT actions recompute from the sources by default (correct under
    removals); ``warm_start=True`` relaxes from the previous widths, exact
    for addition-only streams (widths are monotone non-decreasing).
    """

    sources: Tuple[int, ...] = (0,)
    num_iters: int = 30
    warm_start: bool = False

    name = "widest-path"
    normalize_selection_scores = True
    drift_contraction = 0.0  # bottleneck relaxation settles in finite sweeps
    semiring = "max_times"
    summary_weight = "length"
    state_dtypes = {"width": "float32", "source": "bool",
                    "delta": "float32"}
    per_query_params = ("sources",)  # identity lives in state["source"]
    layout_specs = (("length", False, "max_times"),)

    def __post_init__(self):
        if not self.sources:
            raise ValueError("widest-path needs >= 1 source vertex")

    def _source_mask(self, n_cap: int) -> jax.Array:
        src = jnp.asarray(self.sources, jnp.int32)
        if int(src.min()) < 0:
            raise ValueError(f"source {int(src.min())} is negative")
        if int(src.max()) >= n_cap:
            raise ValueError(
                f"source {int(src.max())} >= node_capacity {n_cap}")
        return jnp.zeros((n_cap,), bool).at[src].set(True)

    def init_state(self, graph: GraphState) -> AlgoState:
        source = self._source_mask(graph.node_capacity)
        return {
            "width": jnp.where(source, 1.0, 0.0).astype(jnp.float32),
            "source": source,
            "delta": jnp.zeros((graph.node_capacity,), jnp.float32),
        }

    def exact(self, state, graph, *, layouts=None, backend=None):
        width, iters = _widest_path(
            graph,
            state["source"],
            state["width"] if self.warm_start else None,
            num_iters=self.num_iters,
            layout=layouts[0] if layouts else None,
            backend=backend,
        )
        return {"width": width, "source": state["source"],
                "delta": _finite_churn(width, state["width"])}, iters

    # build_summaries: the inherited default — one forward summary frozen
    # from result_view (= width) over summary_weight/semiring declared above

    def summarized(self, state, graph, summaries, *, backend=None):
        (summary,) = summaries
        width, iters = _summarized_widest_path(
            summary, state["width"], state["source"],
            num_iters=self.num_iters, backend=backend,
        )
        return {"width": width, "source": state["source"],
                "delta": _finite_churn(width, state["width"])}, iters

    def summarized_batched(self, batch_state, graph, summaries, *,
                           row_mask=None, backend=None):
        # one engine lane serves B different source sets: the pinned-1
        # masks ride in the batch state ([B, N]), not in `self`
        (summary,) = summaries
        width, iters, changed = _summarized_widest_path_batched(
            summary, batch_state["width"], batch_state["source"],
            num_iters=self.num_iters, row_mask=row_mask, backend=backend,
        )
        return {"width": width, "source": batch_state["source"],
                "delta": _finite_churn(width, batch_state["width"])}, \
            iters, changed.astype(jnp.float32)

    def drift_residual(self, state, graph, *, layouts=None, backend=None):
        # how much one more max_times relaxation would still widen paths
        if layouts is None:
            return None
        width = state["width"]
        incoming = B.push(width, layouts[0], semiring="max_times",
                          backend=backend)
        relaxed = jnp.where(state["source"], 1.0,
                            jnp.maximum(width, incoming))
        return jnp.abs(relaxed - width)

    def batched_cold_seeds(self, batch_state):
        # widths are nonzero only on the set reachable from the sources
        return batch_state["source"]

    def result_view(self, state):
        return state["width"]

    def selection_view(self, state):
        return state["delta"]


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Callable[..., StreamingAlgorithm]] = {}
#: alias -> canonical name.  Aliases resolve in :func:`make_algorithm` but
#: never show up in :func:`available_algorithms` (and thus in CLI choices
#: or benchmark artifact names), so one algorithm has one canonical spelling.
_ALIASES: Dict[str, str] = {}


def register_algorithm(
    name: str,
    factory: Callable[..., StreamingAlgorithm],
    *,
    aliases: Tuple[str, ...] = (),
) -> None:
    """Register an algorithm factory under ``name`` (overwrites allowed —
    latest registration wins, so users can shadow the built-ins)."""
    _REGISTRY[name] = factory
    for alias in aliases:
        _ALIASES[alias] = name


def available_algorithms() -> Tuple[str, ...]:
    """Canonical registered names (aliases resolve but are not listed)."""
    return tuple(sorted(_REGISTRY))


def algorithm_factory(name: str) -> Callable[..., StreamingAlgorithm]:
    """The registered factory for a name or alias, without instantiating —
    for callers that want to introspect an algorithm's knobs (e.g. its
    dataclass fields / signature) before constructing it."""
    try:
        return _REGISTRY[_ALIASES.get(name, name)]
    except KeyError:
        raise KeyError(
            f"unknown algorithm {name!r}; registered: "
            f"{', '.join(available_algorithms())}") from None


def factory_accepts(factory: Callable, knob: str) -> bool:
    """True if ``factory``'s signature takes ``knob`` — directly or via
    ``**kwargs`` (the documented registration pattern).  The single answer
    to "can this algorithm receive this keyword?", shared by the session
    builder's legacy-knob forwarding and example drivers."""
    import inspect

    try:
        params = inspect.signature(factory).parameters
    except (TypeError, ValueError):
        return False
    return knob in params or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values())


def make_algorithm(spec, **params) -> StreamingAlgorithm:
    """Resolve ``spec`` into a :class:`StreamingAlgorithm` instance.

    ``spec`` may be an instance (returned as-is; ``params`` must be empty),
    or a registry name/alias with factory kwargs, e.g.
    ``make_algorithm("personalized-pagerank", seeds=(3, 14))``.
    """
    if isinstance(spec, StreamingAlgorithm):
        if params:
            raise ValueError(
                "algorithm instance given — pass parameters to its "
                "constructor instead")
        return spec
    return algorithm_factory(spec)(**params)


register_algorithm("pagerank", PageRankAlgorithm)
register_algorithm("personalized-pagerank", PersonalizedPageRankAlgorithm,
                   aliases=("ppr",))
register_algorithm("hits", HITSAlgorithm)
register_algorithm("katz", KatzAlgorithm)
register_algorithm("connected-components", ConnectedComponentsAlgorithm,
                   aliases=("cc", "wcc"))
register_algorithm("sssp", SSSPAlgorithm,
                   aliases=("shortest-paths",))
register_algorithm("widest-path", WidestPathAlgorithm,
                   aliases=("most-reliable-path",))
