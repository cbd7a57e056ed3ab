"""Fused approximate-query step: selection + summary + iteration in one jit.

This is the performance-critical form of the paper's query path (Alg. 1
lines 6–19 for the compute-approximate action) and the function the
multi-pod dry-run lowers for the `veilgraph-pagerank` workload:

    (GraphState, ranks, deg_prev, r, Δ)  ->  (ranks', stats)

Differences vs the unfused engine path:
- no host round-trip between summary construction and the power
  iterations: :func:`fused_query_step` is one XLA program per query, and
  the engine's split form (:func:`hot_set_step`, then
  :func:`summarized_step`) makes one small read between them, the two
  counts that size the summary buffers to the hot set;
- the overflow fallback (|K| or |E_K| over capacity -> exact recompute)
  stays a device-side flag: the summarized result is computed
  unconditionally and the caller discards it and recomputes exactly when
  ``used_fallback`` reads back True (the engine does this on host);
- the algorithm-generic :func:`fused_query_step` is mesh-aware: pass
  cached :class:`~repro.core.backend.ShardedEdgeLayout` s (the engine
  does when configured with a mesh) or ``mesh=``/``mesh_axes=`` to build
  them inline, and every O(E) pass — the frozen big-vertex boundary and
  the exact sweeps — runs as a shard_map partial push + semiring
  all-reduce over per-shard locally-sorted edge streams, with node
  vectors replicated (the TPU analogue of Pregel's vertex-cut message
  exchange).  Summary construction itself is mesh-native too: with
  sharded layouts, ``build_summary`` runs the distributed bucket sort
  (per-shard E_K selection, capacity-padded all-to-all, shard-local row
  offsets — see :func:`repro.core.pagerank._build_summary_sharded`), so
  the lowered program contains no replicated edge-space gathers and no
  unsorted ``push_coo``.
- under ``EngineConfig.async_rebuild`` every input here is *epoch-bound*:
  the graph state, the layouts and the ``deg_prev``/``active_prev``
  baselines all come from one frozen
  :class:`~repro.core.epoch.EpochSnapshot`, never from the engine's live
  (possibly mid-apply) state — and the caller fetches the stats/result
  only after dispatching the next epoch's rebuild, so this program's
  execution overlaps the apply+sort work queued behind it.  The program
  itself is identical in both modes (same trace, zero retraces across an
  epoch flip — pinned by ``analysis/programs.py``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.graph.graph import GraphState
from repro.core import spans
from repro.core.hotset import _frontier_sweep, select_hot_set


class QueryStepStats(NamedTuple):
    """Device-side stats for one fused query step (one host transfer)."""
    num_hot: jax.Array
    num_kr: jax.Array
    num_kn: jax.Array
    num_kdelta: jax.Array
    num_ek: jax.Array
    num_eb: jax.Array
    iterations: jax.Array
    used_fallback: jax.Array  # bool
    # drift estimator outputs (repro.core.control) — populated only under
    # with_drift=True; they ride the same single stats transfer, so the
    # quality controller costs no extra host sync
    drift_probe: jax.Array = 0.0  # sampled fixed-point residual (relative)
    drift_cold: jax.Array = 0.0   # residual mass frozen outside K (relative)


@spans.scope(spans.DRIFT)
def _drift_from_state(algo, new_state, old_state, graph, hot, probe_ids,
                      *, layouts, backend):
    """(drift_probe, drift_cold) for one fused step — the algorithm's
    fixed-point residual when it defines one, else the per-query churn of
    its result view as a proxy.  Works unchanged for batched ``[B, N]``
    states (push is batch-polymorphic); batched callers vmap the signal
    reduction instead."""
    from repro.core.algorithm import _finite_churn
    from repro.core.control import drift_signals

    resid = algo.drift_residual(
        new_state, graph, layouts=layouts, backend=backend)
    if resid is None:
        resid = _finite_churn(algo.result_view(new_state),
                              algo.result_view(old_state))
    result = algo.result_view(new_state)
    if result.ndim == 1:
        return drift_signals(resid, result, hot, graph.node_active,
                             probe_ids, normalize=algo.drift_normalize)
    sig = functools.partial(drift_signals, normalize=algo.drift_normalize)
    return jax.vmap(sig, in_axes=(0, 0, None, None, None))(
        resid, result, hot, graph.node_active, probe_ids)


# ---------------------------------------------------------------------------
# Algorithm-generic fused step
# ---------------------------------------------------------------------------


def _select(state, algo_state, deg_prev, active_prev, r, delta, *, algo, n,
            delta_hop_cap, degree_mode, expand_both):
    """The hot set K of one query and its stage sizes."""
    with spans.scope(spans.HOTSET):
        scores = algo.selection_view(algo_state)
        return select_hot_set(
            state, deg_prev, scores, r, delta,
            active_prev=active_prev, n=n, delta_hop_cap=delta_hop_cap,
            degree_mode=degree_mode, expand_both=expand_both,
            normalize_scores=algo.normalize_selection_scores,
        )


def _summarize_and_sweep(state, algo_state, hot, hstats, probe_ids, *, algo,
                         hot_node_capacity, hot_edge_capacity, layouts,
                         backend, shard_bucket_capacity, with_drift):
    """Summaries of ``hot`` at the given static sizes, then the summarized
    sweep; returns ``(new_algo_state, QueryStepStats)``."""
    from repro.core.algorithm import summaries_overflow

    # only forward the knob when set, so legacy plugin overrides of
    # build_summaries without the keyword keep working
    extra = ({} if shard_bucket_capacity is None
             else {"shard_bucket_capacity": shard_bucket_capacity})
    with spans.scope(spans.SUMMARY):
        summaries = algo.build_summaries(
            algo_state, state, hot,
            hot_node_capacity=hot_node_capacity,
            hot_edge_capacity=hot_edge_capacity,
            layouts=layouts, backend=backend, **extra,
        )
    with spans.scope(spans.SWEEP):
        new_state, iters = algo.summarized(
            algo_state, state, summaries, backend=backend)

    num_eb = summaries[0].num_eb
    for s in summaries[1:]:
        num_eb = num_eb + s.num_eb
    stats = QueryStepStats(
        num_hot=hstats.num_hot,
        num_kr=hstats.num_kr,
        num_kn=hstats.num_kn,
        num_kdelta=hstats.num_kdelta,
        num_ek=summaries[0].num_ek,
        num_eb=num_eb,
        iterations=iters,
        used_fallback=summaries_overflow(summaries),
    )
    if with_drift:
        drift_probe, drift_cold = _drift_from_state(
            algo, new_state, algo_state, state, hot, probe_ids,
            layouts=layouts, backend=backend)
        stats = stats._replace(drift_probe=drift_probe,
                               drift_cold=drift_cold)
    return new_state, stats


@functools.partial(
    jax.jit,
    static_argnames=(
        "algo", "hot_node_capacity", "hot_edge_capacity",
        "n", "delta_hop_cap", "degree_mode", "expand_both", "backend",
        "mesh", "mesh_axes", "shard_bucket_capacity", "with_drift",
    ),
)
def fused_query_step(
    state: GraphState,
    algo_state,
    deg_prev: jax.Array,
    active_prev: jax.Array,
    r: jax.Array,
    delta: jax.Array,
    probe_ids: jax.Array | None = None,
    *,
    algo,
    hot_node_capacity: int,
    hot_edge_capacity: int,
    n: int = 1,
    delta_hop_cap: int = 4,
    degree_mode: str = "out",
    expand_both: bool = False,
    layouts=None,
    backend: str | None = None,
    mesh=None,
    mesh_axes=None,
    shard_bucket_capacity: int | None = None,
    with_drift: bool = False,
):
    """One summarized query for *any* :class:`StreamingAlgorithm`.

    ``algo`` is a frozen (hashable) algorithm instance riding through jit as
    a static argument, so its ``selection_view`` / ``build_summaries`` /
    ``summarized`` trace inline: selection, summary construction and the
    restricted power sweep compile to a single XLA program per
    (algorithm, capacities) pair.  The engine runs the same work as two
    programs, :func:`hot_set_step` then :func:`summarized_step`, so that
    the summary's static sizes follow the hot set; mesh engines call this
    one at the configured capacities.

    ``layouts`` is the cached edge-layout tuple matching
    ``algo.layout_specs`` (the engine builds it once per applied update
    batch) — single :class:`~repro.core.backend.EdgeLayout` s or, under a
    mesh-configured engine, :class:`~repro.core.backend.ShardedEdgeLayout`
    s, which route the frozen big-vertex pass through the shard_map-ed
    partial push.  ``mesh``/``mesh_axes`` (static) cover the cache-less
    caller — the multi-pod dry-run: with ``layouts=None`` and a mesh, the
    per-shard locally-sorted layouts are built inline (S independent
    axis-1 sorts, communication-free under GSPMD edge sharding), so the
    whole query step compiles sharded with zero unsorted ``push_coo``
    calls.  ``backend`` picks the propagation implementation inside each
    shard for the summarized sweep and the frozen big-vertex pass.

    ``probe_ids`` (i32[P]) + static ``with_drift=True`` additionally
    compute the on-device drift estimator (:mod:`repro.core.control`):
    the algorithm's fixed-point residual sampled on the probe set and its
    mass outside the hot set, folded into the returned stats'
    ``drift_probe``/``drift_cold`` fields — same single host transfer,
    no extra sync.

    Returns ``(new_algo_state, QueryStepStats)``.  Overflow does not
    branch on device — the caller discards
    ``new_algo_state`` and recomputes exactly when ``used_fallback`` is set.
    """
    from repro.core.backend import normalize_layout_spec

    if layouts is None and mesh is not None:
        from repro.graph.partition import build_sharded_layout

        with spans.scope(spans.LAYOUT):
            layouts = tuple(
                build_sharded_layout(state, mesh=mesh, axes=mesh_axes,
                                     weight=w, reverse=rev, semiring=s)
                for (w, rev, s) in map(normalize_layout_spec,
                                       algo.layout_specs))

    hot, hstats = _select(
        state, algo_state, deg_prev, active_prev, r, delta, algo=algo, n=n,
        delta_hop_cap=delta_hop_cap, degree_mode=degree_mode,
        expand_both=expand_both)
    return _summarize_and_sweep(
        state, algo_state, hot, hstats, probe_ids, algo=algo,
        hot_node_capacity=hot_node_capacity,
        hot_edge_capacity=hot_edge_capacity, layouts=layouts,
        backend=backend, shard_bucket_capacity=shard_bucket_capacity,
        with_drift=with_drift)


def _step_module(fn):
    """Compile ``fn`` under :func:`fused_query_step`'s module name: a
    profile then reports the device time of either stage of the split
    step under the one name the approximate step has always had
    (``jit_fused_query_step``)."""
    fn.__name__ = fn.__qualname__ = fused_query_step.__name__
    return fn


@functools.partial(
    jax.jit,
    static_argnames=("algo", "n", "delta_hop_cap", "degree_mode",
                     "expand_both"),
)
@_step_module
def hot_set_step(
    state: GraphState,
    algo_state,
    deg_prev: jax.Array,
    active_prev: jax.Array,
    r: jax.Array,
    delta: jax.Array,
    *,
    algo,
    n: int = 1,
    delta_hop_cap: int = 4,
    degree_mode: str = "out",
    expand_both: bool = False,
):
    """First stage of the split approximate step: the hot set, and the two
    counts the summary's static sizes are chosen from.

    Returns ``(hot bool[N_cap], HotSetStats, counts int32[2])`` with
    ``counts = [|K|, |E_K|]``.  ``|E_K|`` is the number of live edges with
    both endpoints hot; it is the same for the forward and the reverse
    summary, so one count serves both.  The caller reads ``counts`` (one
    small transfer), picks the sizes and runs :func:`summarized_step`.
    """
    hot, hstats = _select(
        state, algo_state, deg_prev, active_prev, r, delta, algo=algo, n=n,
        delta_hop_cap=delta_hop_cap, degree_mode=degree_mode,
        expand_both=expand_both)
    with spans.scope(spans.SUMMARY), spans.scope(spans.SUMMARY_SELECT):
        ek = state.edge_mask() & hot[state.src] & hot[state.dst]
        counts = jnp.stack([hstats.num_hot, jnp.sum(ek.astype(jnp.int32))])
    return hot, hstats, counts


@functools.partial(
    jax.jit,
    static_argnames=("algo", "hot_node_capacity", "hot_edge_capacity",
                     "backend", "with_drift"),
)
@_step_module
def summarized_step(
    state: GraphState,
    algo_state,
    hot: jax.Array,
    hstats,
    probe_ids: jax.Array | None = None,
    *,
    algo,
    hot_node_capacity: int,
    hot_edge_capacity: int,
    layouts=None,
    backend: str | None = None,
    with_drift: bool = False,
):
    """Second stage of the split approximate step: the summaries of the hot
    set from :func:`hot_set_step`, at static sizes the caller chose from
    its counts, and the summarized sweep.

    For a given hot set the summaries do not depend on the sizes, as
    long as they hold ``|K|`` and ``|E_K|``: local ids, the E_K order and
    the kernel's tile boundaries are those of a full-capacity summary,
    with fewer empty slots and trailing tiles.  Answers match bit for
    bit unless the sweep sums over the K-slot buffers (HITS's
    normalisation), whose float32 association follows their length.
    Sizes below the counts raise
    ``used_fallback`` exactly as in :func:`fused_query_step`.  Returns
    ``(new_algo_state, QueryStepStats)``.
    """
    return _summarize_and_sweep(
        state, algo_state, hot, hstats, probe_ids, algo=algo,
        hot_node_capacity=hot_node_capacity,
        hot_edge_capacity=hot_edge_capacity, layouts=layouts,
        backend=backend, shard_bucket_capacity=None, with_drift=with_drift)


# ---------------------------------------------------------------------------
# Batched (multi-query) fused step — the serving engine's wave kernel
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=(
        "algo", "hot_node_capacity", "hot_edge_capacity",
        "n", "delta_hop_cap", "degree_mode", "expand_both", "backend",
        "mesh", "mesh_axes", "shard_bucket_capacity", "with_drift",
    ),
)
def fused_query_step_batched(
    state: GraphState,
    batch_state,
    deg_prev: jax.Array,
    active_prev: jax.Array,
    r: jax.Array,
    delta: jax.Array,
    row_mask: jax.Array,
    cold_rows: jax.Array | None = None,
    probe_ids: jax.Array | None = None,
    *,
    algo,
    hot_node_capacity: int,
    hot_edge_capacity: int,
    n: int = 1,
    delta_hop_cap: int = 4,
    degree_mode: str = "out",
    expand_both: bool = False,
    layouts=None,
    backend: str | None = None,
    mesh=None,
    mesh_axes=None,
    shard_bucket_capacity: int | None = None,
    with_drift: bool = False,
):
    """One summarized wave for B concurrent queries of one algorithm.

    The multi-tenant analogue of :func:`fused_query_step`:
    ``batch_state`` carries every slot's per-query state with a leading
    batch axis (``[B, ...]`` leaves — e.g. B teleport vectors, B source
    masks), and the whole wave shares ONE hot set, ONE summary structure
    and ONE edge layout across all B queries:

    - selection reads ``algo.batched_selection_scores`` — the element-wise
      max of the live rows' volatility signals, so a vertex hot for any
      query is hot for the wave;
    - ``algo.build_summaries`` sees the ``[B, N]`` frozen vectors and
      produces summaries whose compacted E_K structure is row-independent
      with a per-query ``b_in [B, K_cap]`` (one batched push);
    - ``algo.summarized_batched`` then runs the restricted sweep as
      batched ``[B, K_cap]`` pushes, with ``row_mask`` (bool[B], True =
      live) freezing finished/vacant serving slots so they stop
      contributing work and report zero delta.

    ``cold_rows`` (traced bool[B], optional) marks freshly seated slots
    that have not yet converged once.  The paper's selection is driven
    by degree churn and score volatility *since the last measurement
    point* — a cold query has neither (its state is brand new), so its
    first waves need coverage beyond the churn-selected K, exactly as
    the single-query protocol computes initial results over all of G
    before streaming.  Instead of widening to the whole active set, the
    wave expands the cold rows' **seed-local reachability**: the
    algorithm's :meth:`~repro.core.algorithm.StreamingAlgorithm.\
batched_cold_seeds` masks (PPR teleport support, SSSP/widest sources)
    are OR-reduced over the live cold rows and grown to their forward
    reachability fixpoint in a growth-conditioned ``while_loop`` — zero
    sweeps when no row is cold.  The fixpoint is closed under out-edges,
    so no hot→cold edge exists: E_K contains every edge among reachable
    vertices, unreachable cold vertices hold their ⊕-identity values,
    and the seed-local wave is result-identical to the old full-width
    one (bitwise for the min/max semirings).  Algorithms without seed
    structure (``batched_cold_seeds() is None`` — global workloads like
    PageRank/CC) fall back to full-active coverage when any live row is
    cold.  Capacities permitting — bounded caps overflow into the exact
    fallback as usual.

    ``probe_ids`` + static ``with_drift=True`` additionally compute the
    per-slot drift estimator (:mod:`repro.core.control`) and return a
    fourth value ``row_drift f32[B, 2]`` (columns: drift_probe,
    drift_cold per slot, zeroed for vacant rows), riding the same
    transfer as ``row_delta`` — no extra host sync.  The wave-level
    stats carry the max over live slots.

    Returns ``(new_batch_state, QueryStepStats, row_delta f32[B])`` —
    plus ``row_drift`` under ``with_drift`` — where stats describe the
    shared wave (hot-set sizes, E_K/E_B, overflow);
    ``row_delta`` is the per-slot convergence signal the serving engine's
    harvest step compares against each request's tolerance.  Overflow
    semantics are unchanged: no device-side branch, the caller discards
    the batch result and falls back to per-row exact recomputes when
    ``used_fallback`` reads True.
    """
    from repro.core.algorithm import summaries_overflow
    from repro.core.backend import normalize_layout_spec

    if layouts is None and mesh is not None:
        from repro.graph.partition import build_sharded_layout

        with spans.scope(spans.LAYOUT):
            layouts = tuple(
                build_sharded_layout(state, mesh=mesh, axes=mesh_axes,
                                     weight=w, reverse=rev, semiring=s)
                for (w, rev, s) in map(normalize_layout_spec,
                                       algo.layout_specs))

    with spans.scope(spans.HOTSET):
        scores = algo.batched_selection_scores(batch_state, row_mask)
        hot, hstats = select_hot_set(
            state, deg_prev, scores, r, delta,
            active_prev=active_prev, n=n, delta_hop_cap=delta_hop_cap,
            degree_mode=degree_mode, expand_both=expand_both,
            normalize_scores=algo.normalize_selection_scores,
        )
        if cold_rows is not None:
            live_cold = cold_rows & row_mask
            any_cold = jnp.any(live_cold)
            seeds = algo.batched_cold_seeds(batch_state)
            if seeds is None:
                # no per-query seed structure (global workloads): cold-start
                # coverage is the whole active set, as before
                hot = hot | (state.node_active & any_cold)
            else:
                # seed-local delta expansion: grow the live cold rows' seed
                # union to its forward-reachability fixpoint.  Closed under
                # out-edges ⇒ no hot→cold edge ⇒ identical results to full
                # coverage, at seed-local cost.  Initial continue flag is
                # any_cold, so a wave with no cold rows runs zero sweeps.
                seed_mask = (jnp.any(seeds & live_cold[:, None], axis=0)
                             & state.node_active)

                def _grow(carry):
                    mark, _ = carry
                    nxt = _frontier_sweep(state, mark, both=False)
                    return nxt, jnp.any(nxt != mark)

                reach, _ = jax.lax.while_loop(
                    lambda c: c[1], _grow, (seed_mask, any_cold))
                hot = hot | reach
            hstats = hstats._replace(
                num_hot=jnp.sum(hot.astype(jnp.int32)))
    extra = ({} if shard_bucket_capacity is None
             else {"shard_bucket_capacity": shard_bucket_capacity})
    with spans.scope(spans.SUMMARY):
        summaries = algo.build_summaries(
            batch_state, state, hot,
            hot_node_capacity=hot_node_capacity,
            hot_edge_capacity=hot_edge_capacity,
            layouts=layouts, backend=backend, **extra,
        )
    with spans.scope(spans.SWEEP):
        new_state, iters, row_delta = algo.summarized_batched(
            batch_state, state, summaries, row_mask=row_mask,
            backend=backend)

    num_eb = summaries[0].num_eb
    for s in summaries[1:]:
        num_eb = num_eb + s.num_eb
    stats = QueryStepStats(
        num_hot=hstats.num_hot,
        num_kr=hstats.num_kr,
        num_kn=hstats.num_kn,
        num_kdelta=hstats.num_kdelta,
        num_ek=summaries[0].num_ek,
        num_eb=num_eb,
        iterations=iters,
        used_fallback=summaries_overflow(summaries),
    )
    if with_drift:
        probe_b, cold_b = _drift_from_state(
            algo, new_state, batch_state, state, hot, probe_ids,
            layouts=layouts, backend=backend)
        live = row_mask.astype(jnp.float32)
        row_drift = jnp.stack([probe_b, cold_b], axis=-1) * live[:, None]
        stats = stats._replace(drift_probe=jnp.max(probe_b * live),
                               drift_cold=jnp.max(cold_b * live))
        return new_state, stats, row_delta, row_drift
    return new_state, stats, row_delta
