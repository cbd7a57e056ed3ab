"""Traversal workloads on min/max semirings: SSSP, connected components,
and widest (most-reliable) paths.

These are the ROADMAP's long-open "needs a non-float state story" workloads,
unlocked by the semiring-generic propagation API: both are the same power
sweep as PageRank, just over a different algebra —

- **SSSP** (single-source shortest paths) is Bellman-Ford iteration on the
  ``min_plus`` semiring: ``dist(v) = min(dist(v), min_{(u,v)} dist(u) +
  len(u,v))`` with source distances pinned to 0.  Edge lengths come from a
  ``weight="length"`` :class:`~repro.core.backend.EdgeLayout` (unit lengths
  — hop counts — unless the caller bakes explicit per-edge lengths).
- **Connected components** is label-min propagation on the ``min_min``
  semiring over *int32* state: every vertex starts labeled with its own id
  and repeatedly takes the minimum label over its neighborhood.  Weak
  connectivity on the directed stream needs the symmetric closure, so the
  sweep pushes over a forward and a reverse unit layout per iteration
  (labels pass through ⊗ unchanged — ``min_min``'s ⊗-identity is +∞).
- **Widest path** (most-reliable path) is the same relaxation on the
  ``max_times`` semiring: ``width(v) = max(width(v), max_{(u,v)} width(u)
  · len(u,v))`` with sources pinned to 1.  Edge lengths act as
  multiplicative reliabilities/capacities and must be **non-negative**;
  unreached vertices hold 0 (not −∞ — a finite state vector keeps
  0-length edges from manufacturing ``−∞·0`` NaNs).  This is the sweep
  that exercises the masked-reduce *max* kernel path end to end.

Both sweeps iterate until a fixed point (no vertex changed) or the
iteration budget, and both have VeilGraph-summarized versions that restrict
the relaxation to the hot set K with *frozen cold state as a Dirichlet
boundary*: ``b_in[z]`` holds the min over z's cold in-neighbors of their
frozen distance-plus-length (SSSP) or label (CC), injected each iteration
exactly like the paper's frozen big-vertex rank mass.  Because min is
associative, commutative and reassociation-exact (no floating-point
rounding in the reduce order), a summarized sweep over ``hot == all active
vertices`` reproduces the exact sweep **bitwise**, not just approximately.

Monotonicity note: both relaxations only ever decrease state, so
warm-starting from previous distances/labels is exact under edge
*additions* (the paper's e+ stream model) — the summarized paths exploit
that.  Edge removals can strand stale-low values; the exact sweeps
therefore default to a cold start (the engine's ground-truth action).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import backend as B
from repro.core import spans
from repro.core.pagerank import SummaryBuffers
from repro.graph.graph import GraphState

#: int32 "+∞": the label of never-seen vertices and empty reduces.
LABEL_SENTINEL = jnp.iinfo(jnp.int32).max


def _fixed_point(step, x0, num_iters: int):
    """Iterate ``x ← step(x)`` until no element changes or the budget runs
    out.  The shared scaffold of every min-semiring sweep: the relaxations
    are monotone, so "nothing changed" identifies the fixed point exactly
    (no float-tolerance subtleties — min never rounds).  Returns
    ``(x, iterations_run)``."""

    def body(carry):
        i, x, _ = carry
        new_x = step(x)
        return i + 1, new_x, jnp.sum((new_x != x).astype(jnp.int32))

    def cond(carry):
        i, _, changed = carry
        return (i < num_iters) & (changed > 0)

    i, x, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), x0, jnp.int32(1)))
    return x, i


def _fixed_point_batched(step, x0, num_iters: int,
                         row_mask: Optional[jax.Array]):
    """Batched :func:`_fixed_point` over ``x0`` of shape ``[B, K]``: rows
    masked out by ``row_mask`` (bool[B], ``None`` = all on) carry their
    state unchanged and report zero change.  Iterates until NO masked row
    changes (min relaxations converge unevenly; the per-row change counts
    are the serving engine's convergence signal).  Returns
    ``(x, iterations_run, changed_rows i32[B])``."""
    batch = x0.shape[0]
    keep = (jnp.ones((batch,), bool) if row_mask is None
            else row_mask)[:, None]

    def body(carry):
        i, x, _ = carry
        new_x = jnp.where(keep, step(x), x)
        changed = jnp.sum((new_x != x).astype(jnp.int32), axis=1)
        return i + 1, new_x, changed

    def cond(carry):
        i, _, changed = carry
        return (i < num_iters) & (jnp.max(changed) > 0)

    i, x, changed = jax.lax.while_loop(
        cond, body, (jnp.int32(0), x0, jnp.ones((batch,), jnp.int32)))
    return x, i, changed


# --------------------------------------------------------------------------
# SSSP — Bellman-Ford on the min_plus semiring
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("num_iters", "backend"))
@spans.scope(spans.EXACT)
def sssp(
    state: GraphState,
    source_mask: jax.Array,
    dist0: Optional[jax.Array] = None,
    *,
    num_iters: int = 30,
    layout: Optional[B.EdgeLayout] = None,
    backend: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Bounded Bellman-Ford from the vertices in ``source_mask``.

    Returns ``(dist f32[N_cap], iterations_run)`` — ``inf`` marks
    unreachable vertices.  The loop exits as soon as an iteration changes
    no distance (a fixed point; at most the graph diameter + 1 trips).

    ``dist0`` warm-starts the relaxation (exact under edge additions —
    distances are monotone non-increasing; see the module docstring for
    the removal caveat); sources are pinned to 0 regardless.  ``layout``
    is an optional cached ``weight="length"``/``min_plus`` layout; without
    one the sweep sorts on entry (unit lengths), amortized over the
    relaxations on both backends.
    """
    backend_r = B.resolve_backend(backend)
    B.require_layout(layout, weight="length", reverse=False, who="sssp",
                     semiring="min_plus")
    inf = jnp.float32(jnp.inf)
    if dist0 is None:
        d0 = jnp.where(source_mask, 0.0, inf)
    else:
        d0 = jnp.where(source_mask, 0.0, dist0.astype(jnp.float32))

    if layout is None:
        # one sort amortized over every relaxation, on both backends (the
        # sorted gather_push skips XLA's scatter sort/unique analysis too)
        layout = B.build_layout(state, weight="length", semiring="min_plus")

    def relax(d):
        incoming = B.push(d, layout, semiring="min_plus", backend=backend_r)
        return jnp.where(source_mask, 0.0, jnp.minimum(d, incoming))

    return _fixed_point(relax, d0, num_iters)


@functools.partial(jax.jit, static_argnames=("num_iters", "backend"))
def summarized_sssp(
    summary: SummaryBuffers,
    dist_prev: jax.Array,
    source_mask: jax.Array,
    *,
    num_iters: int = 30,
    backend: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Bellman-Ford restricted to the hot set K (§3.1 on ``min_plus``).

    ``summary`` is a ``weight="length"``/``min_plus`` big-vertex summary:
    ``b_in[z] = min_{(w,z) ∈ E_B} dist_prev(w) + len(w,z)`` freezes the
    cold boundary.  Hot distances relax against E_K and ``b_in``; cold
    distances carry over unchanged.  Returns the *global* distance vector
    and the iterations run.
    """
    backend_r = B.resolve_backend(backend)
    k_cap = summary.hot_ids.shape[0]
    inf = jnp.float32(jnp.inf)
    local_valid = jnp.arange(k_cap, dtype=jnp.int32) < summary.num_hot
    src_local = jnp.where(local_valid, source_mask[summary.hot_ids], False)
    d0 = jnp.where(local_valid, dist_prev[summary.hot_ids], inf)
    d0 = jnp.where(src_local, 0.0, d0)
    layout = B.summary_layout(summary, semiring="min_plus")

    def relax(d):
        relaxed = jnp.minimum(
            d, jnp.minimum(
                B.push(d, layout, semiring="min_plus", backend=backend_r),
                summary.b_in))
        return jnp.where(local_valid, jnp.where(src_local, 0.0, relaxed), inf)

    d_loc, i = _fixed_point(relax, d0, num_iters)
    dist = dist_prev.at[summary.hot_ids].set(d_loc, mode="drop")
    return dist, i


@functools.partial(jax.jit, static_argnames=("num_iters", "backend"))
def summarized_sssp_batched(
    summary: SummaryBuffers,
    dist_prev: jax.Array,
    source_mask: jax.Array,
    *,
    num_iters: int = 30,
    row_mask: Optional[jax.Array] = None,
    backend: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Batched :func:`summarized_sssp`: B source sets, one shared summary.

    ``dist_prev``/``source_mask`` are ``[B, N]`` (per-slot source sets);
    the summary's E_K is shared while ``b_in`` may be the per-query
    ``[B, K_cap]`` form.  Each relaxation is ONE batched ``min_plus`` push
    — min is reassociation-exact, so every row is bitwise equal to its
    single-query sweep over the same summary.  ``row_mask`` (bool[B])
    freezes finished/vacant slots (see serving docs).  Returns
    ``(dist [B, N], iterations, changed_rows i32[B])``.
    """
    backend_r = B.resolve_backend(backend)
    k_cap = summary.hot_ids.shape[0]
    inf = jnp.float32(jnp.inf)
    local_valid = jnp.arange(k_cap, dtype=jnp.int32) < summary.num_hot
    src_local = jnp.where(local_valid, source_mask[:, summary.hot_ids],
                          False)
    d0 = jnp.where(local_valid, dist_prev[:, summary.hot_ids], inf)
    d0 = jnp.where(src_local, 0.0, d0)
    layout = B.summary_layout(summary, semiring="min_plus")

    def relax(d):
        relaxed = jnp.minimum(
            d, jnp.minimum(
                B.push(d, layout, semiring="min_plus", backend=backend_r),
                summary.b_in))
        return jnp.where(local_valid, jnp.where(src_local, 0.0, relaxed),
                         inf)

    d_loc, i, changed = _fixed_point_batched(relax, d0, num_iters, row_mask)
    dist = dist_prev.at[:, summary.hot_ids].set(d_loc, mode="drop")
    if row_mask is not None:
        dist = jnp.where(row_mask[:, None], dist, dist_prev)
    return dist, i, changed


# --------------------------------------------------------------------------
# Widest path — max-reliability relaxation on the max_times semiring
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("num_iters", "backend"))
@spans.scope(spans.EXACT)
def widest_path(
    state: GraphState,
    source_mask: jax.Array,
    width0: Optional[jax.Array] = None,
    *,
    num_iters: int = 30,
    layout: Optional[B.EdgeLayout] = None,
    backend: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Bounded widest-path relaxation from the vertices in ``source_mask``.

    ``max_times`` Bellman-Ford: sources are pinned to width 1 and each
    iteration takes ``width(v) = max(width(v), max_{(u,v)} width(u) ·
    len(u,v))`` — with edge lengths in (0, 1] this is the most-reliable
    path; with capacities > 1 a multiplicative throughput.  Lengths must be
    non-negative (they come from the ``weight="length"`` layout; unit
    lengths make every reachable vertex width 1).  Returns
    ``(width f32[N_cap], iterations_run)`` — 0 marks unreachable vertices.

    ``width0`` warm-starts (exact under edge additions — widths are
    monotone non-decreasing); sources re-pin to 1 regardless.
    """
    backend_r = B.resolve_backend(backend)
    B.require_layout(layout, weight="length", reverse=False,
                     who="widest_path", semiring="max_times")
    if width0 is None:
        w0 = jnp.where(source_mask, 1.0, 0.0).astype(jnp.float32)
    else:
        w0 = jnp.where(source_mask, 1.0, width0.astype(jnp.float32))

    if layout is None:
        layout = B.build_layout(state, weight="length", semiring="max_times")

    def relax(w):
        incoming = B.push(w, layout, semiring="max_times", backend=backend_r)
        return jnp.where(source_mask, 1.0, jnp.maximum(w, incoming))

    return _fixed_point(relax, w0, num_iters)


@functools.partial(jax.jit, static_argnames=("num_iters", "backend"))
def summarized_widest_path(
    summary: SummaryBuffers,
    width_prev: jax.Array,
    source_mask: jax.Array,
    *,
    num_iters: int = 30,
    backend: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Widest-path relaxation restricted to the hot set K.

    ``summary`` is a ``weight="length"``/``max_times`` big-vertex summary:
    ``b_in[z] = max_{(w,z) ∈ E_B} width_prev(w) · len(w,z)`` freezes the
    cold boundary (−∞ where z has no cold in-neighbors — harmless under
    max).  Hot widths relax against E_K and ``b_in``; cold widths carry
    over unchanged.  Returns the *global* width vector and the iterations
    run.
    """
    backend_r = B.resolve_backend(backend)
    k_cap = summary.hot_ids.shape[0]
    local_valid = jnp.arange(k_cap, dtype=jnp.int32) < summary.num_hot
    src_local = jnp.where(local_valid, source_mask[summary.hot_ids], False)
    w0 = jnp.where(local_valid, width_prev[summary.hot_ids], 0.0)
    w0 = jnp.where(src_local, 1.0, w0)
    layout = B.summary_layout(summary, semiring="max_times")

    def relax(w):
        relaxed = jnp.maximum(
            w, jnp.maximum(
                B.push(w, layout, semiring="max_times", backend=backend_r),
                summary.b_in))
        return jnp.where(local_valid, jnp.where(src_local, 1.0, relaxed), 0.0)

    w_loc, i = _fixed_point(relax, w0, num_iters)
    width = width_prev.at[summary.hot_ids].set(w_loc, mode="drop")
    return width, i


@functools.partial(jax.jit, static_argnames=("num_iters", "backend"))
def summarized_widest_path_batched(
    summary: SummaryBuffers,
    width_prev: jax.Array,
    source_mask: jax.Array,
    *,
    num_iters: int = 30,
    row_mask: Optional[jax.Array] = None,
    backend: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Batched :func:`summarized_widest_path`: B source sets, one summary.

    ``width_prev``/``source_mask`` are ``[B, N]``; each relaxation is ONE
    batched ``max_times`` push — max is reassociation-exact, so every row
    is bitwise equal to its single-query sweep over the same summary (the
    ``summarized_batched`` leg of the tuned masked-reduce max path).
    ``row_mask`` (bool[B]) freezes finished/vacant slots.  Returns
    ``(width [B, N], iterations, changed_rows i32[B])``.
    """
    backend_r = B.resolve_backend(backend)
    k_cap = summary.hot_ids.shape[0]
    local_valid = jnp.arange(k_cap, dtype=jnp.int32) < summary.num_hot
    src_local = jnp.where(local_valid, source_mask[:, summary.hot_ids],
                          False)
    w0 = jnp.where(local_valid, width_prev[:, summary.hot_ids], 0.0)
    w0 = jnp.where(src_local, 1.0, w0)
    layout = B.summary_layout(summary, semiring="max_times")

    def relax(w):
        relaxed = jnp.maximum(
            w, jnp.maximum(
                B.push(w, layout, semiring="max_times", backend=backend_r),
                summary.b_in))
        return jnp.where(local_valid, jnp.where(src_local, 1.0, relaxed),
                         0.0)

    w_loc, i, changed = _fixed_point_batched(relax, w0, num_iters, row_mask)
    width = width_prev.at[:, summary.hot_ids].set(w_loc, mode="drop")
    if row_mask is not None:
        width = jnp.where(row_mask[:, None], width, width_prev)
    return width, i, changed


# --------------------------------------------------------------------------
# Connected components — label-min propagation on the min_min semiring
# --------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("num_iters", "backend"))
@spans.scope(spans.EXACT)
def connected_components(
    state: GraphState,
    labels0: Optional[jax.Array] = None,
    *,
    num_iters: int = 30,
    fwd_layout: Optional[B.EdgeLayout] = None,
    rev_layout: Optional[B.EdgeLayout] = None,
    backend: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Weakly-connected components by label-min propagation.

    Returns ``(labels i32[N_cap], iterations_run)``: every active vertex
    ends up labeled with the minimum vertex id of its weakly-connected
    component; inactive vertices hold :data:`LABEL_SENTINEL`.  ``labels0``
    warm-starts (labels are monotone non-increasing under edge additions);
    every active vertex is re-seeded with ``min(labels0[v], v)`` so
    vertices first seen after ``labels0`` was computed join correctly.
    """
    backend_r = B.resolve_backend(backend)
    B.require_layout(fwd_layout, weight="unit", reverse=False,
                     who="connected_components fwd_layout",
                     semiring="min_min")
    B.require_layout(rev_layout, weight="unit", reverse=True,
                     who="connected_components rev_layout",
                     semiring="min_min")
    n_cap = state.node_capacity
    active = state.node_active
    ids = jnp.arange(n_cap, dtype=jnp.int32)
    if labels0 is None:
        l0 = jnp.where(active, ids, LABEL_SENTINEL)
    else:
        l0 = jnp.where(active, jnp.minimum(labels0.astype(jnp.int32), ids),
                       LABEL_SENTINEL)

    # each direction's sort is amortized over every relaxation, on both
    # backends; a caller may have either one of the two cached already
    if fwd_layout is None:
        fwd_layout = B.build_layout(state, weight="unit", semiring="min_min")
    if rev_layout is None:
        rev_layout = B.build_layout(state, weight="unit", reverse=True,
                                    semiring="min_min")

    def relax(lab):
        incoming = jnp.minimum(
            B.push(lab, fwd_layout, semiring="min_min", backend=backend_r),
            B.push(lab, rev_layout, semiring="min_min", backend=backend_r))
        return jnp.where(active, jnp.minimum(lab, incoming), LABEL_SENTINEL)

    return _fixed_point(relax, l0, num_iters)


@functools.partial(jax.jit, static_argnames=("num_iters", "backend"))
def summarized_connected_components(
    fwd: SummaryBuffers,
    rev: SummaryBuffers,
    labels_prev: jax.Array,
    *,
    num_iters: int = 30,
    backend: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Label-min propagation restricted to the hot set K.

    ``fwd``/``rev`` are ``weight="unit"``/``min_min`` summaries over the
    same hot mask (so they share ``hot_ids``); their ``b_in`` vectors
    freeze the minimum cold label reachable over one boundary edge in each
    orientation.  Hot labels relax against E_K (both directions) and the
    frozen boundary; cold labels carry over unchanged.  Returns the
    *global* label vector and the iterations run.
    """
    backend_r = B.resolve_backend(backend)
    k_cap = fwd.hot_ids.shape[0]
    local_valid = jnp.arange(k_cap, dtype=jnp.int32) < fwd.num_hot
    # re-seed with own ids: a vertex first seen after labels_prev was
    # computed is necessarily hot (new vertices always enter K_r)
    l0 = jnp.where(
        local_valid,
        jnp.minimum(labels_prev.astype(jnp.int32)[fwd.hot_ids], fwd.hot_ids),
        LABEL_SENTINEL)
    boundary = jnp.minimum(fwd.b_in, rev.b_in)
    fwd_layout = B.summary_layout(fwd, semiring="min_min")
    rev_layout = B.summary_layout(rev, semiring="min_min")

    def relax(lab):
        incoming = jnp.minimum(
            B.push(lab, fwd_layout, semiring="min_min", backend=backend_r),
            B.push(lab, rev_layout, semiring="min_min", backend=backend_r))
        relaxed = jnp.minimum(lab, jnp.minimum(incoming, boundary))
        return jnp.where(local_valid, relaxed, LABEL_SENTINEL)

    l_loc, i = _fixed_point(relax, l0, num_iters)
    labels = labels_prev.at[fwd.hot_ids].set(l_loc, mode="drop")
    return labels, i


@functools.partial(jax.jit, static_argnames=("num_iters", "backend"))
def summarized_connected_components_batched(
    fwd: SummaryBuffers,
    rev: SummaryBuffers,
    labels_prev: jax.Array,
    *,
    num_iters: int = 30,
    row_mask: Optional[jax.Array] = None,
    backend: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Batched :func:`summarized_connected_components` over ``[B, N]``
    label matrices sharing one fwd/rev summary pair.  Label-min is
    reassociation-exact, so each row matches its single-query sweep
    bitwise.  ``row_mask`` (bool[B]) freezes finished/vacant slots.
    Returns ``(labels [B, N], iterations, changed_rows i32[B])``.
    """
    backend_r = B.resolve_backend(backend)
    k_cap = fwd.hot_ids.shape[0]
    local_valid = jnp.arange(k_cap, dtype=jnp.int32) < fwd.num_hot
    l0 = jnp.where(
        local_valid,
        jnp.minimum(labels_prev.astype(jnp.int32)[:, fwd.hot_ids],
                    fwd.hot_ids),
        LABEL_SENTINEL)
    boundary = jnp.minimum(fwd.b_in, rev.b_in)
    fwd_layout = B.summary_layout(fwd, semiring="min_min")
    rev_layout = B.summary_layout(rev, semiring="min_min")

    def relax(lab):
        incoming = jnp.minimum(
            B.push(lab, fwd_layout, semiring="min_min", backend=backend_r),
            B.push(lab, rev_layout, semiring="min_min", backend=backend_r))
        relaxed = jnp.minimum(lab, jnp.minimum(incoming, boundary))
        return jnp.where(local_valid, relaxed, LABEL_SENTINEL)

    l_loc, i, changed = _fixed_point_batched(relax, l0, num_iters, row_mask)
    labels = labels_prev.at[:, fwd.hot_ids].set(l_loc, mode="drop")
    if row_mask is not None:
        labels = jnp.where(row_mask[:, None], labels, labels_prev)
    return labels, i, changed
