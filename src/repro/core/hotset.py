"""Hot-vertex selection: K = K_r ∪ K_n ∪ K_Δ  (paper §3.2, Eqs. 2–5).

All three stages are expressed as dense masked edge sweeps (the TPU-native
form of the paper's vertex-centric BFS): a frontier expansion is one
scatter-or along the edge list, so K_n costs n sweeps and K_Δ costs at most
``delta_hop_cap`` sweeps.  Selection runs once per query and is O(E) with
tiny constants; the savings come from the power iterations afterwards
running only on the compacted hot subgraph.

Faithfulness notes
------------------
- Eq. 2 uses the vertex degree d_t(u) = |N_t(u)| (out-neighbors); new
  vertices (no previous degree) are always included (paper footnote 2).
- Eq. 3 expands along directed edges u→v from K_r, n hops.
- Eqs. 4–5: candidates v beyond K_r ∪ K_n are included while their hop
  distance from K_n stays below f_Δ(v) = log(n + d̄·v_s/(Δ·d_t(v))) / log d̄.
  f_Δ is clamped to [0, delta_hop_cap]; d̄ is the average degree over the
  currently active vertices.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import spans
from repro.graph.graph import GraphState


class HotSetParams(NamedTuple):
    """The paper's hot-set model knobs (r, n, Δ) bundled as a pytree —
    r and Δ are runtime scalars, n is a static hop count."""

    r: jax.Array       # update-ratio threshold (f32 scalar)
    n: int             # neighborhood diameter (static: 0, 1, 2, …)
    delta: jax.Array   # Δ score-dilution bound (f32 scalar)


class HotSetStats(NamedTuple):
    """Device-side sizes of the three selection stages (K_r, K_n, K_Δ)
    and their union |K| — one host transfer per query."""

    num_kr: jax.Array
    num_kn: jax.Array
    num_kdelta: jax.Array
    num_hot: jax.Array


def _frontier_sweep(state: GraphState, mark: jax.Array, *, both: bool) -> jax.Array:
    """One BFS sweep: returns mask of vertices reachable in <=1 hop from mark."""
    mask = state.edge_mask()
    hit_src = mask & mark[state.src]
    reach = jnp.zeros_like(mark).at[state.dst].max(hit_src)
    if both:
        hit_dst = mask & mark[state.dst]
        reach = reach.at[state.src].max(hit_dst)
    return mark | reach


@functools.partial(
    jax.jit,
    static_argnames=("n", "delta_hop_cap", "degree_mode", "expand_both",
                     "normalize_scores"),
)
def select_hot_set(
    state: GraphState,
    deg_prev: jax.Array,
    ranks_prev: jax.Array,
    r: jax.Array,
    delta: jax.Array,
    *,
    active_prev: Optional[jax.Array] = None,
    n: int = 1,
    delta_hop_cap: int = 4,
    degree_mode: str = "out",
    expand_both: bool = False,
    normalize_scores: bool = False,
) -> Tuple[jax.Array, HotSetStats]:
    """Compute the hot-vertex mask K over the current graph.

    ``deg_prev`` is the degree snapshot taken at the previous measurement
    point t-1 (same ``degree_mode``); ``active_prev`` the activity snapshot
    (a vertex first seen after t-1 has no previous rank and is always in K_r
    — paper footnote 2).  Without ``active_prev``, deg_prev>0 is the proxy
    (wrong for pre-existing sinks under degree_mode="out").

    ``normalize_scores`` rescales v_s to mean 1 over the active set before
    the Δ-dilution bound.  Eqs. 4-5 calibrate Δ against Gelly-style
    PageRank, whose scores average ≈ 1 per vertex; algorithms with
    L1-normalized score vectors (personalized PageRank, HITS) opt in so the
    same Δ values keep the paper's semantics.  Off by default — the raw
    paper formula.

    Returns (bool[N_cap] mask, stats).
    """
    with spans.scope(spans.HOTSET_KR):
        if degree_mode == "out":
            deg_now = state.out_deg
        elif degree_mode == "in":
            deg_now = state.in_deg
        elif degree_mode == "total":
            deg_now = state.out_deg + state.in_deg
        else:
            raise ValueError(f"degree_mode={degree_mode}")

        active = state.node_active
        deg_now_f = deg_now.astype(jnp.float32)
        deg_prev_f = deg_prev.astype(jnp.float32)

        # ---- Eq. 2: K_r --------------------------------------------------
        if active_prev is None:
            was_seen = deg_prev > 0
        else:
            was_seen = active_prev
        is_new = active & ~was_seen
        # Zero-prior-degree audit: the paper's relative-degree-change test
        # divides by deg_prev, which is 0 for brand-new vertices and for
        # pre-existing zero-degree ones (sinks under out-degree mode).
        # Both paths are deterministic and division-free here:
        #  - brand-new vertices (active now, unseen before) are
        #    unconditionally hot via `is_new`, regardless of r — a vertex
        #    with no prior result has nothing valid to freeze;
        #  - the ratio clamps its denominator to >= 1, so it is always
        #    finite (never NaN/inf) and only *consulted* where deg_prev > 0
        #    — the deg_prev == 0 branch of `changed` triggers purely on
        #    gaining degree, at any r including r = inf.
        ratio = jnp.abs(deg_now_f / jnp.maximum(deg_prev_f, 1.0) - 1.0)
        changed = jnp.where(deg_prev > 0, ratio > r, deg_now > 0)
        k_r = active & (is_new | (was_seen & changed))

    with spans.scope(spans.HOTSET_KN):
        # ---- Eq. 3: K_n — n-hop directed expansion around K_r -------------
        k_rn = k_r
        for _ in range(n):
            k_rn = _frontier_sweep(state, k_rn, both=expand_both)
        k_n_only = k_rn & ~k_r

    with spans.scope(spans.HOTSET_KDELTA):
        # ---- Eqs. 4-5: K_Δ — score-dilution-bounded expansion -------------
        # f_Δ(v) = log(n + d̄·v_s / (Δ·d_t(v))) / log(d̄), clamped to >= 0.
        n_active = jnp.maximum(
            state.num_active_nodes().astype(jnp.float32), 1.0)
        total_deg = jnp.sum(jnp.where(active, deg_now_f, 0.0))
        d_bar = jnp.maximum(total_deg / n_active, 1.0 + 1e-6)
        v_s = jnp.maximum(ranks_prev, 0.0)
        if normalize_scores:
            total_score = jnp.sum(jnp.where(active, v_s, 0.0))
            v_s = v_s * (n_active / jnp.maximum(total_score, 1e-30))
        arg = n + d_bar * v_s / (jnp.maximum(delta, 1e-9)
                                 * jnp.maximum(deg_now_f, 1.0))
        f_delta = jnp.log(jnp.maximum(arg, 1e-9)) / jnp.log(d_bar)
        f_delta = jnp.clip(f_delta, 0.0, float(delta_hop_cap))

        # hop-distance relaxation from K_r ∪ K_n, capped at delta_hop_cap
        # sweeps; a candidate v joins when its distance h satisfies
        # h <= f_Δ(v).  The loop exits early once a sweep adds nothing
        # (typical after 1-2 hops), saving O(E) passes per query.
        def delta_body(carry):
            h, k_delta, frontier, _ = carry
            nxt = (_frontier_sweep(state, frontier, both=expand_both)
                   & ~frontier)
            joined = (nxt & (f_delta >= h.astype(jnp.float32)) & ~k_rn
                      & ~k_delta)
            grew = jnp.any(joined)
            # expansion continues only through vertices that actually joined
            return h + 1, k_delta | joined, frontier | joined, grew

        def delta_cond(carry):
            h, _, _, grew = carry
            return (h <= delta_hop_cap) & grew

        _, k_delta, _, _ = jax.lax.while_loop(
            delta_cond,
            delta_body,
            (jnp.int32(1), jnp.zeros_like(k_rn), k_rn, jnp.bool_(True)),
        )

    hot = (k_r | k_rn | k_delta) & active
    stats = HotSetStats(
        num_kr=jnp.sum(k_r.astype(jnp.int32)),
        num_kn=jnp.sum(k_n_only.astype(jnp.int32)),
        num_kdelta=jnp.sum(k_delta.astype(jnp.int32)),
        num_hot=jnp.sum(hot.astype(jnp.int32)),
    )
    return hot, stats
