"""HITS (hubs & authorities) — exact and VeilGraph-summarized versions.

HITS is the second propagation workload ported onto the engine's
:class:`StreamingAlgorithm` interface (beyond-paper: the paper's five-UDF
structure and hot-vertex summarization are algorithm-agnostic; PageRank is
only its case study).  The update rules are the classic mutual recursion

    auth(v) = Σ_{(u,v) ∈ E} hub(u)          (gather along in-edges)
    hub(u)  = Σ_{(u,v) ∈ E} auth(v)         (gather along out-edges)

with L1 normalization over the active vertex set each half-iteration, which
keeps 30-iteration power sweeps inside f32 range.

Both directions run through the unified :func:`repro.core.backend.push`
primitive on the ``plus_times`` semiring (unit weights are its ⊗-identity,
1): the authority update over a forward (dst-sorted) layout, the hub update
over a reverse (src-sorted) one — on the pallas backend each half-iteration
is one destination-tiled one-hot-matmul MXU kernel call.

The summarized version runs both updates only for vertices in the hot set K,
against *two* compacted summaries built by the generalized
:func:`repro.core.pagerank.build_summary`:

- a forward summary (``weight="unit"``) whose ``b_in`` freezes the hub mass
  flowing from non-hot vertices into hot authorities, and
- a reverse summary (``weight="unit", reverse=True``) whose ``b_in`` freezes
  the authority mass that hot hubs collect from their non-hot out-neighbors.

Cold scores are carried over unchanged; per-iteration normalization uses a
global σ estimate *tracked across sweeps* (measured by exact computations,
carried in the algorithm state, anchored by the frozen cold mass) so that
with K = V (r = 1.0) the summarized sweep is the exact sweep up to f32
reassociation and at partial coverage the hot block's mass stays stationary
against the frozen boundary.
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

_EPS = 1e-12


def _l1_normalize(x: jax.Array) -> jax.Array:
    return x / jnp.maximum(jnp.sum(jnp.abs(x)), _EPS)


@functools.partial(jax.jit, static_argnames=("num_iters", "tol", "backend"))
@spans.scope(spans.EXACT)
def hits(
    state: GraphState,
    auth0: jax.Array | None = None,
    hub0: jax.Array | None = None,
    *,
    num_iters: int = 30,
    tol: float = 0.0,
    fwd_layout: Optional[B.EdgeLayout] = None,
    rev_layout: Optional[B.EdgeLayout] = None,
    backend: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Full HITS power iteration.  Returns ``(auth, hub, iterations_run,
    sigma)``.

    ``sigma`` is ``f32[2]`` — the last half-update's L1 normalizer per
    direction ``[σ_auth, σ_hub]``.  Because the iterate entering each half
    step is L1-normalized, that normalizer is the growth rate of the raw
    update, which converges to the principal singular value σ of the
    (unit-weight) adjacency operator.  Exact computations are where the
    engine *measures* σ; the summarized sweeps track it across queries and
    use it to extrapolate the frozen cold boundary's raw mass (see
    :func:`summarized_hits`).

    With ``tol > 0`` the loop exits early once the L1 change of the
    authority vector drops below ``tol``.  ``auth0``/``hub0`` warm-start the
    iteration (both converge to the principal singular vectors from any
    positive start, so warm starts only save iterations).

    ``fwd_layout``/``rev_layout`` are optional cached unit-weight layouts
    (forward/reverse orientation — see
    :func:`repro.core.backend.build_layout`); the pallas backend sorts on
    entry when they are absent.
    """
    backend_r = B.resolve_backend(backend)
    B.require_layout(fwd_layout, weight="unit", reverse=False,
                     who="hits fwd_layout")
    B.require_layout(rev_layout, weight="unit", reverse=True,
                     who="hits rev_layout")
    n_cap = state.node_capacity
    active = state.node_active
    mask = state.edge_mask()
    n_active = jnp.maximum(state.num_active_nodes().astype(jnp.float32), 1.0)

    uniform = jnp.where(active, 1.0 / n_active, 0.0)
    a0 = uniform if auth0 is None else _l1_normalize(jnp.where(active, auth0, 0.0))
    h0 = uniform if hub0 is None else _l1_normalize(jnp.where(active, hub0, 0.0))

    if backend_r == "pallas":
        if fwd_layout is None:
            fwd_layout = B.build_layout(state, weight="unit")
        if rev_layout is None:
            rev_layout = B.build_layout(state, weight="unit", reverse=True)
    edge_w = mask.astype(jnp.float32)

    def _push_fwd(x):
        if fwd_layout is None:
            return B.push_coo(x, state.src, state.dst, n_cap, weight=edge_w)
        return B.push(x, fwd_layout, backend=backend_r)

    def _push_rev(x):
        if rev_layout is None:
            return B.push_coo(x, state.dst, state.src, n_cap, weight=edge_w)
        return B.push(x, rev_layout, backend=backend_r)

    def body(carry):
        i, a, h, _, _, _ = carry
        a_raw = jnp.where(active, _push_fwd(h), 0.0)
        sig_a = jnp.sum(jnp.abs(a_raw))
        a_new = a_raw / jnp.maximum(sig_a, _EPS)
        h_raw = jnp.where(active, _push_rev(a_new), 0.0)
        sig_h = jnp.sum(jnp.abs(h_raw))
        h_new = h_raw / jnp.maximum(sig_h, _EPS)
        delta = jnp.sum(jnp.abs(a_new - a))
        return i + 1, a_new, h_new, delta, sig_a, sig_h

    def cond(carry):
        i, _, _, delta = carry[:4]
        return (i < num_iters) & (delta > tol)

    i, a, h, _, sig_a, sig_h = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0), a0, h0, jnp.float32(jnp.inf), jnp.float32(1.0),
         jnp.float32(1.0)))
    return a, h, i, jnp.stack([sig_a, sig_h])


@functools.partial(jax.jit, static_argnames=("num_iters", "tol", "backend"))
def summarized_hits(
    fwd: SummaryBuffers,
    rev: SummaryBuffers,
    auth_prev: jax.Array,
    hub_prev: jax.Array,
    sigma_prev: Optional[jax.Array] = None,
    *,
    num_iters: int = 30,
    tol: float = 0.0,
    backend: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """HITS power iteration restricted to the hot set K.

    ``fwd``/``rev`` are summaries over the same hot mask (so they share
    ``hot_ids``); ``fwd.b_in`` holds the frozen cold→hot hub contribution to
    authorities and ``rev.b_in`` the frozen hot→cold authority contribution
    to hubs.

    Unlike PageRank, HITS is an eigenvector problem: the exact sweep's
    normalization divides by the global raw-update mass, whose growth rate
    converges to the principal singular value σ.  The restricted sweep
    treats cold scores as a Dirichlet boundary (frozen, injected through
    ``b_in``) and normalizes each half-update by a global σ estimate
    anchored to the *tracked* value ``sigma_prev`` (``f32[2] = [σ_a, σ_h]``,
    measured by the last exact computation or returned by the last
    summarized sweep — see :func:`hits`)::

        σ̂ = (Σ|raw_hot| + σ_tracked·cold) / (Σ|prev_hot| + cold)

    with ``cold = Σ|prev_global| − Σ|prev_hot|``.  The cold block never
    recomputes its raw update, but at the global fixed point that raw mass
    is exactly ``σ·cold`` — extrapolating it with the tracked σ makes the
    restricted iteration's equilibrium normalizer *pin to* ``σ_tracked``
    whenever cold mass is present, so the hot block's L1 mass is stationary
    against the boundary instead of drifting (the pre-fix estimator used
    the hot block's own growth rate alone, which pinned the hot/cold mass
    ratio even when updates genuinely shifted mass into or out of K; a
    naive ``(Σ|raw|+cold)/(Σ|prev|+cold)`` blend systematically
    underestimates σ and drifts linearly).  With K = V the cold mass is
    zero and σ̂ reduces to the exact sweep's normalization, so the r = 1.0
    sweep is still the exact sweep up to f32 reassociation — and a cold
    start with an untrusted ``sigma_prev`` under a full-coverage hot set is
    still properly normalized.  A degenerate half-update (no internal
    edges, no boundary inflow) keeps the previous scores and estimate.

    Returns the updated *global* ``(auth, hub, iterations_run, sigma)``
    where ``sigma`` is the sweep's final per-direction σ̂ — the value to
    track into the next sweep.  ``sigma_prev=None`` starts the track at 1.

    Each half-iteration is one :func:`repro.core.backend.push` over its
    summary's pre-sorted E_K layout.
    """
    backend_r = B.resolve_backend(backend)
    k_cap = fwd.hot_ids.shape[0]
    local_valid = jnp.arange(k_cap, dtype=jnp.int32) < fwd.num_hot

    sig0 = (jnp.ones((2,), jnp.float32) if sigma_prev is None
            else jnp.asarray(sigma_prev, jnp.float32))
    a0 = jnp.where(local_valid, auth_prev[fwd.hot_ids], 0.0)
    h0 = jnp.where(local_valid, hub_prev[fwd.hot_ids], 0.0)
    # frozen cold L1 mass per direction — constant across the sweep (cold
    # scores are the Dirichlet boundary), computed once outside the loop
    cold_a = jnp.maximum(
        jnp.sum(jnp.abs(auth_prev)) - jnp.sum(jnp.abs(a0)), 0.0)
    cold_h = jnp.maximum(
        jnp.sum(jnp.abs(hub_prev)) - jnp.sum(jnp.abs(h0)), 0.0)
    fwd_layout = B.summary_layout(fwd)
    rev_layout = B.summary_layout(rev)

    def half_step(prev, raw, cold, anchor, sigma_last):
        """Normalize a raw half-update by the anchored global-σ estimate."""
        mass = jnp.sum(jnp.abs(raw)) + cold
        growth = ((jnp.sum(jnp.abs(raw)) + anchor * cold)
                  / jnp.maximum(jnp.sum(jnp.abs(prev)) + cold, _EPS))
        # degenerate hot blocks (no internal edges, no boundary inflow)
        # keep their previous scores and carry the last well-defined σ̂
        ok = mass > _EPS
        sigma = jnp.where(ok, growth, sigma_last)
        return (jnp.where(ok, raw / jnp.maximum(sigma, _EPS), prev), sigma)

    def body(carry):
        i, a, h, _, sig_a, sig_h = carry
        a_in = B.push(h, fwd_layout, backend=backend_r)
        a_new, sig_a = half_step(
            a, jnp.where(local_valid, a_in + fwd.b_in, 0.0), cold_a,
            sig0[0], sig_a)
        h_in = B.push(a_new, rev_layout, backend=backend_r)
        h_new, sig_h = half_step(
            h, jnp.where(local_valid, h_in + rev.b_in, 0.0), cold_h,
            sig0[1], sig_h)
        delta = jnp.sum(jnp.abs(a_new - a))
        return i + 1, a_new, h_new, delta, sig_a, sig_h

    def cond(carry):
        i, _, _, delta = carry[:4]
        return (i < num_iters) & (delta > tol)

    i, a_loc, h_loc, _, sig_a, sig_h = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0), a0, h0, jnp.float32(jnp.inf), sig0[0], sig0[1]))

    auth = auth_prev.at[fwd.hot_ids].set(a_loc, mode="drop")
    hub = hub_prev.at[fwd.hot_ids].set(h_loc, mode="drop")
    return auth, hub, i, jnp.stack([sig_a, sig_h])


@functools.partial(jax.jit, static_argnames=("num_iters", "tol", "backend"))
def summarized_hits_batched(
    fwd: SummaryBuffers,
    rev: SummaryBuffers,
    auth_prev: jax.Array,
    hub_prev: jax.Array,
    sigma_prev: Optional[jax.Array] = None,
    *,
    num_iters: int = 30,
    tol: float = 0.0,
    row_mask: Optional[jax.Array] = None,
    backend: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Batched :func:`summarized_hits`: ``[B, N]`` auth/hub matrices
    sharing one fwd/rev summary pair, with the per-row anchored-σ
    normalization of the single-query sweep (cold mass and σ̂ are ``[B]``
    vectors; ``sigma_prev`` is the ``[B, 2]`` tracked anchor, None → 1s).
    ``row_mask`` (bool[B]) freezes finished/vacant slots — their scores
    *and* their tracked σ.  Returns ``(auth [B, N], hub [B, N],
    iterations, row_delta [B], sigma [B, 2])``.
    """
    backend_r = B.resolve_backend(backend)
    batch = auth_prev.shape[0]
    k_cap = fwd.hot_ids.shape[0]
    local_valid = jnp.arange(k_cap, dtype=jnp.int32) < fwd.num_hot

    sig0 = (jnp.ones((batch, 2), jnp.float32) if sigma_prev is None
            else jnp.asarray(sigma_prev, jnp.float32))
    a0 = jnp.where(local_valid, auth_prev[:, fwd.hot_ids], 0.0)
    h0 = jnp.where(local_valid, hub_prev[:, fwd.hot_ids], 0.0)
    cold_a = jnp.maximum(
        jnp.sum(jnp.abs(auth_prev), axis=1) - jnp.sum(jnp.abs(a0), axis=1),
        0.0)
    cold_h = jnp.maximum(
        jnp.sum(jnp.abs(hub_prev), axis=1) - jnp.sum(jnp.abs(h0), axis=1),
        0.0)
    live = (jnp.ones((batch,), bool) if row_mask is None else row_mask)
    keep = live[:, None]
    fwd_layout = B.summary_layout(fwd)
    rev_layout = B.summary_layout(rev)

    def half_step(prev, raw, cold, anchor, sigma_last):
        mass = jnp.sum(jnp.abs(raw), axis=1) + cold
        growth = ((jnp.sum(jnp.abs(raw), axis=1) + anchor * cold)
                  / jnp.maximum(jnp.sum(jnp.abs(prev), axis=1) + cold, _EPS))
        ok = (mass > _EPS) & live
        sigma = jnp.where(ok, growth, sigma_last)
        scaled = jnp.where(ok[:, None],
                           raw / jnp.maximum(sigma, _EPS)[:, None], prev)
        return jnp.where(keep, scaled, prev), sigma

    def body(carry):
        i, a, h, _, sig_a, sig_h = carry
        a_in = B.push(h, fwd_layout, backend=backend_r)
        a_new, sig_a = half_step(
            a, jnp.where(local_valid, a_in + fwd.b_in, 0.0), cold_a,
            sig0[:, 0], sig_a)
        h_in = B.push(a_new, rev_layout, backend=backend_r)
        h_new, sig_h = half_step(
            h, jnp.where(local_valid, h_in + rev.b_in, 0.0), cold_h,
            sig0[:, 1], sig_h)
        delta = jnp.sum(jnp.abs(a_new - a), axis=1)
        return i + 1, a_new, h_new, delta, sig_a, sig_h

    def cond(carry):
        i, _, _, delta = carry[:4]
        return (i < num_iters) & (jnp.max(delta) > tol)

    i, a_loc, h_loc, delta, sig_a, sig_h = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0), a0, h0, jnp.full((batch,), jnp.inf, jnp.float32),
         sig0[:, 0], sig0[:, 1]))

    auth = auth_prev.at[:, fwd.hot_ids].set(a_loc, mode="drop")
    hub = hub_prev.at[:, fwd.hot_ids].set(h_loc, mode="drop")
    auth = jnp.where(keep, auth, auth_prev)
    hub = jnp.where(keep, hub, hub_prev)
    return auth, hub, i, delta, jnp.stack([sig_a, sig_h], axis=1)
