"""Katz centrality — exact and VeilGraph-summarized versions.

Katz scores count attenuated walks: ``c = Σ_k α^k (Aᵀ)^k · β·1``, computed
by the fixed-point iteration

    c(v) = β + α · Σ_{(u,v) ∈ E} c(u)

— the same sum-of-products power sweep as PageRank, but over *unit* edge
weights (no out-degree normalization) with the teleport term replaced by
the constant attraction β.  The iteration is a contraction (and the fixed
point exists) whenever ``α < 1/σ_max(A)``; keep α small for hubby graphs.

The summarized version is structurally the summarized PageRank sweep: hot
vertices iterate over the compacted E_K buffer with the *frozen* cold
contribution ``b_in[z] = Σ_{(w,z) ∈ E_B} c_prev(w)`` injected each
iteration, cold scores carried over unchanged.  Both sweeps route through
the unified :func:`repro.core.backend.push` primitive on the ``plus_times``
semiring (the one-hot-matmul MXU fast path).
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


@functools.partial(
    jax.jit,
    static_argnames=("alpha", "beta", "num_iters", "tol", "backend"),
)
@spans.scope(spans.EXACT)
def katz(
    state: GraphState,
    init: Optional[jax.Array] = None,
    *,
    alpha: float = 0.05,
    beta: float = 1.0,
    num_iters: int = 30,
    tol: float = 0.0,
    layout: Optional[B.EdgeLayout] = None,
    backend: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Full Katz power iteration.  Returns ``(katz f32[N_cap], iterations)``.

    ``init`` warm-starts the iteration (the sweep is a contraction for
    admissible α, so warm starts only save iterations); with ``tol > 0``
    the loop exits early once ``‖c_t − c_{t−1}‖₁ < tol``.  ``layout`` is an
    optional cached forward ``weight="unit"`` / ``plus_times`` layout;
    without one the sweep sorts on entry, amortized over the iterations on
    both backends.
    """
    backend_r = B.resolve_backend(backend)
    B.require_layout(layout, weight="unit", reverse=False, who="katz")
    active = state.node_active
    c0 = jnp.where(active, beta if init is None else init, 0.0).astype(
        jnp.float32)

    if layout is None:
        # one sort amortized over every iteration, on both backends (the
        # sorted gather_push skips XLA's scatter sort/unique analysis too)
        layout = B.build_layout(state, weight="unit")

    def body(carry):
        i, c, _ = carry
        incoming = B.push(c, layout, backend=backend_r)
        new_c = jnp.where(active, beta + alpha * incoming, 0.0)
        delta = jnp.sum(jnp.abs(new_c - c))
        return i + 1, new_c, delta

    def cond(carry):
        i, _, delta = carry
        return (i < num_iters) & (delta > tol)

    i, c, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), c0, jnp.float32(jnp.inf)))
    return c, i


@functools.partial(
    jax.jit,
    static_argnames=("alpha", "beta", "num_iters", "tol", "backend"),
)
def summarized_katz(
    summary: SummaryBuffers,
    katz_prev: jax.Array,
    *,
    alpha: float = 0.05,
    beta: float = 1.0,
    num_iters: int = 30,
    tol: float = 0.0,
    backend: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Katz power iteration restricted to the hot set K.

    ``summary`` is a ``weight="unit"`` big-vertex summary frozen from the
    previous Katz vector; per iteration every hot vertex z updates

        c(z) = β + α · ( Σ_{(u,z) ∈ E_K} c(u) + b_in(z) )

    with cold scores carried over unchanged.  Returns the *global* score
    vector and the iterations run.
    """
    backend_r = B.resolve_backend(backend)
    k_cap = summary.hot_ids.shape[0]
    local_valid = jnp.arange(k_cap, dtype=jnp.int32) < summary.num_hot
    c0 = jnp.where(local_valid, katz_prev[summary.hot_ids], 0.0)
    layout = B.summary_layout(summary)

    def body(carry):
        i, c, _ = carry
        incoming = B.push(c, layout, backend=backend_r)
        new_c = jnp.where(
            local_valid, beta + alpha * (incoming + summary.b_in), 0.0)
        delta = jnp.sum(jnp.abs(new_c - c))
        return i + 1, new_c, delta

    def cond(carry):
        i, _, delta = carry
        return (i < num_iters) & (delta > tol)

    i, c_loc, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), c0, jnp.float32(jnp.inf)))
    katz_v = katz_prev.at[summary.hot_ids].set(c_loc, mode="drop")
    return katz_v, i


@functools.partial(
    jax.jit,
    static_argnames=("alpha", "beta", "num_iters", "tol", "backend"),
)
def summarized_katz_batched(
    summary: SummaryBuffers,
    katz_prev: jax.Array,
    *,
    alpha: float = 0.05,
    beta: float = 1.0,
    num_iters: int = 30,
    tol: float = 0.0,
    row_mask: Optional[jax.Array] = None,
    backend: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Batched :func:`summarized_katz`: a ``[B, N]`` score matrix sharing
    one summary, relaxed with one batched push per iteration.  ``row_mask``
    (bool[B]) freezes finished/vacant slots — masked rows carry through
    unchanged and report zero delta.  Returns
    ``(katz [B, N], iterations, row_delta f32[B])``.
    """
    backend_r = B.resolve_backend(backend)
    batch = katz_prev.shape[0]
    k_cap = summary.hot_ids.shape[0]
    local_valid = jnp.arange(k_cap, dtype=jnp.int32) < summary.num_hot
    c0 = jnp.where(local_valid, katz_prev[:, summary.hot_ids], 0.0)
    keep = (jnp.ones((batch,), bool) if row_mask is None
            else row_mask)[:, None]
    layout = B.summary_layout(summary)

    def body(carry):
        i, c, _ = carry
        incoming = B.push(c, layout, backend=backend_r)
        new_c = jnp.where(
            local_valid, beta + alpha * (incoming + summary.b_in), 0.0)
        new_c = jnp.where(keep, new_c, c)
        delta = jnp.sum(jnp.abs(new_c - c), axis=1)
        return i + 1, new_c, delta

    def cond(carry):
        i, _, delta = carry
        return (i < num_iters) & (jnp.max(delta) > tol)

    i, c_loc, delta = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0), c0, jnp.full((batch,), jnp.inf, jnp.float32)))
    katz_v = katz_prev.at[:, summary.hot_ids].set(c_loc, mode="drop")
    katz_v = jnp.where(keep, katz_v, katz_prev)
    return katz_v, i, delta
