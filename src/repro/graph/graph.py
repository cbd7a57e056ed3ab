"""Device-resident streaming graph state.

The TPU adaptation of VeilGraph's mutable Flink graph: a padded COO edge
buffer with *static* capacities.  Streaming edge additions/removals are
functional scatters into the preallocated buffers (the graph analogue of a
KV cache), so every update and every query step is jit-compatible.

Layout
------
- ``src``/``dst``: int32[edge_capacity] COO endpoints.  Slots at index >=
  ``num_edges`` are padding; padding slots hold ``0`` and are excluded by
  ``edge_mask()``.
- ``edge_alive``: bool[edge_capacity] — False for removed edges (removals are
  tombstones; the slot is not reused until ``compact`` is called host-side).
- ``out_deg``/``in_deg``: int32[node_capacity], maintained incrementally.
- ``node_active``: a node is active once it has appeared in any edge.

All graph-level reductions mask with ``edge_mask`` so padding and tombstones
never contribute.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class GraphState(NamedTuple):
    """Padded COO graph; a JAX pytree (NamedTuple of arrays)."""

    src: jax.Array          # int32[E_cap]
    dst: jax.Array          # int32[E_cap]
    edge_alive: jax.Array   # bool[E_cap]  (False => tombstoned removal)
    num_edges: jax.Array    # int32 scalar: high-water mark of used slots
    out_deg: jax.Array      # int32[N_cap]
    in_deg: jax.Array       # int32[N_cap]
    node_active: jax.Array  # bool[N_cap]
    #: optional f32[E_cap] per-edge length/weight in *slot* order (streamed
    #: in through add_edges); ``None`` until any edge carries a weight.
    #: Consumed by ``weight="length"`` layouts, which default to it when no
    #: explicit ``lengths=`` override is given.  Unset slots hold 1.0.
    edge_len: Optional[jax.Array] = None

    # ---- static-shape helpers -------------------------------------------
    @property
    def node_capacity(self) -> int:
        """Static node-space size (vertex ids are < node_capacity)."""
        return self.out_deg.shape[0]

    @property
    def edge_capacity(self) -> int:
        """Static COO buffer size (live + tombstoned + padding slots)."""
        return self.src.shape[0]

    def edge_mask(self) -> jax.Array:
        """bool[E_cap]: True for live (non-padding, non-tombstone) edges."""
        in_use = jnp.arange(self.edge_capacity, dtype=jnp.int32) < self.num_edges
        return in_use & self.edge_alive

    def num_live_edges(self) -> jax.Array:
        """int32 scalar: edges that are in use and not tombstoned."""
        return jnp.sum(self.edge_mask().astype(jnp.int32))

    def num_active_nodes(self) -> jax.Array:
        """int32 scalar: vertices that have appeared in any edge."""
        return jnp.sum(self.node_active.astype(jnp.int32))

    def total_deg(self) -> jax.Array:
        """int32[N_cap]: out-degree + in-degree per vertex."""
        return self.out_deg + self.in_deg


def empty(node_capacity: int, edge_capacity: int) -> GraphState:
    """An empty graph with the given static capacities."""
    return GraphState(
        src=jnp.zeros((edge_capacity,), jnp.int32),
        dst=jnp.zeros((edge_capacity,), jnp.int32),
        edge_alive=jnp.ones((edge_capacity,), bool),
        num_edges=jnp.zeros((), jnp.int32),
        out_deg=jnp.zeros((node_capacity,), jnp.int32),
        in_deg=jnp.zeros((node_capacity,), jnp.int32),
        node_active=jnp.zeros((node_capacity,), bool),
    )


def from_edges(
    src: np.ndarray,
    dst: np.ndarray,
    node_capacity: int,
    edge_capacity: int,
    weights: Optional[np.ndarray] = None,
) -> GraphState:
    """Build a GraphState from host edge arrays (initial graph G).

    ``weights`` optionally attaches a per-edge length column (f32, same
    length as ``src``) consumed by ``weight="length"`` layouts; absent
    edges/slots default to 1.0.
    """
    src = np.asarray(src, np.int32)
    dst = np.asarray(dst, np.int32)
    if src.shape != dst.shape or src.ndim != 1:
        raise ValueError("src/dst must be 1-D arrays of equal length")
    m = src.shape[0]
    if m > edge_capacity:
        raise ValueError(f"{m} edges exceed edge_capacity={edge_capacity}")
    if m and (src.max() >= node_capacity or dst.max() >= node_capacity):
        raise ValueError("node id exceeds node_capacity")
    edge_len = None
    if weights is not None:
        weights = np.asarray(weights, np.float32)
        if weights.shape != src.shape:
            raise ValueError("weights must align with src/dst")
        len_pad = np.ones((edge_capacity,), np.float32)
        len_pad[:m] = weights
        edge_len = jnp.asarray(len_pad)

    src_pad = np.zeros((edge_capacity,), np.int32)
    dst_pad = np.zeros((edge_capacity,), np.int32)
    src_pad[:m] = src
    dst_pad[:m] = dst
    out_deg = np.zeros((node_capacity,), np.int32)
    in_deg = np.zeros((node_capacity,), np.int32)
    np.add.at(out_deg, src, 1)
    np.add.at(in_deg, dst, 1)
    node_active = (out_deg + in_deg) > 0
    return GraphState(
        src=jnp.asarray(src_pad),
        dst=jnp.asarray(dst_pad),
        edge_alive=jnp.ones((edge_capacity,), bool),
        num_edges=jnp.asarray(m, jnp.int32),
        out_deg=jnp.asarray(out_deg),
        in_deg=jnp.asarray(in_deg),
        node_active=jnp.asarray(node_active),
        edge_len=edge_len,
    )


def _add_edges_impl(state: GraphState, new_src: jax.Array, new_dst: jax.Array,
                    new_len: Optional[jax.Array] = None) -> GraphState:
    k = new_src.shape[0]
    e_cap = state.edge_capacity
    base = state.num_edges
    slots = base + jnp.arange(k, dtype=jnp.int32)
    ok = slots < e_cap
    # slots past capacity scatter out of range and are dropped, so they
    # cannot write over the last slot in range
    put = lambda buf, vals: buf.at[jnp.where(ok, slots, e_cap)].set(
        vals, mode="drop")

    src = put(state.src, new_src)
    dst = put(state.dst, new_dst)
    alive = put(state.edge_alive, True)
    edge_len = state.edge_len
    if new_len is not None:
        if edge_len is None:
            edge_len = jnp.ones((e_cap,), jnp.float32)
        edge_len = put(edge_len, new_len.astype(jnp.float32))
    elif edge_len is not None:
        # unweighted chunk into a weighted graph: slots default to 1.0
        edge_len = put(edge_len, 1.0)

    one = jnp.where(ok, 1, 0).astype(jnp.int32)
    out_deg = state.out_deg.at[new_src].add(one)
    in_deg = state.in_deg.at[new_dst].add(one)
    n = state.node_capacity
    node_active = state.node_active.at[jnp.where(ok, new_src, n)].set(
        True, mode="drop")
    node_active = node_active.at[jnp.where(ok, new_dst, n)].set(
        True, mode="drop")

    num_edges = jnp.minimum(base + k, e_cap).astype(jnp.int32)
    return GraphState(src, dst, alive, num_edges, out_deg, in_deg,
                      node_active, edge_len)


#: Append a fixed-size chunk of edges.
#:
#: ``new_src``/``new_dst`` have a *static* chunk length (the stream chunk
#: size), so this compiles once per chunk size.  Slots past
#: ``edge_capacity`` are silently dropped (callers check ``has_capacity``
#: first; the engine's BeforeUpdates stage enforces it).
#:
#: ``new_len`` optionally streams a per-edge length column alongside the
#: endpoints (f32[k]); the first weighted chunk materializes ``edge_len``
#: (previous slots default to 1.0), and later unweighted chunks leave
#: their slots at 1.0.
#:
#: Donates the input state: the previous epoch's buffers are reused in
#: place, so the caller must not hold references to them.
add_edges = functools.partial(jax.jit, donate_argnums=(0,))(_add_edges_impl)

#: Non-donating ``add_edges``: same program, but the input state's buffers
#: survive the call.  The async rebuild pipeline applies updates with this
#: variant so the served ``EpochSnapshot`` (which aliases the pre-update
#: buffers) stays immutable while the live state advances past it.
add_edges_preserving = jax.jit(_add_edges_impl)


def _remove_edges_by_slot_impl(state: GraphState, slots: jax.Array) -> GraphState:
    e_cap = state.edge_capacity
    # a batch may name one slot twice (find_edge_slots maps repeated
    # requests for one edge to its slot): count each slot once
    slots = jnp.sort(slots)
    first = jnp.ones(slots.shape, bool).at[1:].set(slots[1:] != slots[:-1])
    slots_c = jnp.clip(slots, 0, e_cap - 1)
    hit = (first & (slots >= 0) & (slots < state.num_edges)
           & state.edge_alive[slots_c])
    alive = state.edge_alive.at[jnp.where(hit, slots_c, e_cap)].set(
        False, mode="drop")
    dec = hit.astype(jnp.int32)
    out_deg = state.out_deg.at[state.src[slots_c]].add(-dec)
    in_deg = state.in_deg.at[state.dst[slots_c]].add(-dec)
    return state._replace(edge_alive=alive, out_deg=out_deg, in_deg=in_deg)


#: Tombstone the edges stored at ``slots`` (int32[k]); -1 entries are
#: no-ops.  Donates the input state (buffers reused in place).
#:
#: Beyond-paper: the paper restricts its evaluation to edge additions (e+)
#: and leaves removals to future work; the substrate supports them so the
#: engine's stream model is complete.
remove_edges_by_slot = functools.partial(
    jax.jit, donate_argnums=(0,))(_remove_edges_by_slot_impl)

#: Non-donating ``remove_edges_by_slot`` — see ``add_edges_preserving``;
#: used by the async pipeline so served snapshots keep their buffers.
remove_edges_by_slot_preserving = jax.jit(_remove_edges_by_slot_impl)


def find_edge_slots(state: GraphState, src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    """Host-side lookup of buffer slots holding the given edges (-1 if absent)."""
    s = np.asarray(jax.device_get(state.src))
    d = np.asarray(jax.device_get(state.dst))
    alive = np.asarray(jax.device_get(state.edge_mask()))
    key = s.astype(np.int64) * (2**32) + d.astype(np.int64)
    lut = {}
    for i in np.nonzero(alive)[0]:
        lut.setdefault(key[i], i)
    q = np.asarray(src, np.int64) * (2**32) + np.asarray(dst, np.int64)
    return np.asarray([lut.get(k, -1) for k in q], np.int32)


@jax.jit
def recompute_degrees(state: GraphState) -> Tuple[jax.Array, jax.Array]:
    """O(E) degree recomputation — the oracle for the incremental counters."""
    m = state.edge_mask().astype(jnp.int32)
    n = state.node_capacity
    out_deg = jax.ops.segment_sum(m, state.src, num_segments=n)
    in_deg = jax.ops.segment_sum(m, state.dst, num_segments=n)
    return out_deg.astype(jnp.int32), in_deg.astype(jnp.int32)


@jax.jit
def inv_out_degree(state: GraphState) -> jax.Array:
    """f32[N_cap]: 1/d_out(u) with 0 for dangling/inactive nodes."""
    d = state.out_deg.astype(jnp.float32)
    return jnp.where(d > 0, 1.0 / jnp.maximum(d, 1.0), 0.0)


def compact(state: GraphState) -> GraphState:
    """Host-side rebuild dropping tombstones (reclaims removed-edge slots)."""
    mask = np.asarray(jax.device_get(state.edge_mask()))
    s = np.asarray(jax.device_get(state.src))[mask]
    d = np.asarray(jax.device_get(state.dst))[mask]
    w = None
    if state.edge_len is not None:
        w = np.asarray(jax.device_get(state.edge_len))[mask]
    return from_edges(s, d, state.node_capacity, state.edge_capacity,
                      weights=w)


def to_networkx(state: GraphState):
    """Debug/test helper: export live edges to a networkx DiGraph."""
    import networkx as nx

    mask = np.asarray(jax.device_get(state.edge_mask()))
    s = np.asarray(jax.device_get(state.src))[mask]
    d = np.asarray(jax.device_get(state.dst))[mask]
    g = nx.DiGraph()
    active = np.nonzero(np.asarray(jax.device_get(state.node_active)))[0]
    g.add_nodes_from(active.tolist())
    g.add_edges_from(zip(s.tolist(), d.tolist()))
    return g
