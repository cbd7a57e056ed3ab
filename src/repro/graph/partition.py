"""Edge partitioning across a device mesh.

The VeilGraph runtime shards the COO edge buffers over every mesh axis
(1-D edge parallelism: the TPU analogue of Pregel's edge-cut) while node
vectors stay replicated; the per-iteration push is a local partial reduce
plus one all-reduce of the dense rank vector.  Two layers live here:

- the GSPMD shardings the dry-run and a real deployment pin on the raw
  ``GraphState`` buffers (:func:`edge_sharding`, :func:`graph_shardings`,
  from the rule table :func:`rules_for_mesh` and :func:`guarded_pspec`),
  plus a host-side round-robin assignment for multi-host ingestion;
- the **sharded edge layouts** the mesh-aware propagation backend
  consumes (:func:`build_sharded_layout`): the edge buffer cut into
  contiguous shards, each destination-sorted *locally* — the cached-sort
  story of :mod:`repro.core.backend` carried to the distributed setting
  without ever running a sort across shards (a pod-scale global argsort
  would defeat GSPMD's edge sharding; S independent local sorts do not);
- **shard rebalancing** (:func:`rebalance_sharded_layout` and friends):
  streaming appends land at the high-water mark, so the contiguous cut
  fills tail-heavy and removals hollow out arbitrary shards; the engine
  tracks per-shard live-edge counts after each applied update batch and,
  past ``EngineConfig.rebalance_threshold``, recuts the partition with a
  live-balanced slot assignment (:func:`balanced_shard_slots`) that the
  next layout build migrates to with one static-shaped gather.  Any valid
  partition yields the same push result (bitwise for min semirings), so
  rebalancing is purely a load-balance decision.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core import backend as B
from repro.graph.graph import GraphState, inv_out_degree

AxisRules = Dict[str, Tuple[str, ...]]

# Logical axis name -> mesh axes, for the production meshes of
# :mod:`repro.launch.mesh`: the ``edges`` axis is laid over every mesh axis
# (1-D edge parallelism); node vectors stay replicated.
RULES_SINGLE_POD: AxisRules = {"edges": ("data", "model")}
RULES_MULTI_POD: AxisRules = {"edges": ("pod", "data", "model")}


def rules_for_mesh(mesh: Optional[Mesh]) -> AxisRules:
    """The rule table for ``mesh``; no mesh means no rules."""
    if mesh is None:
        return {}
    if "pod" in mesh.axis_names:
        return RULES_MULTI_POD
    return RULES_SINGLE_POD


def guarded_pspec(
    shape: Sequence[int],
    logical: Sequence[Optional[str]],
    rules: AxisRules,
    axis_sizes: Dict[str, int],
) -> P:
    """The PartitionSpec of logical axis names (None = replicated) under
    ``rules``, with divisibility guards.

    A mesh axis is applied to a dim only if the dim is divisible by the
    product of the axes selected so far times that axis (an edge buffer
    whose capacity the mesh does not divide stays replicated), and an axis
    is never used twice in one spec.
    """
    out = []
    used: set = set()
    for dim, name in zip(shape, logical):
        if name is None:
            out.append(None)
            continue
        sel = []
        prod = 1
        for a in rules.get(name, ()):
            if a in used:
                continue
            nxt = prod * axis_sizes.get(a, 1)
            if nxt > 0 and dim % nxt == 0 and dim >= nxt:
                sel.append(a)
                prod = nxt
        used.update(sel)
        if not sel:
            out.append(None)
        elif len(sel) == 1:
            out.append(sel[0])
        else:
            out.append(tuple(sel))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def edge_sharding(mesh: Mesh, edge_capacity: int) -> NamedSharding:
    """The 1-D GSPMD sharding for an edge-capacity buffer: the ``edges``
    logical axis laid over the mesh per its sharding rules."""
    rules = rules_for_mesh(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return NamedSharding(mesh, guarded_pspec((edge_capacity,), ("edges",),
                                             rules, sizes))


def graph_shardings(mesh: Mesh, state: GraphState) -> GraphState:
    """Sharding pytree for a GraphState: edges sharded, nodes replicated."""
    e = edge_sharding(mesh, state.edge_capacity)
    n = NamedSharding(mesh, P())
    return GraphState(src=e, dst=e, edge_alive=e, num_edges=n,
                      out_deg=n, in_deg=n, node_active=n,
                      edge_len=None if state.edge_len is None else e)


def host_edge_slice(num_edges: int, process: int,
                    num_processes: int) -> Tuple[int, int]:
    """Contiguous per-host ingestion range (multi-host streaming loaders)."""
    per = (num_edges + num_processes - 1) // num_processes
    lo = min(process * per, num_edges)
    return lo, min(lo + per, num_edges)


# ---------------------------------------------------------------------------
# Sharded edge layouts (the mesh-aware backend's input)
# ---------------------------------------------------------------------------


def shard_slots(edge_capacity: int, num_shards: int) -> np.ndarray:
    """int32[S, E_s] original edge slot per (shard, position) — the
    contiguous partition :func:`build_sharded_layout` applies *before* its
    per-shard sort.  Shard ``s`` owns slots ``[s·E_s, (s+1)·E_s)``
    (contiguous, so a 1-D edge-sharded buffer reshapes onto the shard axis
    with zero communication); slots ≥ ``edge_capacity`` are padding
    (sentinel ``edge_capacity``).  Every real slot lands in exactly one
    shard — the property the partition tests pin.
    """
    e_s = -(-edge_capacity // num_shards)
    slots = np.arange(num_shards * e_s, dtype=np.int32)
    return np.where(slots < edge_capacity, slots,
                    edge_capacity).reshape(num_shards, e_s)


@functools.partial(
    jax.jit,
    static_argnames=("num_shards", "weight", "reverse", "chunk", "semiring",
                     "tile_n", "weight_dtype"))
def _build_shards(
    state: GraphState,
    *,
    num_shards: int,
    weight: str,
    reverse: bool,
    chunk: int,
    semiring: str,
    lengths: Optional[jax.Array] = None,
    slots: Optional[jax.Array] = None,
    tile_n: Optional[int] = None,
    weight_dtype: Optional[str] = None,
) -> B.ShardedEdgeLayout:
    """The jitted core of :func:`build_sharded_layout` (no mesh metadata —
    the partition and the S local sorts are pure array work).

    ``slots`` (int32[S, ⌈E_cap/S⌉], sentinel ``E_cap`` for padding)
    overrides the default contiguous cut with an explicit slot→shard
    assignment — the rebalancing path: the stacked streams are then built
    by one static-shaped gather per buffer (the slot *migration*) instead
    of the communication-free pad+reshape.
    """
    if weight == "length" and lengths is None:
        lengths = state.edge_len  # streamed per-edge lengths, if any
    s = B.validate_weight_spec(weight, reverse=reverse, semiring=semiring,
                               lengths=lengths,
                               edge_capacity=state.edge_capacity)
    e_cap = state.edge_capacity
    n_cap = state.node_capacity
    mask = state.edge_mask()
    e_src, e_dst = (state.dst, state.src) if reverse else (state.src,
                                                           state.dst)
    # same ⊗-operand definition as build_layout, here in slot order
    w = B.bake_weights(s, weight, mask, e_src,
                       inv_deg=inv_out_degree(state), lengths=lengths,
                       weight_dtype=weight_dtype)

    e_s = -(-e_cap // num_shards)
    if slots is None:
        # contiguous slot partition: pad the slot space to S·E_s and
        # reshape — on a 1-D edge-sharded buffer this is communication-free
        # under GSPMD
        pad = num_shards * e_s - e_cap

        def cut(x, cval):
            return jnp.pad(x, (0, pad), constant_values=cval).reshape(
                num_shards, e_s)
    else:
        # rebalanced partition: migrate slots with one static-shaped gather
        # per buffer (a one-off resharding under GSPMD, amortized exactly
        # like the sort — once per applied update batch)
        ok = slots < e_cap
        sl = jnp.minimum(slots, e_cap - 1)

        def cut(x, cval):
            return jnp.where(ok, x[sl], jnp.asarray(cval, x.dtype))

    src2 = cut(e_src, 0)
    dst2 = cut(jnp.where(mask, e_dst, n_cap), n_cap)  # invalid sorts last
    w2 = cut(w, s.zero)
    valid2 = cut(mask, False)
    order2 = cut(jnp.arange(e_cap, dtype=jnp.int32), e_cap)

    # S independent destination sorts — axis-1 sorts stay shard-local under
    # GSPMD (no cross-device exchange), unlike one global E_cap argsort
    perm = jnp.argsort(dst2, axis=1, stable=True)
    take = lambda x: jnp.take_along_axis(x, perm, axis=1)
    src2, dst2, w2, valid2, order2 = map(take,
                                         (src2, dst2, w2, valid2, order2))
    row_offsets = jax.vmap(
        lambda d: jnp.searchsorted(
            d, jnp.arange(n_cap + 1, dtype=jnp.int32),
            side="left").astype(jnp.int32))(dst2)

    # chunk slack per shard, same convention as the single builder: the
    # kernel's fixed-size chunk loads never run past any shard's buffer
    extra = B.padded_length(e_s, chunk) - e_s
    pad2 = lambda x, cval: jnp.pad(x, ((0, 0), (0, extra)),
                                   constant_values=cval)
    dst_p = pad2(dst2, n_cap)
    valid_p = pad2(valid2, False)
    rank = (jax.vmap(B.stream_rank)(dst_p, valid_p)
            if s.add != "sum" else None)
    return B.ShardedEdgeLayout(
        pad2(src2, 0), dst_p, pad2(w2, s.zero),
        valid_p, row_offsets, pad2(order2, e_cap), rank,
        weight_mode=weight, reverse=reverse, pad_chunk=chunk,
        semiring=s.name, tile_n=tile_n, tile_chunk=chunk)


def build_sharded_layout(
    state: GraphState,
    *,
    mesh: Optional[Mesh] = None,
    axes: Optional[Tuple[str, ...]] = None,
    num_shards: Optional[int] = None,
    weight: str = "inv_out",
    reverse: bool = False,
    chunk: Optional[int] = None,
    semiring: str = "plus_times",
    lengths: Optional[jax.Array] = None,
    slots: Optional[jax.Array] = None,
    tile_n: Optional[int] = None,
    weight_dtype: Optional[str] = None,
) -> B.ShardedEdgeLayout:
    """Edge-partitioned, per-shard destination-sorted propagation layout.

    The sharded sibling of :func:`repro.core.backend.build_layout` — same
    ``weight``/``reverse``/``semiring``/``lengths`` spec space (validated
    by the same :func:`~repro.core.backend.validate_weight_spec`), but the
    edge stream is first cut into ``num_shards`` slot ranges and each
    shard sorted independently, so no sort ever crosses a shard boundary.
    :func:`repro.core.backend.push` consumes the result as a
    ``shard_map``-ed partial push + semiring all-reduce.

    Parameters
    ----------
    mesh / axes
        Device mapping: the shard axis is laid over ``axes`` (default:
        every mesh axis, flattened).  With ``mesh=None`` (``num_shards``
        required) the layout runs as an on-device loop — the reference
        semantics sharded parity tests compare against, and a way to
        exercise S-way partitioning without S devices.
    num_shards
        Defaults to the total device count of ``axes`` and must stay a
        multiple of it.
    weight / reverse / semiring / lengths
        The baked ⊗-operand spec — see
        :func:`repro.core.backend.build_layout`.
    slots
        Optional explicit slot→shard assignment
        (int32[num_shards, ⌈E_cap/num_shards⌉], sentinel ``E_cap`` in
        padding positions; every live slot must appear exactly once).
        Default is the contiguous cut of :func:`shard_slots`; pass
        :func:`balanced_shard_slots` output (or any custom partition) to
        *rebalance* — the streams are then gathered per the assignment
        instead of reshaped.  See :func:`rebalance_sharded_layout`.

    Returns a :class:`~repro.core.backend.ShardedEdgeLayout` of stacked
    ``[num_shards, E_pad]`` streams.  Traced inline-compatible: callable
    from inside jit (the fused query step builds sharded layouts on the
    fly when handed a mesh but no cache), with the engine caching built
    instances per applied update batch exactly like single layouts.
    """
    if mesh is not None:
        mesh = auto_axes(mesh)
        axes = tuple(axes) if axes is not None else tuple(mesh.axis_names)
        for a in axes:
            if a not in mesh.axis_names:
                raise ValueError(
                    f"mesh axis {a!r} not in mesh {tuple(mesh.axis_names)}")
        n_dev = mesh_shard_count(mesh, axes)
        if num_shards is None:
            num_shards = n_dev
        if num_shards % n_dev:
            raise ValueError(
                f"num_shards={num_shards} must be a multiple of the "
                f"{n_dev} devices on mesh axes {axes}")
    elif num_shards is None:
        raise ValueError("build_sharded_layout needs mesh= or num_shards=")
    else:
        axes = ()
    if slots is not None:
        want = (num_shards, -(-state.edge_capacity // num_shards))
        if tuple(slots.shape) != want:
            raise ValueError(
                f"slots assignment shape {tuple(slots.shape)} does not "
                f"match {want} for num_shards={num_shards}, "
                f"edge_capacity={state.edge_capacity}")
        slots = jnp.asarray(slots, jnp.int32)
    layout = _build_shards(
        state, num_shards=num_shards, weight=weight, reverse=reverse,
        chunk=B.CHUNK if chunk is None else chunk, semiring=semiring,
        lengths=lengths, slots=slots, tile_n=tile_n,
        weight_dtype=weight_dtype)
    if mesh is not None:
        layout = dataclasses.replace(layout, mesh=mesh, axes=axes)
    return layout


# ---------------------------------------------------------------------------
# Shard rebalancing (streaming keeps the contiguous cut tail-heavy)
# ---------------------------------------------------------------------------


def auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis of type ``AxisType.Auto``.

    The sharded paths leave propagation to the partitioner (GSPMD).
    ``jax.make_mesh`` makes ``Explicit`` axes by default, under which every
    gather over a sharded stream would have to name its output sharding;
    the engine and :func:`build_sharded_layout` take the same devices with
    auto axes instead."""
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def mesh_shard_count(mesh: Mesh, axes: Optional[Tuple[str, ...]]) -> int:
    """Total device count over ``axes`` (default: every mesh axis) — the
    shard count a mesh-configured engine partitions its layouts into.
    The single definition :func:`build_sharded_layout` and the engine's
    rebalance path both resolve through, so the rebalanced ``slots`` shape
    can never drift from the layout's shard count."""
    names = tuple(axes) if axes is not None else tuple(mesh.axis_names)
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n


@jax.jit
def shard_live_counts(state: GraphState, slots: jax.Array) -> jax.Array:
    """int32[S]: live (non-padding, non-tombstone) edges per shard under a
    slot assignment — the balance signal the engine tracks after
    ``add_edges``/``remove_edges`` batches apply."""
    e_cap = state.edge_capacity
    mask = state.edge_mask()
    ok = slots < e_cap
    live = ok & mask[jnp.minimum(slots, e_cap - 1)]
    return jnp.sum(live.astype(jnp.int32), axis=1)


def shard_imbalance(counts: jax.Array) -> jax.Array:
    """Scalar imbalance of per-shard live counts:
    ``(max − min) / max(mean, 1)``.  0 for a perfectly even partition;
    ``≈ S`` when one shard holds everything.  Dimensionless, so one
    threshold works across graph sizes."""
    c = counts.astype(jnp.float32)
    return (jnp.max(c) - jnp.min(c)) / jnp.maximum(jnp.mean(c), 1.0)


@functools.partial(jax.jit, static_argnames=("num_shards",))
def balanced_shard_slots(state: GraphState, *,
                         num_shards: int) -> jax.Array:
    """A live-balanced slot→shard assignment (int32[S, ⌈E_cap/S⌉]).

    Live slots are dealt round-robin across shards in slot order (shard
    counts differ by at most one), then dead/padding slots continue the
    same deal — so the slot ids a streaming ``add_edges`` will fill next
    (consecutive ids above the high-water mark) are also pre-spread across
    shards, keeping post-rebalance appends balanced instead of refilling
    one tail shard.  Pure prefix-sum work, jit-compatible; feed the result
    to :func:`build_sharded_layout` via ``slots=``.
    """
    e_cap = state.edge_capacity
    e_s = -(-e_cap // num_shards)
    mask = state.edge_mask()
    m = mask.astype(jnp.int32)
    live_rank = jnp.cumsum(m) - m           # exclusive prefix over lives
    dead_rank = jnp.cumsum(1 - m) - (1 - m)
    seq = jnp.where(mask, live_rank, jnp.sum(m) + dead_rank)
    flat = (seq % num_shards) * e_s + seq // num_shards
    out = jnp.full((num_shards * e_s,), e_cap, jnp.int32)
    out = out.at[flat].set(jnp.arange(e_cap, dtype=jnp.int32), mode="drop")
    return out.reshape(num_shards, e_s)


@jax.jit
def rebalance_decision(state: GraphState, slots: jax.Array,
                       threshold: jax.Array
                       ) -> Tuple[jax.Array, jax.Array]:
    """On-device rebalance verdict: ``(should_rebalance bool[],
    imbalance f32[])`` for the current slot assignment.

    One fused jitted program — live-count, imbalance and the threshold
    compare all stay on device, so the engine's per-applied-batch
    balance check transfers exactly one (bool, f32) pair to host instead
    of syncing on an eager ``float(...)`` mid-pipeline.  ``threshold``
    may be a python float (weak-typed scalar traces once).

    The async rebuild pipeline (``EngineConfig.async_rebuild``) goes one
    step further: the verdict is *dispatched but not awaited* alongside
    each :class:`~repro.core.epoch.EpochSnapshot` build and the (bool,
    f32) pair is fetched only when the snapshot is promoted at a wave
    boundary — a recut then applies to the *next* epoch's layout cuts,
    never to the already-sorted snapshot being promoted.
    """
    imbalance = shard_imbalance(shard_live_counts(state, slots))
    return imbalance > threshold, imbalance


def rebalance_sharded_layout(
    state: GraphState,
    *,
    num_shards: int,
    slots: Optional[jax.Array] = None,
    threshold: float = 1.0,
) -> Tuple[jax.Array, bool, float]:
    """Recut the edge partition when live-edge imbalance exceeds
    ``threshold``.

    ``slots`` is the current assignment (default: the contiguous
    :func:`shard_slots` cut — what a mesh engine starts from).  Returns
    ``(slots', rebalanced, imbalance)``: the assignment to build the next
    layouts with, whether it changed, and the imbalance that was measured
    (one :func:`rebalance_decision` verdict pair read back to host — this
    runs between jitted steps, once per applied update batch, never in
    the query hot loop).

    The recut itself is :func:`balanced_shard_slots`; the *migration*
    happens at the next :func:`build_sharded_layout` call, which gathers
    the streams per the new assignment (static shapes — one O(E) gather,
    amortized exactly like the per-shard sorts).  The engine drives this
    loop: it invalidates its cached layouts and counts the event in
    ``engine.rebalances``.
    """
    if slots is None:
        slots = jnp.asarray(shard_slots(state.edge_capacity, num_shards))
    should, imbalance = jax.device_get(  # analysis: allow(AST-HOST-SYNC): the one verdict read per applied batch — the documented host boundary of the rebalance loop
        rebalance_decision(state, slots, jnp.float32(threshold)))
    if not bool(should):
        return slots, False, float(imbalance)
    return (balanced_shard_slots(state, num_shards=num_shards), True,
            float(imbalance))


def place_sharded_layout(layout: B.ShardedEdgeLayout) -> B.ShardedEdgeLayout:
    """``device_put`` the stacked arrays onto the layout's mesh (leading
    shard axis over ``layout.axes``, trailing dims replicated).

    A freshly built layout lives wherever jit put it (one device, by
    default); left there, every consuming ``shard_map`` would re-distribute
    the full O(E) stream per call — paying the data-movement half of the
    "sorted at most once per update batch" amortization every query.  The
    engine runs this once per cache fill instead.  No-op without a mesh.
    """
    if layout.mesh is None:
        return layout
    sharded = NamedSharding(layout.mesh, P(layout.axes))
    put = lambda x: None if x is None else jax.device_put(x, sharded)
    return dataclasses.replace(
        layout, src=put(layout.src), dst=put(layout.dst),
        weight=put(layout.weight), valid=put(layout.valid),
        row_offsets=put(layout.row_offsets), order=put(layout.order),
        rank=put(layout.rank))


__all__ = [
    "auto_axes",
    "balanced_shard_slots",
    "build_sharded_layout",
    "edge_sharding",
    "graph_shardings",
    "guarded_pspec",
    "host_edge_slice",
    "mesh_shard_count",
    "place_sharded_layout",
    "rebalance_decision",
    "rebalance_sharded_layout",
    "rules_for_mesh",
    "shard_imbalance",
    "shard_live_counts",
    "shard_slots",
]
