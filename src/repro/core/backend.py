"""Unified propagation backend: one ``push`` primitive for every sweep.

Every power sweep in the repo — exact PageRank, summarized PageRank, both
HITS directions, Katz, SSSP relaxations, connected-components label
propagation, ``build_summary``'s frozen big-vertex pass and the
algorithm-generic fused query step — is the same primitive applied to a
different edge layout under a different algebra:

    out[v] = ⊕ over in-edges (u, v) of ( values[u] ⊗ weight(u, v) )

The (⊕, ⊗) pair is an explicit :class:`~repro.core.semiring.Semiring`
(``plus_times`` sum-of-products, ``min_plus`` shortest paths, ``min_min``
label-min over int32, ``max_times`` widest paths — see
:mod:`repro.core.semiring`).  This module owns the primitive and its two
implementations:

- ``"pallas"``  — the destination-tiled MXU/VPU kernels in
  :mod:`repro.kernels.spmv.kernel` (Mosaic on TPU, ``interpret`` mode
  elsewhere), consuming a receiver-sorted edge stream with per-tile
  ranges: the one-hot matmul for ``sum`` reductions, the tiled
  masked-reduce variant for ``min``/``max``;
- ``"segment_sum"`` — :func:`repro.graph.csr.gather_push`, an
  ``indices_are_sorted`` XLA segment-sum/min/max over the same sorted
  stream.

Both consume an :class:`EdgeLayout`: the receiver-sorted edge stream with
the per-edge weight baked in, in the semiring's dtype (``1/d_out(u)`` for
PageRank-style sweeps, the ⊗-identity for ``"unit"`` layouts, per-edge
lengths for ``"length"`` ones).  Sorting is the amortizable cost — layouts
are built once per applied update batch (the engine caches them; see
``VeilGraphEngine.edge_layouts``), reused across queries, and within one
query across all ~30 power iterations.

Backend selection
-----------------
``resolve_backend(None)`` picks per device: ``"pallas"`` when JAX's default
backend is TPU, ``"segment_sum"`` otherwise.  The ``VEILGRAPH_BACKEND``
environment variable overrides (values: ``pallas``, ``segment_sum``,
``auto``), and every sweep/engine entry point takes an explicit ``backend=``
knob that overrides both.  Resolution happens at trace time; a changed
environment variable does not invalidate already-compiled sweeps.

Sharded execution
-----------------
``push`` is mesh-aware: hand it a :class:`ShardedEdgeLayout` (built by
:func:`repro.graph.partition.build_sharded_layout` — the edge stream cut
into contiguous shards, each destination-sorted *locally* so no sort ever
crosses a shard boundary) and the same primitive runs as a
``shard_map``-ed partial push per shard followed by one semiring
all-reduce of the dense node vector (``psum``/``pmin``/``pmax`` per the
(⊕, ⊗) pair — min/max reductions stay bitwise identical to the
single-device result, sums differ only by f32 summation order).  Either
backend runs *inside* each shard, so the Pallas MXU kernels lower under
GSPMD too.  Without a mesh attached the same layout runs as a sequential
per-shard loop on one device — the reference semantics the parity tests
pin the distributed path against.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import os
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import spans
from repro.core.semiring import Semiring, resolve_semiring
from repro.graph.csr import SortedEdges, gather_push, sort_by_dst
from repro.graph.graph import GraphState, inv_out_degree
from repro.kernels.spmv.kernel import (CHUNK, TILE_N, spmv_push,
                                       spmv_push_batched, spmv_reduce_push,
                                       spmv_reduce_push_batched)

BACKENDS = ("segment_sum", "pallas")

#: weight modes an EdgeLayout can bake: ``inv_out`` = 1/d_out(u) (PageRank
#: emission; plus_times only), ``unit`` = the semiring's ⊗-identity,
#: ``length`` = per-edge lengths (default 1) for min_plus-style relaxations.
WEIGHT_MODES = ("inv_out", "unit", "length")

#: env override for backend selection (read at trace time)
BACKEND_ENV_VAR = "VEILGRAPH_BACKEND"


def resolve_backend(backend: Optional[str] = None) -> str:
    """Resolve ``None``/``"auto"`` to a concrete backend name.

    Priority: explicit argument > ``$VEILGRAPH_BACKEND`` > device default
    (TPU → ``"pallas"``, anything else → ``"segment_sum"``).
    """
    if backend in (None, "auto"):
        backend = os.environ.get(BACKEND_ENV_VAR, "auto")
    if backend in (None, "auto", ""):
        backend = "pallas" if jax.default_backend() == "tpu" else "segment_sum"
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r}; expected one of "
            f"{BACKENDS + ('auto',)}")
    return backend


def default_interpret() -> bool:
    """Pallas runs as a compiled Mosaic kernel only on TPU; everywhere else
    the kernel body executes in interpret mode (how CI validates it)."""
    return jax.default_backend() != "tpu"


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("src", "dst", "weight", "valid", "row_offsets", "order",
                 "rank"),
    meta_fields=("weight_mode", "reverse", "pad_chunk", "semiring", "tile_n",
                 "tile_chunk"),
)
@dataclasses.dataclass(frozen=True)
class EdgeLayout:
    """Receiver-sorted edge stream with baked per-edge weights.

    The propagation-ready form of :class:`~repro.graph.csr.SortedEdges`:
    same sorted order plus the per-edge multiplier, padded by at least one
    kernel chunk so the Pallas kernel's fixed-size chunk loads never run
    past the buffer.  ``dst`` holds ``num_segments`` in padding slots and
    ``weight`` the semiring's ⊕-identity there (0 for sum-of-products,
    ±∞/int extrema for min/max reductions), so both backends ignore
    padding without branching.

    ``row_offsets`` (int32[num_segments + 1]) gives the edge range per
    receiver; per-tile kernel ranges for any tile size derive from it with
    one gather, so one cached layout serves every ``tile_n``.

    ``weight_mode``/``reverse``/``semiring`` record how the layout was
    built and ``pad_chunk`` how much chunk slack the stream was padded
    with; they ride through jit as static metadata so consumers can reject
    a mismatched cached layout at trace time (:func:`require_layout`, the
    semiring check and ``chunk`` bound in :func:`push`) instead of
    silently mis-weighting, mis-padding, or reading out of bounds.
    """

    src: jax.Array          # int32[E_pad] emitting endpoint (sorted order)
    dst: jax.Array          # int32[E_pad] receiving endpoint (sentinel = N)
    weight: jax.Array       # dtype[E_pad] per-edge operand (⊕-id if invalid)
    valid: jax.Array        # bool[E_pad]
    row_offsets: jax.Array  # int32[num_segments + 1]
    #: original edge slot per sorted position (sentinel = edge_capacity in
    #: padding) — lets consumers map baked weights back to slot order
    #: (build_summary recovers per-edge lengths this way).  None for
    #: summary layouts, whose edge space is already compacted.
    order: Optional[jax.Array] = None
    #: per-edge rank within its destination run (``i - row_offsets[dst_i]``
    #: in sorted order; 0 in padding) — the segmented-scan reduce kernel's
    #: same-run test.  Only baked for min/max-semiring layouts (``push``
    #: derives it inline otherwise).
    rank: Optional[jax.Array] = None
    weight_mode: str = "inv_out"
    reverse: bool = False
    pad_chunk: int = CHUNK
    semiring: str = "plus_times"
    #: autotuned kernel geometry (static, ``None`` = kernel defaults):
    #: stamped at build time by the engine's autotune pass so every
    #: consuming sweep picks the tuned ``(tile_n, chunk)`` with no user
    #: knobs — ``push`` resolves explicit argument > layout meta > default.
    tile_n: Optional[int] = None
    tile_chunk: Optional[int] = None

    @property
    def num_segments(self) -> int:
        """Size of the receiver/node space this layout pushes into."""
        return self.row_offsets.shape[0] - 1


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("src", "dst", "weight", "valid", "row_offsets", "order",
                 "rank"),
    meta_fields=("weight_mode", "reverse", "pad_chunk", "semiring", "tile_n",
                 "tile_chunk", "mesh", "axes"),
)
@dataclasses.dataclass(frozen=True)
class ShardedEdgeLayout:
    """Edge-partitioned sibling of :class:`EdgeLayout`: one locally
    destination-sorted stream per shard, stacked along a leading shard axis.

    Built by :func:`repro.graph.partition.build_sharded_layout`: the COO
    buffer is cut into ``num_shards`` contiguous slot ranges (so a
    1-D-edge-sharded buffer reshapes onto the shard axis with zero
    communication) and each shard is sorted by receiving endpoint
    *independently* — the amortized sort never crosses a shard boundary,
    which is what makes the cached-layout backend viable under GSPMD where
    a global pod-scale argsort would defeat the edge sharding.

    Every per-shard row carries the same invariants as a single
    :class:`EdgeLayout` (baked ⊗-operand, ⊕-identity padding, per-receiver
    ``row_offsets`` over the full ``num_segments`` node space, ≥ one chunk
    of slack), so :func:`push` runs the ordinary single-shard kernel inside
    each shard and completes the ⊕ with one collective.

    ``mesh``/``axes`` are static metadata naming where the shard axis
    lives: ``mesh=None`` means no device mapping — :func:`push` then loops
    shards sequentially and merges partials on one device (the reference
    semantics).  With a mesh, the leading axis is ``shard_map``-ed over
    ``axes`` (``num_shards`` must be a multiple of the axes' total device
    count; the per-device surplus shards loop locally).
    """

    src: jax.Array          # int32[S, E_pad] emitting endpoint (sorted)
    dst: jax.Array          # int32[S, E_pad] receiving endpoint (sentinel=N)
    weight: jax.Array       # dtype[S, E_pad] per-edge operand (⊕-id invalid)
    valid: jax.Array        # bool[S, E_pad]
    row_offsets: jax.Array  # int32[S, num_segments + 1]
    #: original edge slot per (shard, sorted position); sentinel =
    #: edge_capacity in padding — the partition certificate (each live slot
    #: appears in exactly one shard) and the lengths back-map.
    order: Optional[jax.Array] = None
    #: per-(shard, position) rank within its destination run — the
    #: segmented-scan reduce kernel's same-run test, baked only for
    #: min/max-semiring layouts (see :class:`EdgeLayout`).
    rank: Optional[jax.Array] = None
    weight_mode: str = "inv_out"
    reverse: bool = False
    pad_chunk: int = CHUNK
    semiring: str = "plus_times"
    #: autotuned kernel geometry (static; see :class:`EdgeLayout`)
    tile_n: Optional[int] = None
    tile_chunk: Optional[int] = None
    mesh: Optional[Mesh] = None
    axes: Tuple[str, ...] = ()

    @property
    def num_shards(self) -> int:
        """Number of edge shards stacked along the leading axis."""
        return self.row_offsets.shape[0]

    @property
    def num_segments(self) -> int:
        """Size of the receiver/node space (shared by every shard)."""
        return self.row_offsets.shape[1] - 1


#: layout kinds push() accepts
AnyEdgeLayout = Union[EdgeLayout, ShardedEdgeLayout]


def padded_length(e: int, chunk: int) -> int:
    """Stream length after chunk-slack padding — the next chunk multiple
    plus one spare chunk, so the kernel's fixed-size dynamic loads never
    run past the buffer.  The one definition every layout builder (single
    and sharded) pads with."""
    return (e // chunk + 2) * chunk


def validate_weight_dtype(weight_dtype: Optional[str],
                          s: Semiring) -> Optional[str]:
    """Trace-time check for compressed edge-weight storage: only the f32
    semirings may store weights in a narrower float dtype (accumulation
    stays f32 via jnp promotion — ``bf16 ⊗ f32 → f32``); the int32
    ``min_min`` family has no narrow storage form."""
    if weight_dtype is None:
        return None
    dt = jnp.dtype(weight_dtype)
    if dt == jnp.dtype(s.dtype):
        return None  # storage dtype == semiring dtype: nothing to compress
    if jnp.dtype(s.dtype) != jnp.float32 or dt not in (
            jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16)):
        raise ValueError(
            f"weight_dtype={weight_dtype!r} is not a storage form of "
            f"semiring {s.name!r} ({s.dtype}); compressed weights need an "
            f"f32 semiring and a bfloat16/float16 storage dtype")
    return str(dt)


def bake_weights(s: Semiring, weight: str, valid: jax.Array,
                 src: jax.Array, *, inv_deg=None,
                 lengths=None, weight_dtype: Optional[str] = None
                 ) -> jax.Array:
    """The per-edge ⊗-operand for a stream, per weight mode — the single
    definition of what ``inv_out``/``unit``/``length`` bake, shared by the
    single and sharded layout builders so the two cannot drift.

    ``valid``/``src``/``lengths`` are aligned to the caller's stream order
    (sorted or slot order — the caller gathers); ``inv_deg`` is the
    node-space ``1/d_out`` vector for ``inv_out``.  ``lengths=None`` under
    ``weight="length"`` means unit hop counts.  Invalid slots bake the
    semiring's ⊕-identity so they never contribute.

    ``weight_dtype`` optionally narrows the *storage* dtype (bf16 halves
    the weight stream's HBM traffic); the ⊗ with f32 node values promotes
    back to f32, so accumulation precision is unchanged.
    """
    dtype = jnp.dtype(s.dtype)
    zero = jnp.asarray(s.zero, dtype)
    if weight == "inv_out":
        w = jnp.where(valid, inv_deg[src], 0.0)
    elif weight == "unit":
        w = jnp.where(valid, jnp.asarray(s.one, dtype), zero)
    else:
        per_edge = (jnp.asarray(1, dtype) if lengths is None
                    else lengths.astype(dtype))
        w = jnp.where(valid, per_edge, zero)
    if validate_weight_dtype(weight_dtype, s) is not None:
        w = w.astype(weight_dtype)
    return w


def stream_rank(dst: jax.Array, valid: jax.Array) -> jax.Array:
    """Per-edge rank within its destination run over a sorted stream (0 in
    invalid/padding slots so the segmented scan's ``rank >= offset`` test
    never crosses into them).  A run starts where ``dst`` changes; a
    running max of those starts gives each edge its run's start with no
    gather from the row offsets.  Baked at layout-build time for
    min/max-semiring layouts; :func:`push` computes it inline for layouts
    that lack it."""
    idx = jnp.arange(dst.shape[0], dtype=jnp.int32)
    change = jnp.concatenate([jnp.ones((1,), bool), dst[1:] != dst[:-1]])
    start = jax.lax.cummax(jnp.where(change, idx, 0))
    return jnp.where(valid, idx - start, 0)


def _pad_stream(src, dst, weight, valid, *, sentinel: int, chunk: int,
                zero=0.0):
    """Pad the sorted stream to a chunk multiple plus one spare chunk;
    padded weight slots hold ``zero`` (the consuming semiring's
    ⊕-identity) so they never contribute."""
    e = src.shape[0]
    pad = padded_length(e, chunk) - e
    return (
        jnp.pad(src, (0, pad)),
        jnp.pad(dst, (0, pad), constant_values=sentinel),
        jnp.pad(weight, (0, pad), constant_values=zero),
        jnp.pad(valid, (0, pad)),
    )


def validate_weight_spec(weight: str, *, reverse: bool = False,
                         semiring="plus_times", lengths=None,
                         edge_capacity: Optional[int] = None) -> "Semiring":
    """Shared trace-time checks for every (weight, reverse, semiring)
    consumer — :func:`build_layout` and ``build_summary`` must accept
    exactly the same spec space or layouts and summaries drift apart.
    Returns the resolved semiring."""
    s = resolve_semiring(semiring)
    if weight not in WEIGHT_MODES:
        raise ValueError(f"unknown weight mode {weight!r}; expected one of "
                         f"{WEIGHT_MODES}")
    if reverse and weight == "inv_out":
        raise ValueError(
            "reverse=True requires weight='unit' or 'length': inv_out "
            "would normalize by the out-degree of the receiving endpoint")
    if weight == "inv_out" and (s.add, s.mul) != ("sum", "times"):
        raise ValueError(
            "weight='inv_out' (1/d_out emission) is a sum-of-products "
            f"notion; semiring {s.name!r} needs 'unit' or 'length' weights")
    if lengths is not None and weight != "length":
        raise ValueError("lengths= is only meaningful with weight='length'")
    if (lengths is not None and edge_capacity is not None
            and lengths.shape[0] != edge_capacity):
        # a shorter array would silently clamp-gather its last element into
        # every higher edge slot (streamed edges land beyond the initial
        # edge list) — fail loudly at trace time instead
        raise ValueError(
            f"lengths must cover every edge slot: got shape "
            f"{lengths.shape}, edge_capacity={edge_capacity}")
    return s


@functools.partial(
    jax.jit, static_argnames=("weight", "reverse", "chunk", "semiring",
                              "tile_n", "weight_dtype"))
@spans.scope(spans.LAYOUT)
def build_layout(
    state: GraphState,
    *,
    weight: str = "inv_out",
    reverse: bool = False,
    chunk: int = CHUNK,
    semiring: str = "plus_times",
    lengths: Optional[jax.Array] = None,
    tile_n: Optional[int] = None,
    weight_dtype: Optional[str] = None,
) -> EdgeLayout:
    """Full-graph propagation layout, sorted once per call.

    ``weight`` picks the baked per-edge ⊗-operand:

    - ``"inv_out"`` — ``1/d_out(u)`` (PageRank-style emission; only
      meaningful under ``plus_times`` and the forward orientation);
    - ``"unit"``    — the semiring's ⊗-identity (1 for sum-of-products —
      HITS/Katz — but e.g. +∞ for ``min_min`` so labels pass through
      unchanged);
    - ``"length"``  — per-edge lengths for ``min_plus``-style relaxations:
      ``lengths`` (dtype[E_cap], indexed by edge slot) if given, else the
      graph's streamed ``state.edge_len`` column if present, else 1 per
      edge (hop counts).

    ``reverse=True`` builds the transposed layout (receivers are original
    sources — the HITS hub direction / CC's symmetric pass).  Invalid and
    padding slots bake the semiring's ⊕-identity so they never contribute.

    Degrees are baked into ``weight``, so a layout is valid exactly until
    the next applied update batch — the engine invalidates its cache then.

    ``tile_n`` stamps an autotuned output-tile width onto the layout (and
    ``chunk`` doubles as the tuned stream chunk, since the pad slack must
    cover it); :func:`push` then picks the tuned geometry with no per-call
    knobs.  ``weight_dtype`` selects compressed weight storage (bf16
    stream, f32 accumulation — see :func:`bake_weights`).
    """
    record_trace("build_layout")
    if weight == "length" and lengths is None:
        lengths = state.edge_len  # streamed per-edge lengths, if any
    s = validate_weight_spec(weight, reverse=reverse, semiring=semiring,
                             lengths=lengths,
                             edge_capacity=state.edge_capacity)
    se = sort_by_dst(state, reverse=reverse)
    w = bake_weights(
        s, weight, se.valid, se.src, inv_deg=inv_out_degree(state),
        # slot-order lengths follow the sort through se.order
        lengths=None if lengths is None else lengths[se.order],
        weight_dtype=weight_dtype)
    src, dst, w, valid = _pad_stream(
        se.src, se.dst, w, se.valid,
        sentinel=state.node_capacity, chunk=chunk, zero=s.zero)
    order = jnp.pad(se.order, (0, src.shape[0] - se.order.shape[0]),
                    constant_values=state.edge_capacity)
    rank = (stream_rank(dst, valid)
            if s.add != "sum" else None)
    return EdgeLayout(src, dst, w, valid, se.row_offsets, order, rank,
                      weight_mode=weight, reverse=reverse, pad_chunk=chunk,
                      semiring=s.name, tile_n=tile_n, tile_chunk=chunk)


def summary_layout(summary, *, chunk: int = CHUNK,
                   semiring: str = "plus_times") -> AnyEdgeLayout:
    """Propagation layout over a summary's compacted, pre-sorted E_K buffer.

    :func:`repro.core.pagerank.build_summary` already emits E_K sorted by
    local destination with ``ek_row_offsets``; this only derives validity
    and pads for the kernel — flat summaries keep valid edges first, and
    the stacked per-shard form (a summary built through a
    :class:`ShardedEdgeLayout`) marks padding with the ``K_cap`` sentinel
    destination.  A sharded summary yields a :class:`ShardedEdgeLayout`
    carrying the summary's ``mesh``/``axes``, so the consuming sweep's
    :func:`push` runs shard_map-ed per-shard partial pushes + the
    semiring's all-reduce with no further changes.

    ``semiring`` must match the one the summary's ``ek_w``/``b_in`` were
    baked for (checked at trace time against the summary's recorded
    metadata — a ``plus_times`` reduce over +∞-baked min-semiring buffers
    would silently produce NaNs).  Traced inline — call it outside the
    power loop so padding happens once per query, not once per iteration.
    """
    record_trace("summary_layout")
    s = resolve_semiring(semiring)
    baked = getattr(summary, "semiring", None)
    if baked is not None and baked != s.name:
        raise ValueError(
            f"summary_layout(semiring={s.name!r}) over a summary baked for "
            f"{baked!r}; rebuild the summary for this semiring")
    k_cap = summary.hot_ids.shape[0]
    # summaries built through a tuned layout inherit its kernel geometry
    # (stamped as SummaryBuffers meta); older/bare summaries fall back to
    # the kernel defaults
    tile_n = getattr(summary, "tile_n", None)
    tile_chunk = getattr(summary, "tile_chunk", None)
    if tile_chunk is not None:
        chunk = tile_chunk
    if summary.ek_src.ndim == 2:  # stacked per-shard E_K form
        h_s = summary.ek_src.shape[1]
        extra = padded_length(h_s, chunk) - h_s
        pad2 = lambda x, cval: jnp.pad(x, ((0, 0), (0, extra)),
                                       constant_values=cval)
        valid = summary.ek_dst < k_cap
        dst = pad2(summary.ek_dst, k_cap)
        valid = pad2(valid, False)
        rank = (jax.vmap(stream_rank)(dst, valid)
                if s.add != "sum" else None)
        return ShardedEdgeLayout(
            pad2(summary.ek_src, 0), dst,
            pad2(summary.ek_w, s.zero), valid,
            summary.ek_row_offsets, None, rank,
            weight_mode="summary", pad_chunk=chunk, semiring=s.name,
            tile_n=tile_n, tile_chunk=chunk,
            mesh=summary.mesh, axes=summary.axes)
    h_cap = summary.ek_src.shape[0]
    valid = jnp.arange(h_cap, dtype=jnp.int32) < jnp.minimum(
        summary.num_ek, h_cap)
    src, dst, w, valid = _pad_stream(
        summary.ek_src, summary.ek_dst, summary.ek_w, valid,
        sentinel=k_cap, chunk=chunk, zero=s.zero)
    rank = (stream_rank(dst, valid)
            if s.add != "sum" else None)
    return EdgeLayout(src, dst, w, valid, summary.ek_row_offsets, None, rank,
                      weight_mode="summary", pad_chunk=chunk,
                      semiring=s.name, tile_n=tile_n, tile_chunk=chunk)


def require_layout(layout: Optional[AnyEdgeLayout], *, weight: str,
                   reverse: bool, who: str,
                   semiring: str = "plus_times") -> None:
    """Trace-time guard: a cached layout (single or sharded — both carry
    the same static metadata) must match the weighting, orientation and
    semiring the sweep was built for, else its baked weights silently
    mis-weight the propagation (e.g. an algorithm overriding
    ``layout_specs`` without overriding the consuming method).
    ``None`` passes — sweeps fall back to building/unsorted paths."""
    want_s = resolve_semiring(semiring).name
    if layout is not None and (layout.weight_mode != weight
                               or layout.reverse != reverse
                               or layout.semiring != want_s):
        raise ValueError(
            f"{who} needs a layout built with (weight={weight!r}, "
            f"reverse={reverse}, semiring={want_s!r}); got "
            f"(weight={layout.weight_mode!r}, reverse={layout.reverse}, "
            f"semiring={layout.semiring!r})")


def normalize_layout_spec(spec) -> tuple:
    """``(weight, reverse[, semiring])`` → ``(weight, reverse, semiring)``.

    ``StreamingAlgorithm.layout_specs`` entries written before the semiring
    API carry no third element; they mean ``plus_times``.
    """
    if len(spec) == 2:
        return (spec[0], spec[1], "plus_times")
    if len(spec) != 3:
        raise ValueError(
            f"layout spec must be (weight, reverse[, semiring]); got {spec!r}")
    return tuple(spec)


@spans.scope(spans.PUSH)
def push(
    values: jax.Array,
    layout: AnyEdgeLayout,
    *,
    semiring: Union[str, Semiring] = "plus_times",
    backend: Optional[str] = None,
    mask: Optional[jax.Array] = None,
    tile_n: Optional[int] = None,
    chunk: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """The shared propagation primitive:
    ``out[v] = ⊕_{(u,v)} values[u] ⊗ layout.weight[(u,v)]``.

    ``semiring`` names the (⊕, ⊗) pair (registry name or
    :class:`~repro.core.semiring.Semiring`); it must match the semiring the
    layout was built for — the baked weights and padding values are
    algebra-specific, so a mismatch fails at trace time rather than
    silently corrupting the reduce.  ``plus_times`` keeps the one-hot
    matmul MXU fast path; ``min``/``max`` reductions run the tiled
    masked-reduce kernel variant (or XLA segment-min/max on the
    ``segment_sum`` backend).

    ``layout`` may be a single :class:`EdgeLayout` or a
    :class:`ShardedEdgeLayout` — the sharded form runs one partial push
    per shard (the same per-shard kernel, either backend) completed by the
    semiring's all-reduce, ``shard_map``-ed over the layout's mesh when it
    carries one and looped on-device otherwise.

    ``values`` lives in the layout's *node* space (global ids for full-graph
    layouts, local hot ids for summary layouts); the result has
    ``layout.num_segments`` entries.  Receivers with no (unmasked) in-edge
    get the semiring's ⊕-identity (0 / +∞ / −∞).  ``mask`` optionally
    filters edges in the layout's sorted order (shape ``[E_pad]``, or
    ``[S, E_pad]`` for sharded layouts — e.g. the E_B selection in the
    big-vertex pass).  Traced inline — call from inside jitted sweeps;
    ``backend``/``semiring`` must be Python values at trace time.

    **Batched form**: ``values`` may be a ``[B, N]`` matrix — B independent
    query vectors pushed through the one shared layout, returning
    ``[B, num_segments]``.  The pallas sum path runs the batched kernel
    (a true ``[B, chunk] @ [chunk, tile_n]`` MXU matmul per chunk); min/max
    reductions are reassociation-exact, so every batch row is bitwise
    equal to its single-query push.  ``mask`` stays per-edge (shared
    across the batch).

    **Kernel geometry**: ``tile_n``/``chunk`` default to the layout's
    stamped (autotuned) geometry, falling back to the kernel defaults —
    explicit argument > layout meta > ``TILE_N``/``CHUNK``.
    """
    s = resolve_semiring(semiring)
    if isinstance(layout, ShardedEdgeLayout):
        record_trace("push[sharded]")
        return _push_sharded(values, layout, s=s, backend=backend, mask=mask,
                             tile_n=tile_n, chunk=chunk, interpret=interpret)
    if layout.semiring != s.name:
        raise ValueError(
            f"push(semiring={s.name!r}) over a layout built for "
            f"{layout.semiring!r}; rebuild the layout for this semiring")
    backend = resolve_backend(backend)
    record_trace(f"push[{backend}]")
    tile_n = tile_n if tile_n is not None else (
        layout.tile_n if layout.tile_n is not None else TILE_N)
    chunk = chunk if chunk is not None else (
        layout.tile_chunk if layout.tile_chunk is not None else CHUNK)
    num_segments = layout.num_segments
    batched = values.ndim == 2
    if values.ndim > 2:
        raise ValueError(
            f"push expects values of shape [N] or [B, N]; got {values.shape}")
    if backend == "segment_sum":
        if batched:
            # vmap keeps each row's segment-reduce order identical to the
            # single-query call, so min/max rows stay bitwise equal
            return jax.vmap(lambda v: gather_push(
                layout, v, num_segments, weight=layout.weight, mask=mask,
                semiring=s))(values)
        return gather_push(
            layout, values, num_segments, weight=layout.weight, mask=mask,
            semiring=s)

    if chunk > layout.pad_chunk:
        # kernel chunk loads past [start, end) stay inside the buffer only
        # up to the chunk the stream was padded with at build time
        raise ValueError(
            f"push(chunk={chunk}) exceeds the layout's pad_chunk="
            f"{layout.pad_chunk}; rebuild the layout with chunk>={chunk}")

    # pallas: gather contributions outside the kernel (XLA gathers are
    # efficient on TPU), then accumulate per output tile — one-hot matmul
    # for sum reductions, masked min/max reduce otherwise
    num_tiles = -(-num_segments // tile_n)
    bounds = jnp.minimum(
        jnp.arange(num_tiles + 1, dtype=jnp.int32) * tile_n, num_segments)
    tile_start = layout.row_offsets[bounds]
    if interpret is None:
        interpret = default_interpret()
    if s.add == "sum":
        if jnp.dtype(s.dtype) != jnp.float32:
            # the one-hot matmul accumulates on the f32 MXU — a silent cast
            # would break dtype/exactness parity with the segment backend
            # (e.g. int32 path counts losing exactness above 2^24)
            raise NotImplementedError(
                f"the pallas sum-reduce is the f32 one-hot-matmul MXU path; "
                f"semiring {s.name!r} ({s.dtype}) needs "
                f"backend='segment_sum'")
        contrib = s.combine(values[..., layout.src], layout.weight)
        if mask is not None:
            contrib = jnp.where(mask, contrib, 0.0)
        push_fn = spmv_push_batched if batched else spmv_push
        out = push_fn(
            contrib.astype(jnp.float32), layout.dst, tile_start,
            num_tiles=num_tiles, tile_n=tile_n, chunk=chunk,
            interpret=interpret)
    else:
        dtype = jnp.dtype(s.dtype)
        zero = jnp.asarray(s.zero, dtype)
        contrib = s.combine(values.astype(dtype)[..., layout.src],
                            layout.weight)
        if contrib.dtype != dtype:
            # compressed (bf16) weights promote the ⊗ up to f32 already;
            # this cast only normalizes layouts whose weights were stored
            # *below* the semiring dtype but whose ⊗ did not promote
            contrib = contrib.astype(dtype)
        keep = layout.valid if mask is None else (layout.valid & mask)
        contrib = jnp.where(keep, contrib, zero)
        rank = layout.rank
        if rank is None:
            rank = stream_rank(layout.dst, layout.valid)
        reduce_fn = spmv_reduce_push_batched if batched else spmv_reduce_push
        out = reduce_fn(
            contrib, layout.dst, rank, tile_start, num_tiles=num_tiles,
            op=s.add, tile_n=tile_n, chunk=chunk, interpret=interpret)
    return out[..., :num_segments]


def _shard_view(layout: ShardedEdgeLayout, i, src, dst, w, valid,
                ro, rank) -> EdgeLayout:
    """Shard ``i`` of the stacked arrays as a plain :class:`EdgeLayout`
    (same static metadata), ready for the single-shard :func:`push`."""
    return EdgeLayout(
        src[i], dst[i], w[i], valid[i], ro[i], None,
        None if rank is None else rank[i],
        weight_mode=layout.weight_mode, reverse=layout.reverse,
        pad_chunk=layout.pad_chunk, semiring=layout.semiring,
        tile_n=layout.tile_n, tile_chunk=layout.tile_chunk)


def _push_sharded(
    values: jax.Array,
    layout: ShardedEdgeLayout,
    *,
    s: Semiring,
    backend: Optional[str],
    mask: Optional[jax.Array],
    tile_n: Optional[int],
    chunk: Optional[int],
    interpret: Optional[bool],
) -> jax.Array:
    """Sharded form of :func:`push`: per-shard partial push + ⊕ all-reduce.

    Each shard's stream is locally destination-sorted, so the shard-local
    reduce is the ordinary single-shard push (either backend, including
    the Pallas kernels); shard partials are dense ``[num_segments]``
    vectors merged by the semiring's ⊕ — ``lax.psum``/``pmin``/``pmax``
    across the mesh axes when the layout carries a mesh, an on-device
    merge loop otherwise.  min/max semirings are reassociation-exact, so
    the sharded result is *bitwise* equal to the single-layout push; sum
    semirings differ only by f32 summation order.
    """
    if layout.semiring != s.name:
        raise ValueError(
            f"push(semiring={s.name!r}) over a sharded layout built for "
            f"{layout.semiring!r}; rebuild the layout for this semiring")
    backend = resolve_backend(backend)
    num_shards = layout.num_shards
    if mask is not None and mask.shape != layout.dst.shape:
        raise ValueError(
            f"sharded push mask must cover the sharded sorted stream "
            f"{layout.dst.shape}; got {mask.shape}")

    def local_push(values, src, dst, w, valid, ro, rank, m, lo, hi):
        """⊕-merge of shards [lo, hi) resident on this device."""
        part = None
        for i in range(lo, hi):
            one = push(values,
                       _shard_view(layout, i, src, dst, w, valid, ro, rank),
                       semiring=s, backend=backend,
                       mask=None if m is None else m[i],
                       tile_n=tile_n, chunk=chunk, interpret=interpret)
            part = one if part is None else s.merge(part, one)
        return part

    if layout.mesh is None:
        return local_push(values, layout.src, layout.dst, layout.weight,
                          layout.valid, layout.row_offsets, layout.rank,
                          mask, 0, num_shards)

    mesh, axes = layout.mesh, layout.axes
    n_dev = 1
    for a in axes:
        n_dev *= mesh.shape[a]
    if num_shards % n_dev:
        raise ValueError(
            f"sharded layout has {num_shards} shards over {n_dev} devices "
            f"(mesh axes {axes}); shards must divide evenly")
    per_dev = num_shards // n_dev

    has_rank = layout.rank is not None

    def mapped(values, src, dst, w, valid, ro, *rest):
        rest = list(rest)
        rank = rest.pop(0) if has_rank else None
        m = rest.pop(0) if rest else None
        part = local_push(values, src, dst, w, valid, ro, rank, m,
                          0, per_dev)
        return s.all_reduce(part, axes)

    args = [values, layout.src, layout.dst, layout.weight, layout.valid,
            layout.row_offsets]
    in_specs = [P()] + [P(axes)] * 5
    if has_rank:
        args.append(layout.rank)
        in_specs.append(P(axes))
    if mask is not None:
        args.append(mask)
        in_specs.append(P(axes))
    # check_vma=False: the pallas kernels inside each shard have no
    # replication rule, but the all-reduce makes the output replicated by
    # construction
    fn = jax.shard_map(mapped, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=P(), check_vma=False)
    return fn(*args)


#: trace-time invocation counters — observability for "the compiled
#: program contains zero unsorted pushes" and friends: counters tick when
#: a Python call traces the primitive, so lowering a program fresh and
#: reading the counter delta tells what the program is built from.  Every
#: hot entry point ticks its own name (``push[<backend>]``,
#: ``push[sharded]``, ``push_coo``, ``build_layout``, ``summary_layout``);
#: the jaxpr lint's JXP-UNSORTED-SCATTER rule is the structural
#: generalization of the ``push_coo`` counter pin.
_TRACE_COUNTS: collections.Counter = collections.Counter()


def record_trace(name: str) -> None:
    """Tick the trace counter for ``name``.

    Call at trace time from any primitive whose presence in a compiled
    program is a contract (the built-ins above tick themselves; plugins
    and kernels may register their own names).  No-op at run time: jitted
    bodies only execute this while tracing, so counter deltas measure
    *program structure*, not call volume.
    """
    _TRACE_COUNTS[name] += 1


def trace_count(name: str) -> int:
    """Times primitive ``name`` (e.g. ``"push_coo"``) traced since the last
    :func:`reset_trace_counts` — see the counter note above."""
    return _TRACE_COUNTS[name]


def reset_trace_counts() -> None:
    """Zero every trace counter (call before lowering a program fresh)."""
    _TRACE_COUNTS.clear()


def push_coo(
    values: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    num_segments: int,
    *,
    weight: Optional[jax.Array] = None,
    mask: Optional[jax.Array] = None,
    semiring: Union[str, Semiring] = "plus_times",
) -> jax.Array:
    """Unsorted-COO fallback for callers with no layout at hand.

    A plain XLA segment-sum/min/max over the caller's (unsorted) edge
    order.  ``weight`` is the raw ⊗-operand per edge; masked edges
    contribute the semiring's ⊕-identity.  ``values`` may be ``[N]`` or a
    batched ``[B, N]`` matrix (→ ``[B, num_segments]``, vmapped so each
    row matches its single-query call).  Prefer :func:`push` with a
    cached (possibly sharded) layout everywhere else — since the sharded
    layouts landed, no engine/dry-run hot loop goes through here
    (:func:`trace_count` ``("push_coo")`` is how tests and the dry-run
    assert that).
    """
    _TRACE_COUNTS["push_coo"] += 1
    s = resolve_semiring(semiring)

    def one(v):
        contrib = v[src]
        if weight is not None:
            contrib = s.combine(contrib, weight)
        if mask is not None:
            contrib = jnp.where(mask, contrib,
                                jnp.asarray(s.zero, contrib.dtype))
        return s.segment_reduce(contrib, dst, num_segments=num_segments)

    if values.ndim == 2:
        return jax.vmap(one)(values)
    return one(values)


__all__ = [
    "BACKENDS",
    "BACKEND_ENV_VAR",
    "WEIGHT_MODES",
    "AnyEdgeLayout",
    "EdgeLayout",
    "Semiring",
    "bake_weights",
    "padded_length",
    "ShardedEdgeLayout",
    "SortedEdges",
    "build_layout",
    "default_interpret",
    "normalize_layout_spec",
    "record_trace",
    "reset_trace_counts",
    "stream_rank",
    "trace_count",
    "validate_weight_dtype",
    "validate_weight_spec",
    "push",
    "push_coo",
    "require_layout",
    "resolve_backend",
    "resolve_semiring",
    "summary_layout",
]
