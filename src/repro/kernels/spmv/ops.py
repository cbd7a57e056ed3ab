"""jit'd wrappers: padded-COO graph -> tiled kernel inputs -> one push.

Thin convenience wrappers over the unified propagation backend
(:mod:`repro.core.backend`): build (or accept) a destination-sorted edge
layout via :func:`repro.graph.csr.sort_by_dst` and run one push through the
Pallas kernels — the one-hot matmul for sum reductions, the masked-reduce
variant for min/max semirings.  ``interpret`` defaults to
:func:`repro.core.backend.default_interpret`: the same call compiles to a
Mosaic kernel on TPU and runs the kernel body in interpret mode elsewhere
(how the CPU tests validate it).

Callers issuing repeated pushes should build the layout once
(:func:`repro.core.backend.build_layout`, or the engine's cached
``edge_layouts``) and pass it in — re-sorting per push is the cost this
layout amortizes away.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax

from repro.core.backend import (AnyEdgeLayout, EdgeLayout, build_layout,
                                push)
from repro.graph.graph import GraphState
from repro.kernels.spmv.kernel import CHUNK, TILE_N  # noqa: F401  (re-export)


@functools.partial(
    jax.jit,
    static_argnames=("semiring", "weight", "interpret", "tile_n", "chunk"))
def semiring_push(state: GraphState, values: jax.Array, *,
                  semiring: str = "plus_times",
                  weight: str = "unit",
                  interpret: Optional[bool] = None,
                  layout: Optional[EdgeLayout] = None,
                  tile_n: Optional[int] = None,
                  chunk: Optional[int] = None) -> jax.Array:
    """One kernel-backed push over any registered semiring:
    ``out[v] = ⊕_{(u,v)∈E} values[u] ⊗ weight(u, v)`` (e.g.
    ``semiring="min_plus", weight="length"`` is one Bellman-Ford
    relaxation step)."""
    if layout is None:
        layout = build_layout(state, weight=weight, semiring=semiring,
                              chunk=CHUNK if chunk is None else chunk,
                              tile_n=tile_n)
    return push(values, layout, semiring=semiring, backend="pallas",
                tile_n=tile_n, chunk=chunk, interpret=interpret)


def sharded_semiring_push(state: GraphState, values: jax.Array, *,
                          mesh=None,
                          axes: Optional[Tuple[str, ...]] = None,
                          num_shards: Optional[int] = None,
                          semiring: str = "plus_times",
                          weight: str = "unit",
                          backend: Optional[str] = "pallas",
                          interpret: Optional[bool] = None,
                          layout: Optional[AnyEdgeLayout] = None,
                          slots: Optional[jax.Array] = None,
                          tile_n: Optional[int] = None,
                          chunk: Optional[int] = None) -> jax.Array:
    """:func:`semiring_push` over a device mesh: builds (or accepts) a
    per-shard destination-sorted
    :class:`~repro.core.backend.ShardedEdgeLayout` and runs the
    shard_map-ed partial-push + semiring all-reduce.

    ``mesh=None`` with ``num_shards`` runs the same partition as an
    on-device loop (the reference semantics / bench path).  ``slots``
    optionally overrides the contiguous slot cut with an explicit (e.g.
    rebalanced) slot→shard assignment — see
    :func:`repro.graph.partition.balanced_shard_slots`.  Not jitted —
    layout construction happens per call; repeated pushes should build the
    layout once and pass it via ``layout=``.

    Returns the dense ``semiring.dtype[node_capacity]`` result vector.
    """
    if layout is None:
        from repro.graph.partition import build_sharded_layout
        layout = build_sharded_layout(
            state, mesh=mesh, axes=axes, num_shards=num_shards,
            weight=weight, semiring=semiring, chunk=chunk, slots=slots,
            tile_n=tile_n)
    return push(values, layout, semiring=semiring, backend=backend,
                tile_n=tile_n, chunk=chunk, interpret=interpret)


def pagerank_push(state: GraphState, ranks: jax.Array, *,
                  interpret: Optional[bool] = None,
                  layout: Optional[EdgeLayout] = None,
                  tile_n: Optional[int] = None,
                  chunk: Optional[int] = None) -> jax.Array:
    """One power-iteration push: out[v] = Σ_{(u,v)∈E} ranks[u]/d_out(u) —
    the ``plus_times``/``inv_out`` specialization of
    :func:`semiring_push`."""
    return semiring_push(state, ranks, semiring="plus_times",
                         weight="inv_out", interpret=interpret,
                         layout=layout, tile_n=tile_n, chunk=chunk)
