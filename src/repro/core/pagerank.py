"""PageRank power method — exact and VeilGraph-summarized versions.

Faithful to the paper (§2, §3.1):

- vertex-centric formulation: each vertex u emits ``rank(u)/d_out(u)`` along
  every out-edge; a vertex v sets ``rank(v) = (1-β) + β·Σ incoming`` (the
  Gelly-style normalization the paper describes — the (1-β) teleport term is
  *not* divided by |V| and dangling mass is not redistributed; both are
  available as beyond-paper options).
- the summarized version runs the same update *only for vertices in K*, with
  the frozen big-vertex contribution ``b_in`` added each iteration and all
  non-K ranks carried over unchanged.

The summarized iteration runs in a *compacted* space: hot edges are gathered
into a bounded ``hot_edge_capacity`` buffer and hot nodes are relabelled to
``[0, hot_node_capacity)``, so per-iteration cost is O(|E_K| + |K|) — this
is the paper's O(K) claim realized with XLA static shapes.

Both sweeps route their inner propagation through the unified
:func:`repro.core.backend.push` primitive: pass a cached
:class:`~repro.core.backend.EdgeLayout` (the engine does) and choose
``backend="pallas"`` to run each iteration as one destination-tiled MXU
kernel call, or ``"segment_sum"`` for the sorted XLA fallback.  The
compacted E_K buffer is emitted *destination-sorted* with per-tile ranges
(``ek_row_offsets``), so the ~30-iteration summarized loop body is a pure
kernel call with the sort amortized into summary construction.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import backend as B
from repro.core import spans
from repro.graph.graph import GraphState, inv_out_degree


# --------------------------------------------------------------------------
# Exact PageRank over the full graph
# --------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("num_iters", "beta", "tol", "teleport_by_n", "dangling",
                     "backend"),
)
@spans.scope(spans.EXACT)
def pagerank(
    state: GraphState,
    init_ranks: Optional[jax.Array] = None,
    *,
    beta: float = 0.85,
    num_iters: int = 30,
    tol: float = 0.0,
    teleport_by_n: bool = False,
    dangling: bool = False,
    teleport_v: Optional[jax.Array] = None,
    layout: Optional[B.EdgeLayout] = None,
    backend: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Full power-method PageRank.

    Returns ``(ranks f32[N_cap], iterations_run)``.  With ``tol > 0`` the
    loop exits early once ``‖r_t − r_{t−1}‖₁ < tol`` (bounded by num_iters).

    ``teleport_v`` (f32[N_cap], optional) replaces the uniform teleport with
    a personalization vector: ``rank(v) = (1-β)·t(v) + β·Σ incoming`` —
    seeded/personalized PageRank in the same Gelly-style normalization.

    ``layout`` is an optional cached forward ``weight="inv_out"`` edge
    layout (see :func:`repro.core.backend.build_layout`); without one the
    pallas backend sorts on entry (amortized over the sweep) and the
    segment_sum backend falls back to the unsorted COO push.
    """
    backend_r = B.resolve_backend(backend)
    B.require_layout(layout, weight="inv_out", reverse=False, who="pagerank")
    n_cap = state.node_capacity
    active = state.node_active
    n_active = jnp.maximum(state.num_active_nodes().astype(jnp.float32), 1.0)
    inv_deg = inv_out_degree(state)
    mask = state.edge_mask()
    if teleport_v is not None:
        teleport = (1.0 - beta) * teleport_v
    else:
        teleport = jnp.where(teleport_by_n, (1.0 - beta) / n_active, 1.0 - beta)

    if init_ranks is None:
        if teleport_v is not None:
            r0 = jnp.where(active, teleport_v, 0.0)
        else:
            r0 = jnp.where(active, jnp.where(teleport_by_n, 1.0 / n_active, 1.0), 0.0)
    else:
        r0 = init_ranks

    if layout is None and backend_r == "pallas":
        layout = B.build_layout(state, weight="inv_out")
    edge_w = jnp.where(mask, inv_deg[state.src], 0.0)

    def body(carry):
        i, r, _ = carry
        if layout is None:
            incoming = B.push_coo(r, state.src, state.dst, n_cap, weight=edge_w)
        else:
            incoming = B.push(r, layout, backend=backend_r)
        if dangling:
            dangle = jnp.sum(jnp.where(active & (state.out_deg == 0), r, 0.0))
            incoming = incoming + dangle / n_active
        new_r = jnp.where(active, teleport + beta * incoming, 0.0)
        delta = jnp.sum(jnp.abs(new_r - r))
        return i + 1, new_r, delta

    def cond(carry):
        i, _, delta = carry
        return (i < num_iters) & (delta > tol)

    i, r, _ = jax.lax.while_loop(cond, body, (jnp.int32(0), r0, jnp.float32(jnp.inf)))
    return r, i


# --------------------------------------------------------------------------
# Summarized PageRank over the hot set (the paper's contribution)
# --------------------------------------------------------------------------


def compact_indices(mask: jax.Array, size: int, *, rows: int = 64) -> jax.Array:
    """Indices of True entries of ``mask``, compacted into int32[size].

    Order-scrambled position assignment via a column-major prefix sum:
    positions are ``col_off[j] + (#True in column j over rows < i)``, which
    is a bijection onto [0, popcount).  Two design constraints drive the
    layout:

    - the lax.scan runs over the SHORT ``rows`` axis (64 trips) with the
      long axis as the carry, so under GSPMD the carry stays sharded and
      the partitioner never all-gathers the edge stream (§Perf iteration
      V1: the previous layout scanned 2^21 rows of 512 and made GSPMD
      replicate a 4.3 GB operand per trip — 9.0e15 bytes of HBM traffic
      on the pod-scale veilgraph cell);
    - column offsets need an exclusive cumsum over the (still sharded)
      column-totals vector; a second short-scan level reduces it to a
      cumsum over len/``rows``² elements, which is cheap and local.

    Unused slots hold ``len(mask)`` (out-of-bounds sentinel: gathers clip,
    scatters with mode="drop" ignore).  If more than ``size`` entries are
    set, an arbitrary subset of exactly ``size`` survives — callers detect
    overflow from the mask popcount.
    """
    e = mask.shape[0]

    def col_prefix(m2):
        """scan over rows: per-element prefix count within its column +
        column totals."""
        def body(carry, row):
            return carry + row, carry
        return jax.lax.scan(body, jnp.zeros(m2.shape[1], jnp.int32), m2)

    cols = max((e + rows - 1) // rows, 1)
    e_pad = rows * cols
    m = jnp.pad(mask, (0, e_pad - e)) if e_pad != e else mask
    m2 = m.reshape(rows, cols).astype(jnp.int32)
    col_tot, pos_in_col = col_prefix(m2)               # (cols,), (rows, cols)

    # exclusive cumsum of col_tot via a second short-scan level
    cols2 = max((cols + rows - 1) // rows, 1)
    pad2 = rows * cols2 - cols
    ct = jnp.pad(col_tot, (0, pad2)) if pad2 else col_tot
    ct2 = ct.reshape(rows, cols2)
    grp_tot, pos_in_grp = col_prefix(ct2)              # (cols2,), (rows, cols2)
    grp_off = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(grp_tot)[:-1]])
    col_off = (grp_off[None, :] + pos_in_grp).reshape(-1)[:cols]

    pos = (col_off[None, :] + pos_in_col).reshape(-1)
    tgt = jnp.where(m & (pos < size), pos, size)
    return jnp.full((size,), e, jnp.int32).at[tgt].set(
        jnp.arange(e_pad, dtype=jnp.int32), mode="drop"
    )


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("hot_ids", "num_hot", "ek_src", "ek_dst", "ek_w",
                 "ek_row_offsets", "num_ek", "b_in", "num_eb", "overflow"),
    meta_fields=("weight_mode", "semiring", "tile_n", "tile_chunk",
                 "mesh", "axes"),
)
@dataclasses.dataclass(frozen=True)
class SummaryBuffers:
    """Compacted summary graph G = (K ∪ {B}, E_K ∪ E_B) — static capacities.

    ``hot_ids[i]``   — global id of the i-th hot vertex (i < num_hot)
    ``ek_src/dst``   — *local* endpoints of E_K edges, **sorted by local
                       destination** (invalid slots hold the ``K_cap``
                       sentinel destination and sort last)
    ``ek_w``         — val((u,v)) at summary-build time, in the consuming
                       semiring's dtype (1/d_out(u) for the paper's
                       PageRank summaries; the ⊗-identity for ``"unit"``;
                       per-edge lengths for ``"length"``)
    ``ek_row_offsets`` — int32[K_cap + 1] edge range per local destination
                       over the sorted buffer (the summarized sweep's
                       kernel tile ranges derive from it)
    ``b_in``         — per-hot-vertex frozen big-vertex contribution
                       b_in[z] = Σ_{(w,z): w∉K} rank(w)/d_out(w)
    ``overflow``     — True if |K| or |E_K| exceeded a capacity; the caller
                       must fall back to exact recomputation.
    ``weight_mode``/``semiring`` — static metadata recording how
                       ``ek_w``/``b_in`` were baked, so
                       :func:`repro.core.backend.summary_layout` can reject
                       a consumer running the wrong algebra at trace time
                       (a ``plus_times`` sweep over +∞-baked ``min_plus``
                       buffers would silently produce NaNs).

    **Sharded form** (built by :func:`build_summary` when handed a
    :class:`~repro.core.backend.ShardedEdgeLayout`): the ``ek_*`` buffers
    gain a leading shard axis — ``ek_src/dst/w`` become ``[S, H_s]`` and
    ``ek_row_offsets`` ``[S, K_cap + 1]``, one *locally* destination-sorted
    E_K shard per device, with shard ``j`` owning the contiguous local-id
    range ``[j·⌈K_cap/S⌉, (j+1)·⌈K_cap/S⌉)``.  ``hot_ids``/``b_in`` and the
    counters stay replicated node-space vectors/scalars.  ``mesh``/``axes``
    carry the device mapping (static, mirroring ``ShardedEdgeLayout``);
    :func:`repro.core.backend.summary_layout` then emits a sharded layout so
    every summarized sweep runs as a shard_map partial push + all-reduce.
    """

    hot_ids: jax.Array   # int32[K_cap]
    num_hot: jax.Array   # int32
    ek_src: jax.Array    # int32[H_cap] | int32[S, H_s] (local ids, dst-sorted)
    ek_dst: jax.Array    # int32[H_cap] | int32[S, H_s] (sorted; K_cap = padding)
    ek_w: jax.Array      # dtype[H_cap] | dtype[S, H_s] (semiring dtype)
    ek_row_offsets: jax.Array  # int32[K_cap + 1] | int32[S, K_cap + 1]
    num_ek: jax.Array    # int32
    b_in: jax.Array      # dtype[K_cap]
    num_eb: jax.Array    # int32  (size of E_B, for the paper's edge-ratio stat)
    overflow: jax.Array  # bool
    weight_mode: str = "inv_out"
    semiring: str = "plus_times"
    # tuned kernel geometry inherited from the full-graph layout the summary
    # was built against; summary_layout() stamps it onto the E_K layout so
    # summarized sweeps pick the autotuned tile/chunk without user knobs
    tile_n: Optional[int] = None
    tile_chunk: Optional[int] = None
    mesh: Optional["jax.sharding.Mesh"] = None
    axes: Tuple[str, ...] = ()

    @property
    def sharded(self) -> bool:
        """True for the stacked per-shard E_K form (see class docstring)."""
        return self.ek_src.ndim == 2

    @property
    def num_shards(self) -> Optional[int]:
        """Shard count of the sharded form, ``None`` for flat summaries."""
        return self.ek_src.shape[0] if self.sharded else None


def _zero_annihilates(s, weight: str) -> bool:
    """True where ``s.zero ⊗ w = s.zero`` for every weight a layout of
    ``weight`` mode holds: ``"unit"`` bakes the ⊗-identity, and (+, ×) and
    (min, +) absorb ⊕'s identity for any finite weight.  ``max_times`` and
    ``min_min`` over lengths do not (−∞ · 0, min(+∞, w))."""
    return weight == "unit" or (s.add, s.mul) in (("sum", "times"),
                                                  ("min", "plus"))


def _build_summary_sharded(
    state: GraphState,
    ranks_prev: jax.Array,
    hot_mask: jax.Array,
    *,
    hot_node_capacity: int,
    hot_edge_capacity: int,
    weight: str,
    layout: "B.ShardedEdgeLayout",
    backend: Optional[str],
    s,
    shard_bucket_capacity: Optional[int] = None,
) -> SummaryBuffers:
    """Mesh-native summary construction: a distributed bucket sort over the
    shard axis, so no stage ever materializes a replicated O(E) buffer.

    The replicated construction compacts E_K with full-edge-space gathers
    (``e_src[ek_idx]`` over the whole COO buffer) — under GSPMD edge
    sharding those gathers lower to all-gathers of the edge stream, the
    pod-scale wall-clock ceiling this path removes.  Stages, all shard-local
    except the one exchange:

    1. **local selection** — each shard masks its own locally-sorted stream
       for E_K / E_B membership and relabels endpoints through the
       replicated ``local_of`` node vector (O(N) node state stays
       replicated; O(E) edge state never leaves its shard);
    2. **local dst sort** — one axis-1 argsort per shard by *local
       destination* groups each shard's E_K edges into ``S`` contiguous
       destination buckets (bucket ``j`` = local ids ``[j·W, (j+1)·W)``,
       ``W = ⌈K_cap/S⌉``) and destination-sorts within each bucket in the
       same pass;
    3. **capacity-padded all-to-all** — each (source shard, bucket) block is
       padded to ``C = ⌈H_cap/S⌉`` slots and the ``[S_in, S_out, C]`` stack
       is transposed on its leading axes, which under GSPMD *is* the
       all-to-all collective; shard ``j`` now owns every E_K edge whose
       destination falls in its bucket;
    4. **local merge** — one axis-1 argsort per shard merges its ``S``
       sorted incoming blocks; ``ek_row_offsets`` derive shard-locally by
       ``searchsorted`` (never a global sort).

    A block exceeding ``C`` raises the ``overflow`` flag (alongside the
    usual ``|K|``/``|E_K|`` capacity checks) and the caller falls back to
    exact recomputation — ``compact_indices``'s order-scrambled local ids
    spread destinations across buckets, so balanced blocks are the common
    case.  ``b_in`` runs through the sharded :func:`repro.core.backend.push`
    with the E_B mask, exactly like the flat path with a cached layout.

    ``shard_bucket_capacity`` overrides ``C = ⌈H_cap/S⌉`` with a tighter
    per-(source shard, bucket) slot count: the post-exchange per-shard E_K
    buffer is ``S·C`` slots, so the default bound grows with H_cap even
    when hot edges are well spread — a workload with balanced buckets can
    cut the per-device footprint to ``S · shard_bucket_capacity`` and rely
    on the ``overflow`` flag (→ exact fallback) for the rare skewed batch.
    """
    n_cap = state.node_capacity
    k_cap = hot_node_capacity
    h_cap = hot_edge_capacity
    num_shards = layout.num_shards
    e_pad = layout.dst.shape[1]
    if shard_bucket_capacity is None:
        bucket_cap = -(-h_cap // num_shards)  # C: per (src-shard, bucket)
    else:
        if shard_bucket_capacity < 1:
            raise ValueError(
                f"shard_bucket_capacity must be >= 1; got "
                f"{shard_bucket_capacity}")
        bucket_cap = shard_bucket_capacity
    bucket_w = -(-k_cap // num_shards)     # W: local-dst ids per bucket
    w_dtype = jnp.dtype(s.dtype)
    s_zero = jnp.asarray(s.zero, w_dtype)

    with spans.scope(spans.SUMMARY_SELECT):
        # ---- hot-vertex relabelling (replicated node space, same as flat) ---
        hot_ids = compact_indices(hot_mask, k_cap)
        num_hot = jnp.sum(hot_mask.astype(jnp.int32))
        local_valid = jnp.arange(k_cap, dtype=jnp.int32) < num_hot
        local_of = jnp.zeros((n_cap,), jnp.int32).at[hot_ids].set(
            jnp.arange(k_cap, dtype=jnp.int32), mode="drop")

        # ---- per-shard E_K / E_B selection over the sorted streams ----------
        dst_c = jnp.minimum(layout.dst, n_cap - 1)  # clip the n_cap sentinel
        src_hot = hot_mask[layout.src]
        dst_hot = hot_mask[dst_c]
        ek_mask = layout.valid & src_hot & dst_hot
        eb_mask = layout.valid & (~src_hot) & dst_hot
        num_ek = jnp.sum(ek_mask.astype(jnp.int32))
        num_eb = jnp.sum(eb_mask.astype(jnp.int32))

    with spans.scope(spans.SUMMARY_BIN):
        # ---- frozen big-vertex boundary: sharded push over the E_B mask -----
        # ranks_prev may be batched [B, N] (shared-summary serving): the push
        # and the hot-id gather both batch along the leading axis, so b_in
        # becomes [B, K_cap] while E_K stays shared across the batch
        b_in_global = B.push(ranks_prev, layout, backend=backend, mask=eb_mask,
                             semiring=s)
        b_in = jnp.where(local_valid, b_in_global[..., hot_ids], s_zero)

    with spans.scope(spans.SUMMARY_ORDER):
        # ---- stage 2: shard-local relabel + destination sort ----------------
        # layout.weight already holds the baked ⊗-operand in stream order
        # (the single bake both paths share), so E_K weights are a masked
        # copy
        lsrc = jnp.where(ek_mask, local_of[layout.src], 0)
        # the k_cap sentinel sorts last
        ldst = jnp.where(ek_mask, local_of[dst_c], k_cap)
        # keep the layout's (possibly bf16-compressed) storage dtype: a f32
        # s_zero would silently promote ek_w back to f32
        s_zero_w = s_zero.astype(layout.weight.dtype)
        ek_w = jnp.where(ek_mask, layout.weight, s_zero_w)
        perm = jnp.argsort(ldst, axis=1, stable=True)
        take = lambda x: jnp.take_along_axis(x, perm, axis=1)
        lsrc, ldst, ek_w = take(lsrc), take(ldst), take(ek_w)

        # ---- stage 3: capacity-padded blocks + all-to-all exchange ----------
        bounds = jnp.minimum(
            jnp.arange(num_shards + 1, dtype=jnp.int32) * bucket_w, k_cap)
        off = jax.vmap(lambda d: jnp.searchsorted(
            d, bounds, side="left").astype(jnp.int32))(ldst)
        n_block = off[:, 1:] - off[:, :-1]              # [S_in, S_out] counts
        block_overflow = jnp.any(n_block > bucket_cap)
        lane = jnp.arange(bucket_cap, dtype=jnp.int32)
        idx = jnp.minimum(
            off[:, :-1, None] + lane[None, None, :], e_pad - 1
        ).reshape(num_shards, num_shards * bucket_cap)
        block_valid = lane[None, None, :] < jnp.minimum(n_block,
                                                        bucket_cap)[:, :, None]

        if layout.mesh is not None:
            # explicit collective: shard_map + lax.all_to_all.  (Left to GSPMD,
            # the leading-axes transpose of the block stack lowers as an
            # all-gather of the whole [S, S, C] array — 64 GiB/device at the
            # pod-scale dry-run shape — instead of the O(C·S) exchange.)
            from jax.sharding import PartitionSpec as _P

            def _swap(b):
                # per device: [S_loc, S, C] -> split buckets across devices,
                # concat source shards -> [S, S_loc, C] -> local transpose
                b = jax.lax.all_to_all(b, layout.axes, split_axis=1,
                                       concat_axis=0, tiled=True)
                return jnp.swapaxes(b, 0, 1)

            transpose_blocks = jax.shard_map(
                _swap, mesh=layout.mesh, in_specs=_P(layout.axes),
                out_specs=_P(layout.axes), check_vma=False)
        else:
            transpose_blocks = lambda b: jnp.swapaxes(b, 0, 1)

        def exchange(x, fill):
            """[S_in, E_pad] stream -> [S_out, S_in·C] received blocks: gather
            the per-bucket blocks shard-locally, then exchange the leading
            (source shard, bucket) axes — ``lax.all_to_all`` under a mesh, a
            plain transpose on the single-device reference path."""
            g = jnp.take_along_axis(x, idx, axis=1).reshape(
                num_shards, num_shards, bucket_cap)
            g = jnp.where(block_valid, g, fill)
            return transpose_blocks(g).reshape(
                num_shards, num_shards * bucket_cap)

        ek_src2 = exchange(lsrc, 0)
        ek_dst2 = exchange(ldst, k_cap)
        ek_w2 = exchange(ek_w, s_zero_w)

        # ---- stage 4: shard-local merge sort + row offsets ------------------
        perm2 = jnp.argsort(ek_dst2, axis=1, stable=True)
        take2 = lambda x: jnp.take_along_axis(x, perm2, axis=1)
        ek_src2, ek_dst2, ek_w2 = take2(ek_src2), take2(ek_dst2), take2(ek_w2)
        ek_row_offsets = jax.vmap(lambda d: jnp.searchsorted(
            d, jnp.arange(k_cap + 1, dtype=jnp.int32),
            side="left").astype(jnp.int32))(ek_dst2)

        if layout.mesh is not None:
            # pin the summary shards to the layout's mesh placement so the
            # consuming shard_map never redistributes them (and the partitioner
            # keeps every stage above shard-local)
            from jax.sharding import NamedSharding, PartitionSpec
            sh = NamedSharding(layout.mesh, PartitionSpec(layout.axes))
            pin = lambda x: jax.lax.with_sharding_constraint(x, sh)
            ek_src2, ek_dst2, ek_w2, ek_row_offsets = map(
                pin, (ek_src2, ek_dst2, ek_w2, ek_row_offsets))

    return SummaryBuffers(
        hot_ids=hot_ids,
        num_hot=num_hot,
        ek_src=ek_src2,
        ek_dst=ek_dst2,
        ek_w=ek_w2,
        ek_row_offsets=ek_row_offsets,
        num_ek=num_ek,
        b_in=b_in,
        num_eb=num_eb,
        overflow=(num_hot > k_cap) | (num_ek > h_cap) | block_overflow,
        weight_mode=weight,
        semiring=s.name,
        tile_n=layout.tile_n,
        tile_chunk=layout.tile_chunk,
        mesh=layout.mesh,
        axes=layout.axes,
    )


@functools.partial(
    jax.jit,
    static_argnames=("hot_node_capacity", "hot_edge_capacity", "weight",
                     "reverse", "backend", "semiring",
                     "shard_bucket_capacity"),
)
def build_summary(
    state: GraphState,
    ranks_prev: jax.Array,
    hot_mask: jax.Array,
    *,
    hot_node_capacity: int,
    hot_edge_capacity: int,
    weight: str = "inv_out",
    reverse: bool = False,
    layout: Optional[B.EdgeLayout] = None,
    backend: Optional[str] = None,
    semiring: str = "plus_times",
    lengths: Optional[jax.Array] = None,
    shard_bucket_capacity: Optional[int] = None,
    ek_mask: Optional[jax.Array] = None,
) -> SummaryBuffers:
    """Construct the big-vertex summary (§3.1) into bounded buffers.

    Generalized beyond PageRank so other :class:`StreamingAlgorithm` plugins
    can reuse the same compaction machinery:

    - ``weight``: ``"inv_out"`` (PageRank-style ``val((u,v)) = 1/d_out(u)``),
      ``"unit"`` (the semiring's ⊗-identity — HITS / Katz / CC label-min),
      or ``"length"`` (per-edge lengths for SSSP-style relaxations).
      Length resolution: a passed ``layout``'s baked lengths win (mapped
      back to slot order through ``layout.order``, so E_K and the ``b_in``
      boundary can never disagree), else the explicit ``lengths`` array
      (dtype[E_cap], indexed by original edge slot), else 1 per edge.
    - ``reverse``: build the summary over the *transposed* edge set — the
      emitting endpoint is the original ``dst``.  ``b_in[z]`` then freezes
      the contribution of non-hot vertices reached by z's *out*-edges (the
      hub-update direction in HITS, the symmetric pass in CC).
      ``weight="inv_out"`` is only meaningful in the forward orientation.
    - ``layout``: optional cached full-graph edge layout **matching this
      summary's** ``weight``/``reverse``/``semiring`` (the engine passes
      one per ``StreamingAlgorithm.layout_specs`` entry); the frozen
      big-vertex pass then runs through the sorted
      :func:`repro.core.backend.push` instead of an unsorted segment reduce.
    - ``semiring``: the (⊕, ⊗) algebra of the consuming summarized sweep
      (:mod:`repro.core.semiring`).  ``ek_w`` and ``b_in`` take the
      semiring's dtype, invalid slots its ⊕-identity, and the frozen
      big-vertex pass ⊕-reduces cold contributions (a *min* over frozen
      cold distances/labels for ``min_plus``/``min_min``, the paper's sum
      for ``plus_times``).

    ``ranks_prev`` is whatever state vector the frozen big-vertex
    contribution should be computed from (previous PageRank ranks, previous
    hub scores, previous distances/labels, …).  It may be a batched
    ``[B, N]`` matrix (B queries sharing ONE hot set / E_K structure — the
    serving engine's shared summary): the structural buffers are computed
    once while ``b_in`` becomes per-query ``[B, K_cap]`` via one batched
    push.  ``shard_bucket_capacity`` tightens the sharded construction's
    per-(shard, bucket) slot count — see :func:`_build_summary_sharded`.

    ``ek_mask`` (bool[E_cap], optional) is E_K as the caller already has it:
    the live edges with both endpoints in ``hot_mask`` (one mask for both
    orientations).  Handed over, E_K is not rebuilt from ``hot_mask`` at
    every edge.  ``num_eb`` comes from the degree counters either way:
    the live edges into K are exactly E_K ∪ E_B.  The sharded
    construction ignores it.

    Handed a :class:`~repro.core.backend.ShardedEdgeLayout` (the engine does
    when configured with a mesh), construction itself runs sharded — a
    distributed bucket sort over the shard axis producing the stacked
    per-shard E_K form of :class:`SummaryBuffers` (see
    :func:`_build_summary_sharded`), with zero replicated edge-space
    gathers; the consuming summarized sweeps then run through the sharded
    push automatically.
    """
    if weight == "length" and lengths is None and layout is None:
        lengths = state.edge_len  # streamed per-edge lengths, if any
    s = B.validate_weight_spec(weight, reverse=reverse, semiring=semiring,
                               lengths=lengths,
                               edge_capacity=state.edge_capacity)
    B.require_layout(layout, weight=weight, reverse=reverse,
                     who="build_summary", semiring=s)
    if isinstance(layout, B.ShardedEdgeLayout):
        # sharded construction: the layout's baked weights are the single
        # source of truth (like the flat path's layout.order back-map), so
        # an explicit `lengths` array never overrides them
        return _build_summary_sharded(
            state, ranks_prev, hot_mask,
            hot_node_capacity=hot_node_capacity,
            hot_edge_capacity=hot_edge_capacity,
            weight=weight, layout=layout, backend=backend, s=s,
            shard_bucket_capacity=shard_bucket_capacity)
    n_cap = state.node_capacity
    k_cap = hot_node_capacity
    h_cap = hot_edge_capacity
    mask = state.edge_mask()
    inv_deg = inv_out_degree(state)
    w_dtype = jnp.dtype(s.dtype)
    s_zero = jnp.asarray(s.zero, w_dtype)
    if weight == "length" and layout is not None and layout.order is not None:
        # the layout's baked lengths are the single source of truth: map
        # them back to edge-slot order so E_K cannot silently diverge from
        # the b_in boundary pass (e.g. hop counts vs real lengths)
        lengths = jnp.full((state.edge_capacity,), s_zero).at[
            layout.order].set(layout.weight, mode="drop")

    e_src, e_dst = ((state.dst, state.src) if reverse
                    else (state.src, state.dst))
    with spans.scope(spans.SUMMARY_SELECT):
        if ek_mask is None:
            ek_mask = mask & hot_mask[e_src] & hot_mask[e_dst]
        num_hot = jnp.sum(hot_mask.astype(jnp.int32))
        num_ek = jnp.sum(ek_mask.astype(jnp.int32))
        # the live edges into K are E_K ∪ E_B, so |E_B| is K's degree into
        # the summary less |E_K|: node-sized, no edge gather
        deg_in = state.out_deg if reverse else state.in_deg
        num_eb = jnp.sum(jnp.where(hot_mask, deg_in, 0)) - num_ek
        overflow = (num_hot > k_cap) | (num_ek > h_cap)

        # ---- hot-vertex relabelling: global id -> local id ------------------
        # Padding entries hold an out-of-bounds sentinel: gathers clip (and are
        # masked by local_valid), scatters use mode="drop" so padding never
        # clobbers a real slot.
        hot_ids = compact_indices(hot_mask, k_cap)
        local_valid = jnp.arange(k_cap, dtype=jnp.int32) < num_hot
        local_of = jnp.zeros((n_cap,), jnp.int32)
        local_of = local_of.at[hot_ids].set(
            jnp.arange(k_cap, dtype=jnp.int32), mode="drop"
        )

    with spans.scope(spans.SUMMARY_BIN):
        # ---- frozen big-vertex contribution (computed once per query) -------
        # b_in_global[z] = ⊕_{(w,z) ∈ E_B} rank_prev(w) ⊗ val(w)
        # one O(E) push; with a cached layout the reduce reuses the amortized
        # edge sort, and only b_in_global[hot_ids] is read, so the layout
        # push selects E_B by a cold emitter alone, with no receiver test
        if layout is None:
            eb_mask = mask & (~hot_mask[e_src]) & hot_mask[e_dst]
            if weight == "inv_out":
                coo_w = inv_deg[e_src]
            elif weight == "length":
                coo_w = (jnp.ones_like(e_src, dtype=w_dtype) if lengths is None
                         else lengths.astype(w_dtype))
            else:  # "unit": ⊗-identity — skip the combine entirely
                coo_w = None
            b_in_global = B.push_coo(ranks_prev, e_src, e_dst, n_cap,
                                     weight=coo_w, mask=eb_mask, semiring=s)
        elif _zero_annihilates(s, layout.weight_mode):
            # a hot emitter set to s.zero contributes s.zero ⊗ w = s.zero,
            # an exact ⊕-identity: the mask moves from the edges to the
            # vertices and b_in stays bitwise the same
            b_in_global = B.push(jnp.where(hot_mask, s_zero, ranks_prev),
                                 layout, backend=backend, semiring=s)
        else:
            b_in_global = B.push(ranks_prev, layout, backend=backend,
                                 mask=~hot_mask[layout.src], semiring=s)
        # batched ranks_prev [B, N] → b_in [B, K_cap] (see sharded path note)
        b_in = jnp.where(local_valid, b_in_global[..., hot_ids], s_zero)

    with spans.scope(spans.SUMMARY_SELECT):
        # ---- compact E_K into the bounded buffer ----------------------------
        ek_idx = compact_indices(ek_mask, h_cap)
        ek_valid = (jnp.arange(h_cap, dtype=jnp.int32)
                    < jnp.minimum(num_ek, h_cap))
        gsrc = e_src[ek_idx]
        gdst = e_dst[ek_idx]
        # val((u,v)) = 1/d_out(u) *including* edges that leave K (paper §3.1:
        # discarded out-edges still count in the emitting degree).
        if weight == "inv_out":
            ek_w = jnp.where(ek_valid, inv_deg[gsrc], 0.0)
        elif weight == "length":
            # ek_idx holds original edge slots, so explicit lengths gather
            # directly (clipped gathers on padding slots are masked by
            # ek_valid)
            per_edge = (jnp.asarray(1, w_dtype) if lengths is None
                        else lengths.astype(w_dtype)[jnp.minimum(
                            ek_idx, lengths.shape[0] - 1)])
            ek_w = jnp.where(ek_valid, per_edge, s_zero)
        else:  # "unit": the semiring's ⊗-identity
            ek_w = jnp.where(ek_valid, jnp.asarray(s.one, w_dtype), s_zero)
        ek_src = jnp.where(ek_valid, local_of[gsrc], 0)
        ek_dst = jnp.where(ek_valid, local_of[gdst], 0)

    with spans.scope(spans.SUMMARY_ORDER):
        # ---- destination-sort the compacted buffer --------------------------
        # One argsort over H_cap per query makes every summarized iteration a
        # pure sorted push (kernel tile ranges derive from ek_row_offsets);
        # invalid slots take the K_cap sentinel destination and sort last.
        ek_key = jnp.where(ek_valid, ek_dst, k_cap)
        ek_order = jnp.argsort(ek_key, stable=True)
        ek_dst_s = ek_key[ek_order]
        ek_row_offsets = jnp.searchsorted(
            ek_dst_s, jnp.arange(k_cap + 1, dtype=jnp.int32), side="left"
        ).astype(jnp.int32)

    return SummaryBuffers(
        hot_ids=hot_ids,
        num_hot=num_hot,
        ek_src=ek_src[ek_order],
        ek_dst=ek_dst_s,
        ek_w=ek_w[ek_order],
        ek_row_offsets=ek_row_offsets,
        num_ek=num_ek,
        b_in=b_in,
        num_eb=num_eb,
        overflow=overflow,
        weight_mode=weight,
        semiring=s.name,
        tile_n=None if layout is None else layout.tile_n,
        tile_chunk=None if layout is None else layout.tile_chunk,
    )


@functools.partial(
    jax.jit, static_argnames=("num_iters", "beta", "tol", "backend")
)
def summarized_pagerank(
    summary: SummaryBuffers,
    ranks_prev: jax.Array,
    *,
    beta: float = 0.85,
    num_iters: int = 30,
    tol: float = 0.0,
    teleport_v: Optional[jax.Array] = None,
    backend: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Power iteration restricted to the summary graph (§3.1).

    Per iteration, for every hot vertex z (local id):
        rank(z) = (1-β)·t(z) + β·( Σ_{(u,z)∈E_K} rank(u)·val((u,z)) + b_in(z) )
    with t ≡ 1 for classic PageRank or the global personalization vector
    ``teleport_v`` for seeded PageRank.  Cold ranks are carried over
    unchanged.  Returns the *global* rank vector and the number of
    iterations run.

    The loop body is one :func:`repro.core.backend.push` over the summary's
    pre-sorted E_K layout — a single kernel call per iteration on the
    pallas backend.
    """
    backend_r = B.resolve_backend(backend)
    k_cap = summary.hot_ids.shape[0]
    local_valid = jnp.arange(k_cap, dtype=jnp.int32) < summary.num_hot
    r_local0 = jnp.where(local_valid, ranks_prev[summary.hot_ids], 0.0)
    if teleport_v is not None:
        t_local = jnp.where(local_valid, teleport_v[summary.hot_ids], 0.0)
    else:
        t_local = 1.0
    layout = B.summary_layout(summary)

    def body(carry):
        i, r, _ = carry
        incoming = B.push(r, layout, backend=backend_r)
        new_r = jnp.where(
            local_valid,
            (1.0 - beta) * t_local + beta * (incoming + summary.b_in),
            0.0,
        )
        delta = jnp.sum(jnp.abs(new_r - r))
        return i + 1, new_r, delta

    def cond(carry):
        i, _, delta = carry
        return (i < num_iters) & (delta > tol)

    i, r_local, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), r_local0, jnp.float32(jnp.inf))
    )

    # scatter hot results back into the global vector; padding entries of
    # hot_ids are out of bounds and dropped.
    ranks = ranks_prev.at[summary.hot_ids].set(r_local, mode="drop")
    return ranks, i


@functools.partial(
    jax.jit, static_argnames=("num_iters", "beta", "tol", "backend")
)
def summarized_pagerank_batched(
    summary: SummaryBuffers,
    ranks_prev: jax.Array,
    *,
    beta: float = 0.85,
    num_iters: int = 30,
    tol: float = 0.0,
    teleport_v: Optional[jax.Array] = None,
    row_mask: Optional[jax.Array] = None,
    backend: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Batched :func:`summarized_pagerank`: B queries, one shared summary.

    ``ranks_prev`` / ``teleport_v`` are ``[B, N]`` matrices (per-slot
    personalization vectors); the summary is shared across the batch —
    ``b_in`` may be the per-query ``[B, K_cap]`` form
    :func:`build_summary` emits for batched ``ranks_prev``.  Each
    iteration runs ONE batched push over the pre-sorted E_K layout (the
    ``[B, chunk] @ [chunk, tile_n]`` MXU path on the pallas backend).

    ``row_mask`` (bool[B], optional) is the serving engine's per-slot
    convergence mask: rows with ``False`` carry their state unchanged and
    report zero delta, so finished/vacant slots neither drift nor keep the
    wave from converging.

    Returns ``(ranks [B, N], iterations, row_delta [B])`` — ``row_delta``
    is each row's final L1 step size, the per-slot convergence signal.
    """
    backend_r = B.resolve_backend(backend)
    batch = ranks_prev.shape[0]
    k_cap = summary.hot_ids.shape[0]
    local_valid = jnp.arange(k_cap, dtype=jnp.int32) < summary.num_hot
    r_local0 = jnp.where(local_valid, ranks_prev[:, summary.hot_ids], 0.0)
    if teleport_v is not None:
        t_local = jnp.where(local_valid, teleport_v[:, summary.hot_ids], 0.0)
    else:
        t_local = 1.0
    keep = (jnp.ones((batch,), bool) if row_mask is None
            else row_mask)[:, None]
    layout = B.summary_layout(summary)

    def body(carry):
        i, r, _ = carry
        incoming = B.push(r, layout, backend=backend_r)
        new_r = jnp.where(
            local_valid,
            (1.0 - beta) * t_local + beta * (incoming + summary.b_in),
            0.0,
        )
        new_r = jnp.where(keep, new_r, r)
        delta = jnp.sum(jnp.abs(new_r - r), axis=1)
        return i + 1, new_r, delta

    def cond(carry):
        i, _, delta = carry
        return (i < num_iters) & (jnp.max(delta) > tol)

    i, r_local, delta = jax.lax.while_loop(
        cond, body,
        (jnp.int32(0), r_local0, jnp.full((batch,), jnp.inf, jnp.float32)))

    ranks = ranks_prev.at[:, summary.hot_ids].set(r_local, mode="drop")
    ranks = jnp.where(keep, ranks, ranks_prev)
    return ranks, i, delta
