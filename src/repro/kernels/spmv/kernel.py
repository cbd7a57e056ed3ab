"""Pallas TPU kernel: destination-tiled SpMV push (every sweep's hot loop).

TPU adaptation of the paper's vertex-centric message push.  A GPU
implementation would scatter with atomics; TPUs have no scatter-atomics, so
the kernel is restructured around the MXU:

- edges are sorted by destination (csr.sort_by_dst, amortized over ~30
  power iterations per query and — via the engine's layout cache — across
  every query between two applied update batches);
- the destination space is tiled into tile_n-wide output tiles; each grid
  step owns one tile and consumes only its edge range [tile_start[t],
  tile_start[t+1]);
- within a chunk of ``chunk`` edges, the scatter-add becomes a one-hot
  matmul: acc += contrib @ onehotᵀ — a (rows, chunk)·(tile_n, chunk)ᵀ
  product that runs on the MXU instead of a serial scatter (the classic
  TPU segment-sum-by-matmul trick);
- per-edge contributions (e.g. rank[src] / out_deg[src]) are gathered
  OUTSIDE the kernel by XLA (TPU gathers are efficient; VMEM-resident random
  gather inside the kernel is not), so the kernel input is a dense
  contribution stream — the kernel is therefore algorithm-agnostic: PageRank
  weights, HITS unit weights and summarized E_K weights all arrive pre-baked
  in the stream.

Two kernel variants share that structure, selected by the reduction of the
semiring the sweep runs over (:mod:`repro.core.semiring`):

- :func:`spmv_push` — the ``sum``-reduce (``plus_times``) fast path: the
  scatter-add becomes a one-hot matmul on the MXU (f32 only);
- :func:`spmv_reduce_push` — the *segmented-scan* variant for non-additive
  reductions (``min``/``max`` over f32 or i32).  Within each chunk the
  per-destination reduce runs as a Hillis-Steele segmented scan whose
  same-run test is a single compare against the layout's precomputed
  ``rank`` stream (``rank[i]`` = position of edge *i* inside its
  destination run, so "my predecessor at distance ``off`` is in my run"
  is just ``rank >= off`` — no second scan over run-open flags).  Each
  run's reduced value is then scattered through the same one-hot matmul
  as the sum path, encoded so the MXU product stays *bitwise exact*:

  - floats ride as ``[finite value (0 if ±∞), ±∞ sign flag, count]`` rows
    — at most one selected run end per destination column, so each column
    sums exactly one product and ``0·∞`` never reaches the MXU;
  - int32 rides as ``[high 16 bits, low 16 bits, count]`` rows — both
    halves are < 2¹⁶ and therefore exact in f32, and the column
    reconstruction ``(hi << 16) | lo`` recovers every int32 bit pattern.

  A zero count column reconstructs the reduce identity, matching XLA's
  ``segment_min``/``segment_max`` empty-segment convention.  Runs spanning
  a chunk boundary reduce to one partial per chunk; the accumulator's ⊕
  recombines them, and min/max are reassociation-exact so the split
  changes nothing bitwise.

Layout on the chip
------------------
Every per-edge stream enters ``pallas_call`` as a lane-dense 2-D array —
``[1, E_pad]`` for shared streams, ``[B, E_pad]`` for batched
contributions — so every in-kernel vector is 2-D with edges on the lanes.
``tile_start`` is scalar-prefetched into SMEM.  Each tile's chunk loads
start at its range start rounded *down* to a chunk multiple (``chunk`` is
a multiple of 128, so every DMA slice is lane-tile aligned) and both ends
of the range are masked; the layout's one spare chunk of padding
(:func:`repro.core.backend.padded_length`) keeps the last load inside the
buffer.  Loads are **double-buffered**: chunk *i+1* is DMA-copied into
the other slot of a ``(2, rows, chunk)`` VMEM scratch while chunk *i* is
consumed.  Interpret mode runs the same DMA path, which is how the CPU
tests validate it.  The scan's shifts and the run-end test use
``pltpu.roll`` along the lanes plus an iota mask.

``tile_n``/``chunk`` are parameters (module constants are only the
defaults): the per-shape autotuner (:mod:`repro.kernels.spmv.autotune`)
picks them per (E_pad, N, B, dtype, reduce, platform) and the layout cache
carries the tuned geometry.  VMEM budget per step: 2 buffered chunks per
stream + the (tile_n × chunk) one-hot + accumulators — see
:func:`repro.kernels.spmv.autotune.modeled_push_cost` for the analytic
model the tuner prunes with.

Batched (multi-query) variants
------------------------------
:func:`spmv_push_batched` and :func:`spmv_reduce_push_batched` accept a
``[B, E_pad]`` contribution matrix — B independent value vectors pushed
through ONE shared edge stream (the serving engine's wave step).  The one-
hot product becomes a true ``[B, chunk] @ [chunk, tile_n]`` MXU matmul, so
the scatter's fixed cost (edge loads, one-hot build) is amortized over all
B queries.  The single-query kernels are the ``B = 1`` case of the same
bodies.  The reduce variant shrinks its chunk 128-granularly (largest fit,
not halving) so the scan buffers stay inside the VMEM budget; min/max are
reassociation-exact, so each batch row stays bitwise equal to the
single-query kernel.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 512
TILE_N = 256

#: the one-hot products must be exact in f32 (the reduce encodings carry
#: full f32 values and 16-bit integer halves through the MXU)
_DOT = functools.partial(
    jax.lax.dot_general, dimension_numbers=(((1,), (1,)), ((), ())),
    precision=jax.lax.Precision.HIGHEST,
    preferred_element_type=jnp.float32)


def _lane_iota(n: int) -> jax.Array:
    """int32 ``[1, n]`` lane index."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)


def _onehot_t(d_local: jax.Array, tile_n: int) -> jax.Array:
    """``[tile_n, chunk]`` f32 one-hot of the ``[1, chunk]`` local
    destinations (out-of-tile slots carry ``tile_n`` and match no row)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (tile_n, d_local.shape[1]), 0)
    return (rows == d_local).astype(jnp.float32)


def _make_kernel(tile_n: int, chunk: int, n_streams: int, compute, acc0):
    """Kernel body over one output tile of ``n_streams`` input streams.

    ``compute(loaded, valid, d_local, acc)`` folds one chunk (the loaded
    ``[rows, chunk]`` blocks, the ``[1, chunk]`` in-range mask and local
    destinations) into the ``acc0()``-initialized accumulator.
    """

    def kernel(tile_start_ref, *refs):
        ins = refs[:n_streams]
        out_ref = refs[n_streams]
        bufs = refs[n_streams + 1:2 * n_streams + 1]
        sems = refs[2 * n_streams + 1:]
        t = pl.program_id(0)
        start = tile_start_ref[t]
        end = tile_start_ref[t + 1]
        base = t * tile_n
        lo0 = (start // chunk) * chunk          # lane-tile aligned DMA start
        n_chunks = jnp.where(end > start, pl.cdiv(end - lo0, chunk), 0)

        def dma(i, slot):
            lo = pl.multiple_of(lo0 + i * chunk, chunk)
            return [pltpu.make_async_copy(ref.at[:, pl.ds(lo, chunk)],
                                          buf.at[slot], sem.at[slot])
                    for ref, buf, sem in zip(ins, bufs, sems)]

        @pl.when(n_chunks > 0)
        def _():
            for cp in dma(0, 0):
                cp.start()

        def body(i, acc):
            slot = jax.lax.rem(i, 2)

            @pl.when(i + 1 < n_chunks)
            def _():
                for cp in dma(i + 1, 1 - slot):
                    cp.start()

            for cp in dma(i, slot):
                cp.wait()
            loaded = [buf[slot] for buf in bufs]
            pos = lo0 + i * chunk + _lane_iota(chunk)
            valid = (pos >= start) & (pos < end)
            d_local = jnp.where(valid, loaded[1] - base, tile_n)
            return compute(loaded, valid, d_local, acc)

        out_ref[...] = jax.lax.fori_loop(0, n_chunks, body, acc0())

    return kernel


def _tiled_rows(batch: int) -> int:
    """Batch rows as the chip tiles them: a power of two below 8, a
    multiple of 8 above (Mosaic refuses e.g. a 3-row DMA slice)."""
    return 1 << (batch - 1).bit_length() if batch <= 8 else -(-batch // 8) * 8


def _tiled_call(compute, streams, tile_start, *, init, num_tiles, tile_n,
                chunk, out_dtype, interpret):
    """``pallas_call`` of the :func:`_make_kernel` body over the tile grid:
    ``tile_start`` in SMEM, every ``[rows, E_pad]`` stream left in HBM with
    a double-buffered ``(2, rows, chunk)`` VMEM scratch, ``[rows, tile_n]``
    output blocks starting at ``init``.  The batch rows of ``streams[0]``
    are padded to :func:`_tiled_rows` and dropped again from the result."""
    batch = streams[0].shape[0]
    rows = _tiled_rows(batch)
    streams = [jnp.pad(streams[0], ((0, rows - batch), (0, 0)))] + streams[1:]
    kernel = _make_kernel(tile_n, chunk, len(streams), compute,
                          lambda: jnp.full((rows, tile_n), init, out_dtype))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(num_tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(streams),
            out_specs=pl.BlockSpec((rows, tile_n), lambda t, ts: (0, t)),
            scratch_shapes=(
                [pltpu.VMEM((2, s.shape[0], chunk), s.dtype) for s in streams]
                + [pltpu.SemaphoreType.DMA((2,)) for _ in streams])),
        out_shape=jax.ShapeDtypeStruct((rows, num_tiles * tile_n),
                                       out_dtype),
        interpret=interpret,
    )(tile_start, *streams)
    return out[:batch]


def _sum_push(contrib, dst_sorted, tile_start, *, num_tiles, tile_n, chunk,
              interpret):
    """One-hot-matmul scatter-add of ``f32[B, E_pad]`` → ``f32[B, N_t]``."""
    def compute(loaded, valid, d_local, acc):
        c = jnp.where(valid, loaded[0], 0.0)
        return acc + _DOT(c, _onehot_t(d_local, tile_n))

    return _tiled_call(compute, [contrib, dst_sorted[None]], tile_start,
                       init=0.0, num_tiles=num_tiles, tile_n=tile_n,
                       chunk=chunk, out_dtype=jnp.float32,
                       interpret=interpret)


def _reduce_identity(dtype, op: str):
    """The ⊕-identity XLA's segment_min/max use for empty segments."""
    if jnp.issubdtype(dtype, jnp.floating):
        return dtype.type(-jnp.inf if op == "max" else jnp.inf)
    info = jnp.iinfo(dtype)
    return dtype.type(info.min if op == "max" else info.max)


def _reduce_push(contrib, dst_sorted, rank, tile_start, *, num_tiles, op,
                 tile_n, chunk, interpret):
    """Segmented-scan ⊕-reduce of ``dtype[B, E_pad]`` → ``dtype[B, N_t]``
    (see the module docstring for the exact encodings)."""
    if op not in ("min", "max"):
        raise ValueError(f"op must be 'min' or 'max', got {op!r}")
    dtype = contrib.dtype
    identity = _reduce_identity(dtype, op)
    combine = jnp.minimum if op == "min" else jnp.maximum
    is_float = jnp.issubdtype(dtype, jnp.floating)
    n_steps = max(1, math.ceil(math.log2(chunk)))

    def compute(loaded, valid, d_local, acc):
        c, _, r = loaded
        lane = _lane_iota(chunk)
        v = jnp.where(valid, c, identity)
        # Hillis-Steele segmented ⊕-scan: after step k every position holds
        # the reduce of its run's trailing 2^k window; run-last positions
        # end up with the whole run (rank tells same-run membership in one
        # compare, the lane mask drops the roll's wrap-around)
        off = 1
        for _ in range(n_steps):
            pull = (r >= off) & (lane >= off)
            v = jnp.where(pull, combine(v, pltpu.roll(v, off, 1)), v)
            off *= 2
        # run-last positions: the destination changes at the next slot (the
        # chunk's last slot always flushes — a run spanning chunks scatters
        # one partial per chunk and the accumulator ⊕ recombines them)
        nxt_d = jnp.where(lane == chunk - 1, -1,
                          pltpu.roll(d_local, chunk - 1, 1))
        sel = (d_local != nxt_d) & (d_local < tile_n)
        if is_float:
            finite = jnp.abs(v) < jnp.inf
            lo = jnp.where(sel & finite, v, 0.0)
            hi = jnp.where(sel & ~finite, jnp.where(v > 0, 1.0, -1.0), 0.0)
        else:
            lo = jnp.where(sel, v & 0xffff, 0).astype(jnp.float32)
            hi = jnp.where(sel, (v >> 16) & 0xffff, 0).astype(jnp.float32)
        onehot = _onehot_t(d_local, tile_n)
        val, ext = _DOT(lo, onehot), _DOT(hi, onehot)
        cnt = _DOT(sel.astype(jnp.float32), onehot)
        if is_float:
            col = jnp.where(ext != 0, ext * jnp.inf, val).astype(dtype)
        else:
            col = val.astype(jnp.int32) | (ext.astype(jnp.int32) << 16)
        return combine(acc, jnp.where(cnt > 0, col, identity))

    return _tiled_call(compute, [contrib, dst_sorted[None], rank[None]],
                       tile_start, init=identity, num_tiles=num_tiles,
                       tile_n=tile_n, chunk=chunk, out_dtype=dtype,
                       interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("num_tiles", "tile_n", "chunk", "interpret"))
def spmv_push(
    contrib: jax.Array,      # f32[E_pad] — per-edge contribution, dst-sorted
    dst_sorted: jax.Array,   # i32[E_pad] — destination per edge (sorted)
    tile_start: jax.Array,   # i32[num_tiles + 1] — edge range per tile
    *,
    num_tiles: int,
    tile_n: int = TILE_N,
    chunk: int = CHUNK,
    interpret: bool = False,
) -> jax.Array:
    """Returns f32[num_tiles * tile_n] accumulated incoming contributions."""
    return _sum_push(contrib[None], dst_sorted, tile_start,
                     num_tiles=num_tiles, tile_n=tile_n, chunk=chunk,
                     interpret=interpret)[0]


@functools.partial(
    jax.jit,
    static_argnames=("num_tiles", "tile_n", "chunk", "op", "interpret"))
def spmv_reduce_push(
    contrib: jax.Array,      # [E_pad] per-edge contribution, dst-sorted
    dst_sorted: jax.Array,   # i32[E_pad] destination per edge (sorted)
    rank: jax.Array,         # i32[E_pad] position of each edge in its run
    tile_start: jax.Array,   # i32[num_tiles + 1] edge range per tile
    *,
    num_tiles: int,
    op: str,
    tile_n: int = TILE_N,
    chunk: int = CHUNK,
    interpret: bool = False,
) -> jax.Array:
    """Segmented-scan sibling of :func:`spmv_push` for ``op`` ∈ {min, max}.

    ``rank`` is the per-edge position inside its destination run (the
    layout builders derive it from ``row_offsets`` once per build; invalid
    and padding slots must carry 0 so they never pull across runs).
    Returns ``contrib.dtype[num_tiles * tile_n]``; destinations with no
    in-range edge hold the reduce identity (+∞/−∞ or the int extrema) —
    the ⊕-zero of the semiring the caller runs, matching XLA's
    ``segment_min``/``segment_max`` empty-segment convention.
    """
    return _reduce_push(contrib[None], dst_sorted, rank, tile_start,
                        num_tiles=num_tiles, op=op, tile_n=tile_n,
                        chunk=chunk, interpret=interpret)[0]


@functools.partial(
    jax.jit, static_argnames=("num_tiles", "tile_n", "chunk", "interpret"))
def spmv_push_batched(
    contrib: jax.Array,      # f32[B, E_pad] — per-edge contribs, dst-sorted
    dst_sorted: jax.Array,   # i32[E_pad] — destination per edge (sorted)
    tile_start: jax.Array,   # i32[num_tiles + 1] — edge range per tile
    *,
    num_tiles: int,
    tile_n: int = TILE_N,
    chunk: int = CHUNK,
    interpret: bool = False,
) -> jax.Array:
    """Batched :func:`spmv_push`: B contribution streams through one shared
    sorted edge stream.  Returns ``f32[B, num_tiles * tile_n]``."""
    return _sum_push(contrib, dst_sorted, tile_start, num_tiles=num_tiles,
                     tile_n=tile_n, chunk=chunk, interpret=interpret)


#: VMEM budget (bytes) for the batched reduce kernel's per-step working set
#: — scan buffers + encoded rows + one-hot + accumulator; the chunk shrinks
#: 128-granularly until it fits (min/max reduces are order-exact, so a
#: smaller chunk changes nothing numerically)
_REDUCE_TILE_VMEM_BYTES = 6 * 1024 * 1024


def batched_reduce_chunk(batch: int, tile_n: int, chunk: int,
                         itemsize: int = 4) -> int:
    """Largest 128-multiple chunk ≤ ``chunk`` whose batched-reduce working
    set — ~6 scan/encode buffers of ``[B, chunk]``, the ``[tile_n, chunk]``
    one-hot and the ``[B, tile_n]`` accumulator — fits the VMEM budget
    (never below 128).  The shrink is incremental (largest fit), not the
    former collapse-by-halving, so a marginally-over-budget shape loses a
    sliver of chunk instead of half of it.  Exposed so callers can reason
    about the effective chunk the batched reduce kernel will use."""
    acc_bytes = batch * tile_n * itemsize
    per_chunk = 6 * batch * itemsize + 4 * tile_n
    fit = (_REDUCE_TILE_VMEM_BYTES - acc_bytes) // max(per_chunk, 1)
    fit = max(128, (fit // 128) * 128)
    return min(chunk, fit)


@functools.partial(
    jax.jit,
    static_argnames=("num_tiles", "tile_n", "chunk", "op", "interpret"))
def spmv_reduce_push_batched(
    contrib: jax.Array,      # [B, E_pad] per-edge contribs, dst-sorted
    dst_sorted: jax.Array,   # i32[E_pad] destination per edge (sorted)
    rank: jax.Array,         # i32[E_pad] position of each edge in its run
    tile_start: jax.Array,   # i32[num_tiles + 1] edge range per tile
    *,
    num_tiles: int,
    op: str,
    tile_n: int = TILE_N,
    chunk: int = CHUNK,
    interpret: bool = False,
) -> jax.Array:
    """Batched :func:`spmv_reduce_push` for ``op`` ∈ {min, max}.

    Returns ``contrib.dtype[B, num_tiles * tile_n]``; each batch row is
    bitwise equal to the single-query kernel on the same stream (min/max
    are reassociation-exact).  The chunk shrinks automatically (largest
    128-granular fit) so the scan working set stays inside VMEM — smaller
    chunks load the same edges.
    """
    chunk = batched_reduce_chunk(contrib.shape[0], tile_n, chunk,
                                 jnp.dtype(contrib.dtype).itemsize)
    return _reduce_push(contrib, dst_sorted, rank, tile_start,
                        num_tiles=num_tiles, op=op, tile_n=tile_n,
                        chunk=chunk, interpret=interpret)
