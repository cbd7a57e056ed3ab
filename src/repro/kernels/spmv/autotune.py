"""Per-shape geometry autotuner for the destination-tiled SpMV kernels.

The push kernels in :mod:`repro.kernels.spmv.kernel` are parameterised by a
``(tile_n, chunk)`` geometry: ``tile_n`` destination rows per grid step and
``chunk`` edges per streamed load.  The historical defaults (``TILE_N=256``,
``CHUNK=512``) are a reasonable middle of the road but are not optimal
everywhere — small summary layouts want small tiles (the per-tile
partial-chunk overshoot dominates), wide serving batches shrink the VMEM
room for ``chunk``, and the segmented-scan reduce variant pays ``log2
(chunk)`` scan steps per chunk that the sum variant does not.

This module replaces the hardcoded geometry with a small per-shape search:

``TuneKey``
    ``(e_pad, n, b, dtype, reduce, platform)`` — everything the kernel cost
    depends on.  ``e_pad`` is the default-chunk padded edge-stream length
    (a pure function of the edge capacity, so the key is stable across
    candidate chunks), ``reduce`` is the ⊕ kind (``sum``/``min``/``max``)
    and ``platform`` is ``jax.default_backend()`` — tunings are never
    shared across device kinds.

``modeled_push_cost``
    The analytic bytes/FLOPs/VMEM model for one push at a candidate
    geometry.  It is shared with :mod:`repro.launch.roofline` (the CI
    byte-volume gate asserts against the same numbers the tuner ranks by),
    and prunes the candidate grid *before any timing*: candidates whose
    modeled VMEM working set exceeds :data:`VMEM_LIMIT_BYTES` are never
    run.

``tune``
    Mode ``"off"`` returns the defaults; ``"cached"`` answers from the
    in-process cache / any loaded JSON cache, falling back to the analytic
    argmin (no timing — safe for CI); ``"full"`` times the top
    model-ranked candidates on synthetic streams and caches the winner.
    Results are cached in-process exactly like the engine's EdgeLayouts —
    one entry per key, hits skip all work — and the engine surfaces the
    number of measured tunings as ``engine.autotune_runs``.

``save_cache`` / ``load_cache``
    JSON persistence so benchmarks and CI reuse tunings instead of
    re-measuring (``benchmarks/autotune_cache.json`` is the committed
    cache; the benchmark smoke job replays it with ``--autotune cached``).
"""

from __future__ import annotations

import json
import math
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from repro.hardware import TARGET_DEVICE_KIND, ChipPeaks, chip_peaks
from repro.kernels.spmv.kernel import (CHUNK, TILE_N, spmv_push,
                                       spmv_push_batched, spmv_reduce_push,
                                       spmv_reduce_push_batched)

#: Lane-aligned tile widths (the VPU lane count is 128; the one-hot matmul
#: wants the output minor dim to be a multiple of it).
TILE_N_CANDIDATES: Tuple[int, ...] = (128, 256, 512)
#: Edge-stream chunk lengths (power-of-two so the segmented scan's
#: ``log2(chunk)`` step count is exact).
CHUNK_CANDIDATES: Tuple[int, ...] = (128, 256, 512, 1024)

#: VMEM working-set budget per grid step.  v5e cores have ~16 MiB of VMEM;
#: the budget leaves headroom for Mosaic's own spills and the output tile.
VMEM_LIMIT_BYTES = 10 * 1024 * 1024


@dataclass(frozen=True)
class TuneKey:
    """Everything the per-push kernel cost depends on."""

    e_pad: int          # default-chunk padded edge-stream length
    n: int              # destination-space size (num_segments)
    b: int              # batch rows pushed per call (1 = single query)
    dtype: str          # contribution dtype ("float32" / "int32")
    reduce: str         # ⊕ kind: "sum" | "min" | "max"
    platform: str       # jax.default_backend() at tune time

    def as_str(self) -> str:
        return (f"{self.e_pad}/{self.n}/{self.b}/{self.dtype}/"
                f"{self.reduce}/{self.platform}")

    @staticmethod
    def from_str(s: str) -> "TuneKey":
        e_pad, n, b, dtype, reduce, platform = s.split("/")
        return TuneKey(int(e_pad), int(n), int(b), dtype, reduce, platform)


@dataclass(frozen=True)
class PushCost:
    """Analytic cost of one push at a candidate geometry."""

    hbm_bytes: float    # edge streams (incl. partial-chunk waste) + output
    flops: float        # one-hot matmul + (reduce only) segmented scan
    vmem_bytes: float   # double-buffered slots + onehot + matmul operands
    peaks: ChipPeaks    # the modeled chip

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / self.peaks.hbm_bytes_per_s

    @property
    def compute_s(self) -> float:
        return self.flops / self.peaks.flops_bf16

    @property
    def bound_time_s(self) -> float:
        return max(self.memory_s, self.compute_s)


def modeled_push_cost(*, e_pad: int, n: int, b: int = 1, itemsize: int = 4,
                      reduce: str = "sum", tile_n: int = TILE_N,
                      chunk: int = CHUNK,
                      device_kind: str = TARGET_DEVICE_KIND) -> PushCost:
    """Bytes / FLOPs / VMEM model for one push at ``(tile_n, chunk)`` on
    a ``device_kind`` chip (peaks from :func:`repro.hardware.chip_peaks`).

    HBM traffic: every edge is read once per stream — ``b`` contribution
    rows of ``itemsize`` plus 4-byte ``dst`` (and 4-byte ``rank`` for the
    reduce variant) — plus the per-tile partial-chunk overshoot (each tile
    rounds its edge range up to a chunk multiple: ≤ ``chunk`` wasted edges
    per tile), the tile-start table, and the output write.

    FLOPs: the one-hot matmul is ``rows × chunk × tile_n`` MACs per chunk
    (``rows`` = ``b`` for sum, ``2b+1`` for the reduce encoding), the
    segmented scan adds ``log2(chunk)`` compare/combine passes over the
    chunk, and the reduce encode/decode a few elementwise passes.

    VMEM: two buffered slots per input stream (the double-buffering
    scratch), the materialised one-hot, matmul operands/result and the
    accumulator.
    """
    num_tiles = -(-n // tile_n)
    waste = num_tiles * chunk           # partial-chunk overshoot bound
    edges = e_pad + waste
    per_edge = itemsize * b + 4 + (4 if reduce != "sum" else 0)
    hbm = (edges * per_edge + (num_tiles + 1) * 4
           + num_tiles * tile_n * itemsize * b)

    chunks = edges / chunk
    rows = b if reduce == "sum" else 2 * b + 1
    flops = chunks * 2.0 * rows * chunk * tile_n
    if reduce != "sum":
        nsteps = max(1, math.ceil(math.log2(chunk)))
        flops += chunks * b * chunk * (4.0 * nsteps + 8.0)

    slot = 2 * chunk * per_edge
    onehot = chunk * tile_n * 4
    rows_bytes = rows * (chunk + tile_n) * 4
    acc = 2 * tile_n * b * itemsize
    vmem = slot + onehot + rows_bytes + acc
    return PushCost(hbm_bytes=float(hbm), flops=float(flops),
                    vmem_bytes=float(vmem), peaks=chip_peaks(device_kind))


def _modeled_kind(platform: str) -> str:
    """The chip a key is modeled for: the attached TPU's kind on TPU, the
    deployment target where the kernels run in interpret mode."""
    if platform != "tpu":
        return TARGET_DEVICE_KIND
    import jax

    return jax.devices()[0].device_kind


def candidates(key: TuneKey) -> List[Tuple[int, int]]:
    """VMEM-pruned, model-ranked candidate geometries for ``key``
    (cheapest modeled bound-time first).  Pruning is purely analytic —
    nothing is compiled or timed here."""
    itemsize = np.dtype(key.dtype).itemsize
    kind = _modeled_kind(key.platform)
    out = []
    for tile_n in TILE_N_CANDIDATES:
        for chunk in CHUNK_CANDIDATES:
            cost = modeled_push_cost(
                e_pad=key.e_pad, n=key.n, b=key.b, itemsize=itemsize,
                reduce=key.reduce, tile_n=tile_n, chunk=chunk,
                device_kind=kind)
            if cost.vmem_bytes > VMEM_LIMIT_BYTES:
                continue
            out.append((cost.bound_time_s, tile_n, chunk))
    out.sort()
    return [(t, c) for _, t, c in out]


# ---------------------------------------------------------------------------
# in-process cache + measured-run counter (engine-observable)

_CACHE: Dict[TuneKey, Tuple[int, int]] = {}
_RUNS = 0           # number of measured ("full") tunings this process
_HITS = 0           # cache answers (in-process or JSON-loaded)


def run_count() -> int:
    """Measured tuning runs so far in this process (cache hits excluded)."""
    return _RUNS


def cache_hits() -> int:
    return _HITS


def clear_cache() -> None:
    global _RUNS, _HITS
    _CACHE.clear()
    _RUNS = 0
    _HITS = 0


def cache_entries() -> Dict[str, Tuple[int, int]]:
    return {k.as_str(): v for k, v in _CACHE.items()}


def save_cache(path) -> None:
    """Persist the in-process cache as JSON (committed caches let CI and
    benchmarks replay tunings with ``--autotune cached``)."""
    p = Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    payload = {"version": 1,
               "entries": {k: list(v) for k, v in cache_entries().items()}}
    p.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")


def load_cache(path) -> int:
    """Merge a JSON cache into the in-process cache; returns entries added."""
    p = Path(path)
    if not p.exists():
        return 0
    payload = json.loads(p.read_text())
    added = 0
    for ks, (tile_n, chunk) in payload.get("entries", {}).items():
        key = TuneKey.from_str(ks)
        if key not in _CACHE:
            added += 1
        _CACHE[key] = (int(tile_n), int(chunk))
    return added


# ---------------------------------------------------------------------------
# measurement

def _synthetic_args(key: TuneKey, chunk: int, tile_n: int):
    """Synthetic sorted edge streams shaped like ``key`` for timing."""
    import jax.numpy as jnp

    e = key.e_pad
    e_pad = (e // chunk + 2) * chunk
    n = key.n
    rng = np.random.default_rng(0)
    dst = np.sort(rng.integers(0, n, e)).astype(np.int32)
    dstp = np.full(e_pad, n, np.int32)
    dstp[:e] = dst
    row_offsets = np.searchsorted(dst, np.arange(n + 1)).astype(np.int32)
    rank = np.zeros(e_pad, np.int32)
    rank[:e] = np.arange(e) - row_offsets[dst]
    num_tiles = -(-n // tile_n)
    bounds = np.minimum(np.arange(num_tiles + 1) * tile_n, n)
    tile_start = row_offsets[bounds].astype(np.int32)
    if key.reduce == "sum":
        fill = 0.0
    else:
        info = (np.finfo if key.dtype.startswith("float") else np.iinfo)(
            np.dtype(key.dtype))
        fill = info.max if key.reduce == "min" else info.min
    shape = (e_pad,) if key.b == 1 else (key.b, e_pad)
    contrib = np.full(shape, fill, np.dtype(key.dtype))
    vals = rng.random(e).astype(np.float32) + 1.0
    contrib[..., :e] = vals if key.dtype.startswith("float") else (
        (vals * 1000).astype(np.dtype(key.dtype)))
    return (jnp.asarray(contrib), jnp.asarray(dstp), jnp.asarray(rank),
            jnp.asarray(tile_start), num_tiles)


def _time_candidate(key: TuneKey, tile_n: int, chunk: int, *,
                    interpret: bool, iters: int = 2) -> float:
    import jax

    contrib, dstp, rank, tile_start, num_tiles = _synthetic_args(
        key, chunk, tile_n)
    kw = dict(num_tiles=num_tiles, tile_n=tile_n, chunk=chunk,
              interpret=interpret)
    if key.reduce == "sum":
        fn = spmv_push if key.b == 1 else spmv_push_batched
        call = lambda: fn(contrib, dstp, tile_start, **kw)
    else:
        fn = spmv_reduce_push if key.b == 1 else spmv_reduce_push_batched
        call = lambda: fn(contrib, dstp, rank, tile_start, op=key.reduce,
                          **kw)
    jax.block_until_ready(call())            # compile / first interpret pass
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(call())
    return (time.perf_counter() - t0) / iters


def tune(key: TuneKey, mode: str = "cached", *,
         measure_top: int = 4) -> Tuple[int, int]:
    """Resolve the ``(tile_n, chunk)`` geometry for ``key``.

    ``"off"`` → the hardcoded defaults, no cache interaction.
    ``"cached"`` → in-process/JSON-loaded answer, else the analytic argmin
    of :func:`modeled_push_cost` over the pruned grid (no timing).
    ``"full"`` → time the ``measure_top`` best-modeled candidates on
    synthetic streams and cache the measured winner.
    """
    global _RUNS, _HITS
    if mode == "off":
        return (TILE_N, CHUNK)
    if mode not in ("cached", "full"):
        raise ValueError(f"unknown autotune mode {mode!r}; "
                         f"expected 'off', 'cached' or 'full'")
    hit = _CACHE.get(key)
    if hit is not None:
        _HITS += 1
        return hit
    cands = candidates(key)
    if not cands:
        return (TILE_N, CHUNK)
    if mode == "cached":
        # analytic argmin — deterministic and cheap, so it is NOT written
        # to the cache: the cache holds measured (or JSON-loaded) tunings
        # only, and a later "full" run must still get to time candidates
        return cands[0]
    import jax

    interpret = jax.default_backend() != "tpu"
    timed = [(_time_candidate(key, t, c, interpret=interpret), t, c)
             for t, c in cands[:measure_top]]
    timed.sort()
    best = (timed[0][1], timed[0][2])
    _RUNS += 1
    _CACHE[key] = best
    return best


def tune_for_push(*, edge_capacity: int, num_segments: int, batch: int = 1,
                  dtype: str = "float32", reduce: str = "sum",
                  mode: str = "cached",
                  measure_top: int = 4) -> Tuple[int, int]:
    """Front door used at layout-build time: build the key from engine
    capacities (``e_pad`` = the default-chunk padded stream length, so the
    key does not depend on the chunk being tuned) and resolve."""
    import jax

    e_pad = (edge_capacity // CHUNK + 2) * CHUNK
    key = TuneKey(e_pad=e_pad, n=num_segments, b=batch, dtype=dtype,
                  reduce=reduce, platform=jax.default_backend())
    return tune(key, mode, measure_top=measure_top)


__all__ = [
    "CHUNK_CANDIDATES", "PushCost", "TILE_N_CANDIDATES", "TuneKey",
    "VMEM_LIMIT_BYTES", "cache_entries", "cache_hits", "candidates",
    "clear_cache", "load_cache", "modeled_push_cost", "run_count",
    "save_cache", "tune", "tune_for_push",
]
