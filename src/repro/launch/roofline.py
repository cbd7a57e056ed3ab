"""Push-kernel roofline: the per-kernel bytes/FLOPs model of one SpMV
sweep and the "modeled HBM traffic must not regress" gate over the pinned
shapes of ``benchmarks/roofline_baseline.json``.

The terms come from :func:`repro.kernels.spmv.autotune.modeled_push_cost`
over the peaks of the modeled chip, a TPU v5e
(:func:`repro.hardware.chip_peaks`).
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Optional


def push_roofline_check(*, edge_capacity: int, num_segments: int,
                        batch: int = 1, reduce: str = "sum",
                        dtype: str = "float32",
                        tile_n: Optional[int] = None,
                        chunk: Optional[int] = None,
                        measured_s: Optional[float] = None,
                        baseline: Optional[Dict] = None,
                        tolerance: float = 0.10) -> Dict:
    """Roofline record for ONE SpMV push sweep, with optional gates.

    The bytes/FLOPs come from the same analytic model the autotuner ranks
    candidates with (:func:`repro.kernels.spmv.autotune.modeled_push_cost`),
    so the CI gate and the tuner can never disagree about what a shape
    "should" cost.  Two gates, both optional:

    - ``measured_s``: a wall-clock measurement for the sweep (compiled,
      real device).  The record gains ``fraction_of_peak`` =
      bound_time / measured — the asserted-on number on TPU.  In interpret
      mode there is no meaningful wall clock; gate on the modeled byte
      volume instead (the ``baseline`` gate below).
    - ``baseline``: a dict holding a committed ``hbm_bytes`` figure for
      this shape.  Raises ``AssertionError`` when the current model
      exceeds it by more than ``tolerance`` (default 10%) — the "modeled
      HBM traffic must not regress" CI check.

    Geometry defaults to the kernel's hardcoded tiles; pass the autotuned
    ``(tile_n, chunk)`` to score the tuned sweep.
    """
    from repro.kernels.spmv import autotune as AT
    from repro.kernels.spmv.kernel import CHUNK, TILE_N

    import numpy as _np

    e_pad = (edge_capacity // CHUNK + 2) * CHUNK
    itemsize = _np.dtype(dtype).itemsize
    cost = AT.modeled_push_cost(
        e_pad=e_pad, n=num_segments, b=batch, itemsize=itemsize,
        reduce=reduce,
        tile_n=TILE_N if tile_n is None else tile_n,
        chunk=CHUNK if chunk is None else chunk)
    rec = {
        "edge_capacity": edge_capacity,
        "num_segments": num_segments,
        "batch": batch,
        "reduce": reduce,
        "dtype": dtype,
        "tile_n": TILE_N if tile_n is None else tile_n,
        "chunk": CHUNK if chunk is None else chunk,
        "hbm_bytes": cost.hbm_bytes,
        "flops": cost.flops,
        "vmem_bytes": cost.vmem_bytes,
        "memory_s": cost.memory_s,
        "compute_s": cost.compute_s,
        "bound_time_s": cost.bound_time_s,
        "dominant": "memory" if cost.memory_s >= cost.compute_s
        else "compute",
    }
    if measured_s is not None:
        rec["measured_s"] = measured_s
        rec["fraction_of_peak"] = (cost.bound_time_s / measured_s
                                   if measured_s > 0 else 0.0)
    if baseline is not None:
        base_bytes = float(baseline["hbm_bytes"])
        ratio = cost.hbm_bytes / base_bytes if base_bytes else float("inf")
        rec["baseline_hbm_bytes"] = base_bytes
        rec["hbm_ratio_vs_baseline"] = ratio
        if ratio > 1.0 + tolerance:
            raise AssertionError(
                f"modeled HBM traffic regressed {100 * (ratio - 1):.1f}% "
                f"(> {100 * tolerance:.0f}%) for push shape "
                f"E={edge_capacity} N={num_segments} B={batch} "
                f"reduce={reduce}: {cost.hbm_bytes:.3e} B vs baseline "
                f"{base_bytes:.3e} B")
    return rec


def check_push_baselines(baseline_path, *, update: bool = False,
                         tolerance: float = 0.10) -> Dict:
    """Gate every pinned push shape in a committed baseline JSON.

    The file holds named shapes with their parameters and the blessed
    modeled ``hbm_bytes``; each is re-modeled and checked within
    ``tolerance`` via :func:`push_roofline_check`.  ``update=True``
    rewrites the file with current numbers instead of asserting (run it
    after an *intentional* cost-model or kernel-geometry change and commit
    the diff).  Returns ``{name: record}``.
    """
    path = Path(baseline_path)
    payload = json.loads(path.read_text())
    out = {}
    for name, entry in sorted(payload.get("shapes", {}).items()):
        params = {k: entry[k] for k in
                  ("edge_capacity", "num_segments", "batch", "reduce",
                   "dtype") if k in entry}
        geom = {k: entry[k] for k in ("tile_n", "chunk") if k in entry}
        rec = push_roofline_check(
            **params, **geom,
            baseline=None if update else {"hbm_bytes": entry["hbm_bytes"]},
            tolerance=tolerance)
        out[name] = rec
        if update:
            entry["hbm_bytes"] = rec["hbm_bytes"]
            entry["flops"] = rec["flops"]
    if update:
        path.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")
    return out

