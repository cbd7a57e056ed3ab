"""Roofline-term extraction from a compiled (SPMD-partitioned) executable.

Terms over the peaks of the modeled chip, a TPU v5e
(:func:`repro.hardware.chip_peaks`):

  compute term    = per-device HLO FLOPs / peak bf16 FLOP/s
  memory term     = per-device HLO bytes accessed / HBM bytes/s
  collective term = per-device collective bytes / ICI bytes/s per link

``cost_analysis()`` reports the per-device SPMD program, so no /chips
normalization is applied.  Collective bytes are not in cost_analysis: we
parse the partitioned HLO and sum result-shape bytes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute, with ring
factors (all-reduce counts 2×(n-1)/n, gather/scatter (n-1)/n of the full
buffer; n approximated by the largest mesh axis participating — recorded as
an approximation in EXPERIMENTS.md).
"""

from __future__ import annotations

import json
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Optional

from repro.hardware import chip_peaks
from repro.launch.hlo_cost import analyze_hlo

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

# e.g. "bf16[16,4096,256]{2,1,0}" — first shape in the op result
_SHAPE_RE = re.compile(r"([a-z]+[0-9]*(?:e[0-9]+m[0-9]+)?)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"^\s*(?:%[\w.-]+ = )?(\(?[a-z0-9\[\],{}() ]+?\)?) (all-reduce|all-gather|"
    r"reduce-scatter|all-to-all|collective-permute)(?:-start)?\(",
    re.MULTILINE)
_GROUPS_RE = re.compile(r"replica_groups=\{?\[?([0-9]+),?([0-9]+)?\]?")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-collective-kind effective bytes moved per device (ring model)."""
    out: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        type_str, kind = m.group(1), m.group(2)
        nbytes = _shape_bytes(type_str)
        # ring factors; n unknown per-op here -> use (n-1)/n ≈ 1 upper bound,
        # all-reduce moves ~2× its buffer
        factor = 2.0 if kind == "all-reduce" else 1.0
        out[kind] = out.get(kind, 0.0) + factor * nbytes
        counts[kind] = counts.get(kind, 0) + 1
    out["_counts"] = counts  # type: ignore
    return out


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    collective_breakdown: Dict[str, float]
    model_flops: float              # 6·N_active·D analytic, GLOBAL per step
    memory_stats: Optional[Dict[str, float]] = None

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / chip_peaks().flops_bf16

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / chip_peaks().hbm_bytes_per_s

    @property
    def collective_s(self) -> float:
        return (self.collective_bytes_per_device
                / chip_peaks().ici_bytes_per_s_link)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / compiled FLOPs (global) — remat/redundancy waste."""
        total = self.flops_per_device * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def bound_time_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """Useful compute time / bound time — the §Perf score per cell."""
        useful_s = (self.model_flops / self.chips) / chip_peaks().flops_bf16
        return useful_s / self.bound_time_s if self.bound_time_s else 0.0

    def to_dict(self) -> Dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
            "collective_breakdown": self.collective_breakdown,
            "model_flops": self.model_flops,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "memory_stats": self.memory_stats,
        }


def model_flops_for(cfg, shape) -> float:
    """Analytic MODEL_FLOPS per step: 6·N_active·D train, 2·N_active·D
    inference (forward only); decode counts the single new token."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


# ---------------------------------------------------------------------------
# Push-kernel roofline: the per-kernel bytes/FLOPs model + regression gate
# ---------------------------------------------------------------------------


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


def analyze(compiled, *, arch: str, shape, mesh_name: str, chips: int,
            cfg) -> Roofline:
    """Roofline terms from the trip-count-aware HLO analyzer (hlo_cost).

    XLA's cost_analysis() counts while bodies once (scan-over-layers would
    be ~L× undercounted); the analyzer multiplies by known_trip_count.  The
    raw XLA numbers are retained in the record for reference.
    """
    xla_cost = compiled.cost_analysis()
    text = compiled.as_text()
    hc = analyze_hlo(text)
    flops = float(hc.flops)
    nbytes = float(hc.bytes)
    coll = dict(hc.coll)
    counts = dict(hc.coll_counts)
    total_coll = float(hc.collective_bytes)
    mem = None
    try:
        ms = compiled.memory_analysis()
        mem = {
            "argument_bytes": ms.argument_size_in_bytes,
            "output_bytes": ms.output_size_in_bytes,
            "temp_bytes": ms.temp_size_in_bytes,
            "alias_bytes": ms.alias_size_in_bytes,
        }
    except Exception:
        pass
    if mem is not None:
        mem["xla_flops_raw"] = float(xla_cost.get("flops", 0.0))
        mem["xla_bytes_raw"] = float(xla_cost.get("bytes accessed", 0.0))
    return Roofline(
        arch=arch, shape=shape.name, mesh=mesh_name, chips=chips,
        flops_per_device=flops, bytes_per_device=nbytes,
        collective_bytes_per_device=total_coll,
        collective_breakdown={**coll, "counts": counts},
        model_flops=model_flops_for(cfg, shape),
        memory_stats=mem,
    )
