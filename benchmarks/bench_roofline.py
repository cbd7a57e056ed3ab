"""Render the roofline table from the dry-run artifacts (§Roofline).

Reads artifacts/dryrun/<mesh>/*.json (produced by repro.launch.dryrun) —
re-running the dry-run requires 512 host devices, so this module only
formats; the dry-run itself is a separate process.
"""

from __future__ import annotations

import glob
import json
from pathlib import Path

from repro.hardware import chip_peaks

ART = Path(__file__).resolve().parent.parent / "artifacts" / "dryrun"


def load(mesh: str):
    rows = []
    for f in sorted(glob.glob(str(ART / mesh / "*.json"))):
        r = json.load(open(f))
        rows.append(r)
    return rows


def _dominant(rf):
    if "dominant" in rf:
        return rf["dominant"]
    t = {"compute": rf["compute_s"], "memory": rf["memory_s"],
         "collective": rf["collective_s"]}
    return max(t, key=t.get)


def _frac(rf):
    if "roofline_fraction" in rf:
        return rf["roofline_fraction"]
    useful = (rf["model_flops"] / rf["chips"]) / chip_peaks().flops_bf16
    b = max(rf["compute_s"], rf["memory_s"], rf["collective_s"])
    return useful / b if b else 0.0


def _ratio(rf):
    if "useful_flops_ratio" in rf:
        return rf["useful_flops_ratio"]
    tot = rf["flops_per_device"] * rf["chips"]
    return rf["model_flops"] / tot if tot else 0.0


def table(mesh: str = "single") -> str:
    rows = load(mesh)
    ok = [r for r in rows if r["status"] == "ok"]
    skipped = [r for r in rows if r["status"] == "skipped"]
    out = []
    hdr = (f"{'arch':24s} {'shape':14s} {'comp_s':>9s} {'mem_s':>9s} "
           f"{'coll_s':>9s} {'dom':>10s} {'6ND/HLO':>8s} {'frac':>7s} "
           f"{'args_GiB':>8s} {'temp_GiB':>8s}")
    out.append(hdr)
    out.append("-" * len(hdr))
    for r in sorted(ok, key=lambda x: (x["arch"], str(x["shape"]))):
        rf = r["roofline"]
        ms = rf.get("memory_stats") or {}
        out.append(
            f"{rf['arch']:24s} {str(rf['shape']):14s} {rf['compute_s']:9.3f} "
            f"{rf['memory_s']:9.3f} {rf['collective_s']:9.3f} "
            f"{_dominant(rf):>10s} {_ratio(rf):8.3f} "
            f"{_frac(rf):7.4f} "
            f"{ms.get('argument_bytes', 0)/2**30:8.2f} "
            f"{ms.get('temp_bytes', 0)/2**30:8.2f}")
    for r in skipped:
        out.append(f"{r['arch']:24s} {r['shape']:14s} "
                   f"   -- skipped: {r['reason'][:60]}")
    return "\n".join(out)


def main():
    for mesh in ("single", "multi"):
        if (ART / mesh).exists():
            print(f"\n=== roofline table: {mesh}-pod mesh ===")
            print(table(mesh))


if __name__ == "__main__":
    main()
