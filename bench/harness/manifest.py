"""``BENCHMARK.json`` and the per-name files it points at.

A cell's configuration is the ``file`` of its entry in ``configs``, its
traffic mix is ``bench/traffic/<traffic>.json``, and each per-layer
metric is read by ``bench/metrics/<name>.py``, whose ``read(record)``
returns a number or None.  Nothing here knows any cell by name.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
MANIFEST = ROOT / "BENCHMARK.json"


@dataclass
class Cell:
    """One workload with everything its run needs."""

    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict] = field(default_factory=list)
    per_layer: List[Dict] = field(default_factory=list)


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, manifest: Path = MANIFEST) -> Cell:
    """The cell ``name`` of ``manifest``; KeyError when there is none."""
    m = load_json(manifest)
    root = manifest.parent
    work = {w["name"]: w for w in m["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; known: {sorted(work)}")
    w = work[name]
    config = {c["name"]: c for c in m["configs"]}[w["config"]]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=load_json(root / config["file"]),
        traffic=load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[e for e in m["end_to_end"] if _applies(e, name)],
        per_layer=[p for p in m["per_layer"] if _applies(p, name)],
    )


def metric_reader(name: str, metrics_dir: Path = BENCH / "metrics"
                  ) -> Callable[[Dict], Optional[float]]:
    """``read`` of ``bench/metrics/<name>.py``."""
    path = metrics_dir / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
