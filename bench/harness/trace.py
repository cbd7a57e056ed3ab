"""From a profiler trace to device times, busy share and idle gaps.

A trace is first flattened (:func:`flatten`) into plain lists, which is
what the reduction reads and what the test fixture records:

- ``modules``: ``[name, start_ns, dur_ns]`` of every XLA module run on the
  device (the ``jit_<function>`` programs);
- ``ops``: ``[name, start_ns, dur_ns]`` of every XLA op run on the device;
- ``spans``: ``[name, start_ns, dur_ns]`` of the harness's own host spans
  (``window``, ``register``, ``query``), on the same clock.

The reduction takes the window from the ``window`` span, clips every
device interval to it, and reports busy time as the union of op
intervals, device time per module, and each idle gap labelled by the
host span that covers its middle.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence

#: host spans the harness opens; the reduction labels gaps with these
HOST_SPANS = ("register", "query", "window")

_MODULE_ID = re.compile(r"\(\d+\)$")


def flatten(trace_dir: str, device_index: int = 0) -> Dict[str, list]:
    """Read the newest ``.xplane.pb`` under ``trace_dir`` into lists."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out = {"modules": [], "ops": [], "spans": []}
    want_plane = f"/device:TPU:{device_index}"
    for plane in data.planes:
        if plane.name == want_plane:
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key:
                    out[key].extend([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)]
                                    for ev in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out["spans"].extend([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)]
                                    for ev in line.events
                                    if ev.name in HOST_SPANS)
    return out


def module_name(name: str) -> str:
    """``jit_pagerank(12)`` -> ``jit_pagerank``."""
    return _MODULE_ID.sub("", name)


def _window(flat) -> tuple:
    spans = [s for s in flat["spans"] if s[0] == "window"]
    if not spans:
        raise ValueError("trace holds no 'window' span")
    _, start, dur = spans[0]
    return start, start + dur


def _clip(events, lo, hi):
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            yield name, a, b


def _union(intervals: Sequence[tuple]) -> List[tuple]:
    merged: List[list] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [tuple(m) for m in merged]


def reduce(flat: Dict[str, list], top: int = 10) -> Dict:
    """Window, busy time, per-module device seconds and the breakdown."""
    lo, hi = _window(flat)
    ops = list(_clip(flat["ops"], lo, hi))
    busy = _union([(a, b) for _, a, b in ops])
    busy_ns = sum(b - a for a, b in busy)
    per_module: Dict[str, float] = defaultdict(float)
    for name, a, b in _clip(flat["modules"], lo, hi):
        per_module[module_name(name)] += (b - a) * 1e-9
    per_op: Dict[str, float] = defaultdict(float)
    for name, a, b in ops:
        per_op[name] += (b - a) * 1e-9
    gaps = []
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    host = [s for s in flat["spans"] if s[0] != "window"]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps.append((_label(host, (a + b) // 2), (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    device_ops = sorted(
        [[f"module:{k}", v] for k, v in per_module.items()]
        + [[k, v] for k, v in per_op.items()], key=lambda x: -x[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "modules": dict(per_module),
        "breakdown": {
            "device_ops": device_ops[:top],
            "idle_gaps": [[name, s] for name, s in gaps[:top]],
        },
    }


def _label(spans, t) -> str:
    """The innermost host span covering ``t``, or ``other``."""
    best: Optional[list] = None
    for name, start, dur in spans:
        if start <= t < start + dur and (best is None or dur < best[2]):
            best = [name, start, dur]
    return best[0] if best else "other"


def module_seconds(trace: Dict, pattern: str) -> Optional[float]:
    """Device seconds of the modules whose name matches ``pattern`` (a
    regex anchored at the start), or None when none ran."""
    rx = re.compile(pattern)
    hits = [v for k, v in trace["modules"].items() if rx.match(k)]
    return sum(hits) if hits else None
