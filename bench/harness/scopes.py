"""The program's own scopes and spans in a profiler trace.

The program names the device ops of each stage with ``veilgraph.*``
scopes (``jax.named_scope``, kept in each op's HLO ``op_name``) and
writes ``veilgraph.*`` host spans (``TraceAnnotation``) on the device
ops' clock; ``repro.core.spans`` lists them.  This module reads both,
beside :mod:`harness.trace`, whose lists and results it leaves as they
are.

:func:`flatten` reads, from the same ``.xplane.pb``:

- ``scoped_ops``: ``[scope_path, start_ns, dur_ns]`` of every XLA op on
  the device whose ``op_name`` holds a ``veilgraph.`` segment; the path
  is those segments joined by ``/`` (``veilgraph.summary/veilgraph.push``);
- ``program_spans``: ``[name, start_ns, dur_ns]`` of every host event
  whose name starts with ``veilgraph.``.

``jax.profiler.ProfileData`` gives an op event its HLO text and times
but not its ``op_name``, which the device plane keeps in the op's event
metadata (the ``tf_op`` stat).  So the device plane is read here from
the protobuf wire format itself (``tsl/profiler/protobuf/xplane.proto``),
keeping only the fields named below.

:func:`reduce` takes a flattened trace holding both these lists and
:func:`harness.trace.flatten`'s, and returns

- ``scopes``: for each scope segment, the device seconds of the union of
  the clipped intervals of the ops under it, so that a ``while`` op and
  the ops of its body count once;
- ``program_spans``: name -> ``[count, seconds]`` of the spans that
  overlap the window, clipped to it;
- ``idle_gaps_by_program_span``: the longest idle gaps of the device,
  each labelled by the innermost program span covering its middle
  (``other`` where none does).

A trace of a program that emits none of these gives ``{}`` and ``[]``.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Tuple

from harness import trace as T

PREFIX = "veilgraph."

#: the event-metadata stat of an XLA op that holds its HLO ``op_name``
OP_NAME_STAT = "tf_op"

# xplane.proto field numbers: XSpace.planes; XPlane.name, .lines,
# .event_metadata, .stat_metadata; XLine.name, .timestamp_ns, .events;
# XEvent.metadata_id, .offset_ps, .duration_ps; XEventMetadata.id,
# .stats; XStatMetadata.id, .name; XStat.metadata_id, .str_value,
# .ref_value; a map entry's key and value
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 3, 4, 5
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_MD_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS = 1, 2, 3
_EVENT_MD_STATS = 5
_STAT_MD_NAME = 2
_STAT_MD_ID, _STAT_STR, _STAT_REF = 1, 5, 7
_KEY, _VALUE = 1, 2


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, span: Tuple[int, int]) -> Iterator[Tuple[int, object]]:
    """(field number, value) of the message in ``buf[span]``: an int for
    a varint, the ``(start, end)`` of a length-delimited field."""
    i, end = span
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind == 1:
            value, i = None, i + 8
        elif kind == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"wire type {kind} in an xplane")
        yield key >> 3, value


def _text(buf: bytes, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf: bytes, span) -> Tuple[int, Optional[tuple]]:
    key, value = 0, None
    for num, v in _fields(buf, span):
        if num == _KEY:
            key = v
        elif num == _VALUE:
            value = v
    return key, value


def _device_ops(buf: bytes, plane) -> List[list]:
    """``[op_name, start_ns, dur_ns]`` of the plane's ``XLA Ops`` line."""
    stat_names: Dict[int, str] = {}
    op_names: Dict[int, str] = {}
    lines, event_md = [], []
    for num, v in _fields(buf, plane):
        if num == _PLANE_LINES:
            lines.append(v)
        elif num == _PLANE_EVENT_MD:
            event_md.append(_map_entry(buf, v))
        elif num == _PLANE_STAT_MD:
            key, md = _map_entry(buf, v)
            for n, w in _fields(buf, md or (0, 0)):
                if n == _STAT_MD_NAME:
                    stat_names[key] = _text(buf, w)
    for key, md in event_md:
        for num, stat in _fields(buf, md or (0, 0)):
            if num != _EVENT_MD_STATS:
                continue
            sid, value = None, None
            for n, w in _fields(buf, stat):
                if n == _STAT_MD_ID:
                    sid = w
                elif n == _STAT_STR:
                    value = _text(buf, w)
                elif n == _STAT_REF:
                    value = stat_names.get(w)
            if stat_names.get(sid) == OP_NAME_STAT and value:
                op_names[key] = value
    ops = []
    for line in lines:
        name, t0, events = "", 0, []
        for num, v in _fields(buf, line):
            if num == _LINE_NAME:
                name = _text(buf, v)
            elif num == _LINE_TIMESTAMP_NS:
                t0 = v
            elif num == _LINE_EVENTS:
                events.append(v)
        if name != "XLA Ops":
            continue
        for ev in events:
            md = offset = dur = 0
            for num, v in _fields(buf, ev):
                if num == _EVENT_MD_ID:
                    md = v
                elif num == _EVENT_OFFSET_PS:
                    offset = v
                elif num == _EVENT_DURATION_PS:
                    dur = v
            if md in op_names:
                ops.append([op_names[md], t0 + offset // 1000, dur // 1000])
    return ops


def scope_path(op_name: str) -> Optional[str]:
    """``jit(f)/veilgraph.sweep/while/veilgraph.push/dot`` ->
    ``veilgraph.sweep/veilgraph.push``; None when no segment is a scope."""
    segs = [s for s in op_name.split("/") if s.startswith(PREFIX)]
    return "/".join(segs) if segs else None


def flatten(trace_dir: str, device_index: int = 0) -> Dict[str, list]:
    """``scoped_ops`` and ``program_spans`` of the newest ``.xplane.pb``
    under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    with open(paths[-1], "rb") as f:
        buf = f.read()
    out = {"scoped_ops": [], "program_spans": []}
    want_plane = f"/device:TPU:{device_index}"
    for num, plane in _fields(buf, (0, len(buf))):
        if num != _SPACE_PLANES:
            continue
        name = next((_text(buf, v) for n, v in _fields(buf, plane)
                     if n == _PLANE_NAME), "")
        if name == want_plane:
            for op_name, start, dur in _device_ops(buf, plane):
                path = scope_path(op_name)
                if path:
                    out["scoped_ops"].append([path, start, dur])
    data = ProfileData.from_serialized_xspace(buf)
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out["program_spans"].extend(
                    [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                    for ev in line.events if ev.name.startswith(PREFIX))
    return out


def reduce(flat: Dict[str, list], top: int = 10) -> Dict:
    """Scope seconds, program span counts and seconds, and idle gaps
    labelled by program span, inside the window."""
    lo, hi = T._window(flat)
    program_spans = flat.get("program_spans", [])
    per_scope = defaultdict(list)
    for path, a, b in T._clip(flat.get("scoped_ops", []), lo, hi):
        for seg in set(path.split("/")):
            per_scope[seg].append((a, b))
    scopes = {seg: sum(b - a for a, b in T._union(iv)) * 1e-9
              for seg, iv in per_scope.items()}
    spans: Dict[str, list] = {}
    for name, a, b in T._clip(program_spans, lo, hi):
        row = spans.setdefault(name, [0, 0.0])
        row[0] += 1
        row[1] += (b - a) * 1e-9
    gaps = []
    if program_spans:
        # the harness's own gap reduction, with the program's spans as
        # the only host spans besides the window
        window = [s for s in flat["spans"] if s[0] == "window"]
        gaps = T.reduce({"modules": [], "ops": flat["ops"],
                         "spans": window + program_spans},
                        top)["breakdown"]["idle_gaps"]
    return {"scopes": scopes, "program_spans": spans,
            "idle_gaps_by_program_span": gaps}
