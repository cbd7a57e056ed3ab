"""Bytes an exact PageRank sweep needs, from each answer's own counts.

An iteration over a graph of ``num_edges`` live edges and ``num_nodes``
active vertices must read, for each edge, the index of the vertex it
comes from and that vertex's rank (4 B + 4 B), and for each vertex its
rank and 1/out-degree and write its new rank (3 x 4 B).  This is what
the algorithm needs, not what one implementation moves: the layout's
padding slots, whole cache lines under a random gather and the kernel's
destination ids are left out, so a sweep that moves less reads a higher
share of the roofline and never one above 100%.  Bytes bound the sweep:
its 2 operations per edge need 1/4 operation per byte, far below the
chip's ratio of peak operations to peak bandwidth.
"""

from __future__ import annotations

from typing import Dict, Iterable

EDGE_BYTES = 8
VERTEX_BYTES = 12

#: ``QueryStats.action`` of an answer recomputed over the whole graph
EXACT = "compute-exact"


def sweep_bytes(iterations: int, num_edges: int, num_nodes: int) -> int:
    """Bytes ``iterations`` full sweeps need."""
    return iterations * (EDGE_BYTES * num_edges + VERTEX_BYTES * num_nodes)


def window_bytes(answers: Iterable[Dict]) -> int:
    """Bytes the exact answers among ``answers`` (the harness's
    per-answer records) need."""
    return sum(sweep_bytes(a["iterations"], a["num_edges"], a["num_nodes"])
               for a in answers if a["action"] == EXACT)
