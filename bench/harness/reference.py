"""Plain references for the answers a cell's run produced.

Everything here is host numpy/scipy in float64 and imports nothing of the
program under test.  It is given the initial edges, the update blocks in
the order the harness registered them, and the answers the program
returned, and it recomputes what those answers must be:

- the graph after the applied blocks (live edge multiset and degrees);
- exact Gelly-style PageRank (``r(v) = (1-beta) + beta * sum r(u)/d(u)``
  over in-edges, ``num_iters`` sweeps from 1 on every active vertex, no
  dangling redistribution: the paper's normalisation), and exact BFS hop
  counts;
- the hot set that VeilGraph's rule (paper Eqs. 2-5: degree change ``r``,
  ``n``-hop expansion, the ``delta`` score-dilution expansion) puts there
  beyond float rounding, from the previous answer;
- one summarized answer of the model on a hot set that is known to be in
  the program's: that rule's set plus every vertex whose answer changed
  (a vertex outside the hot set is carried over bitwise);
- for BFS (min-plus with unit lengths), the summarized answer's fixed
  point, checked vertex by vertex on the same hot set.

The graph of each answer is the initial graph plus a few blocks, so the
initial graph's in-edge matrix is built once and each answer's blocks
ride beside it.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from functools import cached_property
from typing import List, Sequence

import numpy as np
import scipy.sparse as sp

_MIX = np.uint64(0x9E3779B97F4A7C15)


def edge_hash(src: np.ndarray, dst: np.ndarray) -> int:
    """Order-independent 64-bit fingerprint of an edge multiset; the
    fingerprint of a union is the sum of the parts' modulo 2**64."""
    key = (src.astype(np.uint64) << np.uint64(32)) | dst.astype(np.uint64)
    key = key * _MIX
    key ^= key >> np.uint64(29)
    return int(np.sum(key * _MIX, dtype=np.uint64))


class Edges:
    """A fixed edge list and what is derived from it once."""

    def __init__(self, src: np.ndarray, dst: np.ndarray, n: int):
        self.src = np.asarray(src, np.int32)
        self.dst = np.asarray(dst, np.int32)
        self.n = n

    @cached_property
    def out_deg(self) -> np.ndarray:
        return np.bincount(self.src, minlength=self.n).astype(np.int64)

    @cached_property
    def in_deg(self) -> np.ndarray:
        return np.bincount(self.dst, minlength=self.n).astype(np.int64)

    @cached_property
    def hash(self) -> int:
        return edge_hash(self.src, self.dst)

    @cached_property
    def a_in(self) -> sp.csr_matrix:
        """In-edge matrix: row ``v`` holds the sources of ``v``'s in-edges."""
        return sp.csr_matrix((np.ones(self.src.shape[0]),
                              (self.dst, self.src)), shape=(self.n, self.n))

    def row_blocks(self, parts: int) -> List[sp.csr_matrix]:
        cache = self.__dict__.setdefault("_row_blocks", {})
        if parts not in cache:
            cut = np.linspace(0, self.n, parts + 1).astype(np.int64)
            cache[parts] = [(lo, hi, self.a_in[lo:hi])
                            for lo, hi in zip(cut, cut[1:])]
        return cache[parts]


class GraphHistory:
    """The graph as of each answer: the initial edges plus every block
    that answers ``0..i`` applied (``at(-1)`` is the initial graph)."""

    def __init__(self, init_src, init_dst, blocks_src: Sequence[np.ndarray],
                 blocks_dst: Sequence[np.ndarray], block_answer: Sequence[int],
                 num_vertices: int):
        self.base = Edges(init_src, init_dst, num_vertices)
        empty = np.zeros(0, np.int32)
        self.src = np.concatenate([empty, *blocks_src]).astype(np.int32)
        self.dst = np.concatenate([empty, *blocks_dst]).astype(np.int32)
        sizes = [b.shape[0] for b in blocks_src]
        self.birth = np.repeat(np.asarray(block_answer, np.int32), sizes)

    def at(self, answer: int) -> "GraphAt":
        live = self.birth <= answer
        return GraphAt(self.base, self.src[live], self.dst[live])


class GraphAt:
    """One graph: a base edge list plus a few more edges; degrees,
    activity, pushes along in-edges."""

    def __init__(self, base: Edges, extra_src=None, extra_dst=None):
        self.base = base
        self.n = base.n
        self.xsrc = np.asarray(extra_src if extra_src is not None else [],
                               np.int32)
        self.xdst = np.asarray(extra_dst if extra_dst is not None else [],
                               np.int32)
        self.num_edges = base.src.shape[0] + self.xsrc.shape[0]
        self.out_deg = base.out_deg + np.bincount(self.xsrc, minlength=self.n)
        self.in_deg = base.in_deg + np.bincount(self.xdst, minlength=self.n)
        self.active = (self.out_deg + self.in_deg) > 0
        self.inv_out = np.where(self.out_deg > 0,
                                1.0 / np.maximum(self.out_deg, 1), 0.0)

    @classmethod
    def of(cls, src: np.ndarray, dst: np.ndarray, n: int) -> "GraphAt":
        """The graph of one edge list."""
        return cls(Edges(src, dst, n))

    @cached_property
    def hash(self) -> int:
        return (self.base.hash + edge_hash(self.xsrc, self.xdst)) % 2**64

    def out_neighbours(self, mark: np.ndarray) -> np.ndarray:
        """Vertices with an in-edge from a marked vertex."""
        out = np.zeros(self.n, bool)
        out[self.base.dst[mark[self.base.src]]] = True
        out[self.xdst[mark[self.xsrc]]] = True
        return out

    def push(self, x: np.ndarray, pool: ThreadPoolExecutor,
             parts: int) -> np.ndarray:
        """``y[v] = sum of x[u]`` over in-edges ``u -> v``."""
        y = np.empty(self.n)

        def part(blk):
            lo, hi, a = blk
            y[lo:hi] = a @ x

        list(pool.map(part, self.base.row_blocks(parts)))
        return y + np.bincount(self.xdst, weights=x[self.xsrc],
                               minlength=self.n)

    def in_rows(self, ids: np.ndarray) -> sp.csr_matrix:
        """In-edge rows of the vertices ``ids`` (sorted), as a
        ``len(ids) x n`` CSR matrix."""
        row = np.full(self.n, -1, np.int64)
        row[ids] = np.arange(ids.shape[0])
        r = row[self.xdst]
        sel = r >= 0
        extra = sp.csr_matrix((np.ones(int(sel.sum())),
                               (r[sel], self.xsrc[sel])),
                              shape=(ids.shape[0], self.n))
        return (self.base.a_in[ids] + extra).tocsr()


def exact_pagerank(g: GraphAt, *, beta: float, num_iters: int,
                   threads: int = 8) -> np.ndarray:
    """Exact PageRank in float64, the paper's normalisation."""
    x = g.active.astype(np.float64)
    with ThreadPoolExecutor(threads) as pool:
        for _ in range(num_iters):
            x = np.where(g.active,
                         (1.0 - beta) + beta * g.push(x * g.inv_out, pool,
                                                      threads), 0.0)
    return x


def exact_bfs(g: GraphAt, source: int) -> np.ndarray:
    """Hop counts from ``source`` along directed edges (inf where none)."""
    d = np.full(g.n, np.inf)
    d[source] = 0.0
    frontier = np.zeros(g.n, bool)
    frontier[source] = True
    level = 0
    while frontier.any():
        level += 1
        frontier = g.out_neighbours(frontier) & ~np.isfinite(d)
        d[frontier] = level
    return d


def finite_churn(new: np.ndarray, old: np.ndarray) -> np.ndarray:
    """Per-vertex change: ``|new - old|`` where both are finite, 1 where
    exactly one is, 0 else (the BFS volatility signal of the model)."""
    new = np.asarray(new, np.float64)
    old = np.asarray(old, np.float64)
    both = np.isfinite(new) & np.isfinite(old)
    diff = np.subtract(new, old, out=np.zeros_like(new), where=both)
    return np.where(both, np.abs(diff), (new != old).astype(np.float64))


def certain_hot(g: GraphAt, prev: GraphAt, *, r: float, n: int,
                margin: float = 1e-5) -> np.ndarray:
    """Vertices the model's Eqs. 2-3 put in the hot set beyond rounding.

    Eq. 2 with out-degrees: new vertices, and vertices whose out-degree
    moved by more than ``r`` of its previous value (here by more than
    ``r * (1 + margin)``: degrees are whole numbers, so only a ratio at
    ``r`` itself, where float32 rounding decides, is left out); Eq. 3:
    their ``n``-hop out-neighbourhood."""
    d_prev = prev.out_deg
    moved = np.where(d_prev > 0,
                     np.abs(g.out_deg - d_prev) > r * (1 + margin) * d_prev,
                     g.out_deg > 0)
    hot = g.active & (~prev.active | moved)
    for _ in range(n):
        hot = hot | g.out_neighbours(hot)
    return hot & g.active


def rule_hot(g: GraphAt, prev: GraphAt, v_s: np.ndarray, *, r: float,
             n: int, delta: float, hop_cap: int,
             margin: float = 1e-3) -> np.ndarray:
    """The whole hot set of the model's rule (Eqs. 2-5) beyond rounding:
    :func:`certain_hot`, then the Δ expansion.  A vertex ``v`` reached in
    ``h`` hops from that set joins while ``h <= f(v) - margin``, with
    ``f(v) = log(n + d * v_s(v) / (delta * deg(v))) / log(d)`` clamped to
    ``[0, hop_cap]``, ``d`` the mean out-degree over active vertices and
    ``v_s`` the previous answer's volatility (PageRank: its scores);
    expansion goes on only through vertices that joined."""
    k_rn = certain_hot(g, prev, r=r, n=n)
    d_bar = max(g.out_deg[g.active].sum() / max(g.active.sum(), 1),
                1.0 + 1e-6)
    arg = n + d_bar * np.maximum(v_s, 0.0) / (max(delta, 1e-9)
                                               * np.maximum(g.out_deg, 1))
    f = np.clip(np.log(np.maximum(arg, 1e-9)) / np.log(d_bar), 0.0, hop_cap)
    hot = k_rn.copy()
    frontier = k_rn.copy()
    for h in range(1, hop_cap + 1):
        joined = g.out_neighbours(frontier) & ~hot & (f >= h + margin)
        if not joined.any():
            break
        hot |= joined
        frontier |= joined
    return hot & g.active


def summarized_pagerank(g: GraphAt, hot: np.ndarray, prev_answer: np.ndarray,
                        *, beta: float, num_iters: int) -> np.ndarray:
    """VeilGraph's summarized PageRank on ``hot``: hot vertices iterate
    ``num_iters`` times from their previous values against hot in-edges
    and the frozen contribution of cold in-neighbours; returns the hot
    vertices' values, in the order of ``np.nonzero(hot)``."""
    ids = np.nonzero(hot)[0]
    a_h = g.in_rows(ids)
    v = prev_answer.astype(np.float64) * g.inv_out
    x = prev_answer[ids].astype(np.float64)
    for _ in range(num_iters):
        v[ids] = x * g.inv_out[ids]
        x = (1.0 - beta) + beta * (a_h @ v)
    return x


def bfs_fixed_point(g: GraphAt, hot: np.ndarray, answer: np.ndarray,
                    prev_answer: np.ndarray, source: int) -> np.ndarray:
    """What the summarized min-plus sweep's unique fixed point holds on
    ``hot``, given the answer itself everywhere: ``min(previous, min over
    in-edges of d(u) + 1)``, 0 at the source.  In ``np.nonzero(hot)``
    order."""
    ids = np.nonzero(hot)[0]
    a_h = g.in_rows(ids)
    d = answer.astype(np.float64)
    via = d[a_h.indices] + 1.0
    best = np.full(ids.shape[0], np.inf)
    rows = np.diff(a_h.indptr) > 0
    if via.size:
        best[rows] = np.minimum.reduceat(via, a_h.indptr[:-1][rows])
    want = np.minimum(prev_answer[ids].astype(np.float64), best)
    return np.where(ids == source, 0.0, want)


def rel_err(got: np.ndarray, want: np.ndarray, floor: float) -> float:
    """Largest ``|got - want| / max(want, floor)``."""
    got = np.asarray(got, np.float64)
    if got.size == 0:
        return 0.0
    return float(np.max(np.abs(got - want) / np.maximum(want, floor)))


def changed(answer: np.ndarray, prev_answer: np.ndarray) -> np.ndarray:
    """Vertices whose value differs bitwise from the previous answer."""
    return answer != prev_answer
