"""Jaxpr contract lint: structural invariants of the traced hot path.

Walks a program's :class:`jax.extend.core.ClosedJaxpr` (recursing through every
sub-jaxpr — pjit bodies, scan/while carries, cond branches, shard_map,
custom-derivative wrappers) and enforces the contracts that keep the
approximate path cheap on hardware:

- **JXP-F64** — no 64-bit array anywhere (f64/c128/i64/u64): the engine
  is an f32/bf16-accumulate system; one stray wide dtype doubles HBM
  traffic and knocks the MXU path out.
- **JXP-WIDEN64** — no ``convert_element_type`` that widens into an
  8-byte dtype.  Widening into ≤4-byte dtypes is the legal
  accumulate-up pattern (bf16→f32, bool→i32 mask counts); f32→f64 is the
  silent promotion this rule exists to catch.
- **JXP-UNSORTED-SCATTER** — no *edge-scale* scatter-reduce
  (``scatter-add``/``-min``/``-max``/``-mul``) with
  ``indices_are_sorted=False``: the structural generalization of the
  PR 4 ``push_coo`` trace-count pin.  Sorted layouts make the same
  reduce a linear segmented pass; an unsorted edge-scale scatter in a
  hot program means some sweep bypassed the cached layouts.  The rule
  keys on the *updates* operand's element count against
  ``edge_threshold`` (half an edge buffer): scatters over an apply
  chunk (degree bookkeeping, O(chunk)) or the hot-set K-space
  (compaction marks, O(K)) are not the O(E)-random-HBM-writes failure
  class and are exempt.
- **JXP-CALLBACK** — no host callbacks (``pure_callback``/
  ``io_callback``/``debug_callback``/infeed/outfeed) inside a jitted
  sweep: each one is a device→host round-trip per execution.
- **JXP-EDGE-NODE-MATERIALIZE** — no intermediate of ``[E, N]``-class
  size (≥ ``spec.en_threshold`` elements): materializing an
  edge-count × vertex-count buffer is the quadratic blowup a push-based
  system exists to avoid.  Tiles *inside* ``pallas_call`` kernels are
  exempt — a ``[chunk, tile_n]`` one-hot block is the kernel's bounded
  VMEM working set, not an HBM materialization.

Use :func:`lint_jaxpr` on one traced program, or :func:`lint_programs`
over the :mod:`repro.analysis.programs` catalog.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Tuple

import jax
import numpy as np
from jax.extend import core as jax_core

from repro.analysis.findings import Finding

#: dtypes banned outright on the hot path
_WIDE_DTYPES = {"float64", "complex128", "int64", "uint64"}

#: scatter primitives that perform a reduction (plain ``scatter`` —
#: ``.at[].set`` — overwrites and is order-independent per index)
_SCATTER_REDUCE_PRIMS = {"scatter-add", "scatter-min", "scatter-max",
                         "scatter-mul"}

_CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                   "outside_call", "infeed", "outfeed"}

#: widening converts may target at most this many bytes per element
#: (bf16→f32 accumulation et al.); wider targets are JXP-WIDEN64
_MAX_WIDEN_TARGET_BYTES = 4


def _aval_of(v: Any):
    return getattr(v, "aval", None)


def _iter_subjaxprs(params: Dict[str, Any]) -> Iterable[Tuple[str, Any]]:
    """Every (param_name, jaxpr) nested in an eqn's params."""
    for key, val in params.items():
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for v in vals:
            if isinstance(v, jax_core.ClosedJaxpr):
                yield key, v.jaxpr
            elif isinstance(v, jax_core.Jaxpr):
                yield key, v


class _JaxprLinter:
    def __init__(self, program: str, *, en_threshold: Optional[int],
                 edge_threshold: Optional[int] = None,
                 check_f64: bool = True):
        self.program = program
        self.en_threshold = en_threshold
        self.edge_threshold = edge_threshold
        self.check_f64 = check_f64
        self.findings: List[Finding] = []
        self._seen_keys: Dict[str, int] = {}

    def _emit(self, rule: str, prim: str, detail: str) -> None:
        # aggregate per (rule, program, primitive): instruction indices are
        # not stable across refactors, so the key carries none — the first
        # occurrence's detail + a count is the diagnostic
        where = f"{self.program}:{prim}"
        key = f"{rule}::{where}"
        if key in self._seen_keys:
            self._seen_keys[key] += 1
            return
        self._seen_keys[key] = 1
        self.findings.append(Finding(
            pass_id="jaxpr", rule=rule, where=where, detail=detail))

    def _check_aval(self, aval, prim: str, role: str) -> None:
        dtype = getattr(aval, "dtype", None)
        if dtype is None:
            return
        if self.check_f64 and str(dtype) in _WIDE_DTYPES:
            self._emit(
                "JXP-F64", prim,
                f"{role} of {prim!r} has 64-bit dtype {dtype} "
                f"(shape {tuple(getattr(aval, 'shape', ()))}); the hot "
                f"path is f32/bf16-accumulate only")

    def walk(self, jaxpr: jax_core.Jaxpr, *, in_pallas: bool = False
             ) -> None:
        for v in list(jaxpr.invars) + list(jaxpr.constvars):
            self._check_aval(_aval_of(v), "<arg>", "input/const")
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            for ov in eqn.outvars:
                aval = _aval_of(ov)
                self._check_aval(aval, prim, "output")
                if (self.en_threshold is not None and not in_pallas
                        and aval is not None
                        and getattr(aval, "shape", None) is not None):
                    numel = int(np.prod(aval.shape)) if aval.shape else 1
                    if numel >= self.en_threshold:
                        self._emit(
                            "JXP-EDGE-NODE-MATERIALIZE", prim,
                            f"{prim!r} materializes "
                            f"{tuple(aval.shape)} = {numel} elements "
                            f">= [E, N]-class threshold "
                            f"{self.en_threshold}; edge×vertex "
                            f"intermediates defeat the push "
                            f"formulation")

            if prim == "convert_element_type":
                src = _aval_of(eqn.invars[0])
                dst = _aval_of(eqn.outvars[0])
                if src is not None and dst is not None:
                    src_b = np.dtype(src.dtype).itemsize
                    dst_b = np.dtype(dst.dtype).itemsize
                    if (dst_b > src_b
                            and dst_b > _MAX_WIDEN_TARGET_BYTES):
                        self._emit(
                            "JXP-WIDEN64", prim,
                            f"convert_element_type widens {src.dtype} → "
                            f"{dst.dtype} ({src_b}→{dst_b} B/elem); only "
                            f"accumulate-up widening into ≤"
                            f"{_MAX_WIDEN_TARGET_BYTES}-byte dtypes is "
                            f"allowlisted (bf16→f32)")

            if prim in _SCATTER_REDUCE_PRIMS:
                if not eqn.params.get("indices_are_sorted", False):
                    upd = _aval_of(eqn.invars[2]) if len(
                        eqn.invars) > 2 else None
                    upd_shape = getattr(upd, "shape", None)
                    upd_n = (int(np.prod(upd_shape))
                             if upd_shape is not None else None)
                    if (self.edge_threshold is None or upd_n is None
                            or upd_n >= self.edge_threshold):
                        self._emit(
                            "JXP-UNSORTED-SCATTER", prim,
                            f"{prim!r} with indices_are_sorted=False "
                            f"over {upd_n} update rows (edge-scale "
                            f"threshold {self.edge_threshold}) — an "
                            f"unsorted scatter-reduce (O(E) random HBM "
                            f"writes); hot sweeps must push through "
                            f"destination-sorted cached layouts "
                            f"(indices_are_sorted=True segmented "
                            f"reduce)")

            if prim in _CALLBACK_PRIMS or "callback" in prim:
                self._emit(
                    "JXP-CALLBACK", prim,
                    f"{prim!r} inside a jitted sweep — a host round-trip "
                    f"per execution; hot programs must stay on-device "
                    f"end to end")

            inner_pallas = in_pallas or prim == "pallas_call"
            for _, sub in _iter_subjaxprs(eqn.params):
                self.walk(sub, in_pallas=inner_pallas)


def lint_jaxpr(closed: jax_core.ClosedJaxpr, *, program: str,
               en_threshold: Optional[int] = None,
               edge_threshold: Optional[int] = None,
               check_f64: bool = True) -> List[Finding]:
    """Lint one traced program.

    ``en_threshold`` (elements) arms the ``[E, N]``-materialization rule —
    pass ``spec.en_threshold`` from the program catalog so the bound is
    derived from the graph spec the program was traced at.
    ``edge_threshold`` (update rows, ``spec.edge_capacity // 2`` from the
    catalog) scopes the unsorted-scatter rule to edge-scale scatters;
    ``None`` flags every unsorted scatter-reduce regardless of size.
    ``check_f64`` exists for fabricated-violation tests that trace under
    x64.
    """
    linter = _JaxprLinter(program, en_threshold=en_threshold,
                          edge_threshold=edge_threshold,
                          check_f64=check_f64)
    linter.walk(closed.jaxpr)
    # surface multiplicity in the (single) finding per aggregate key
    out = []
    for f in linter.findings:
        n = linter._seen_keys[f.key]
        if n > 1:
            f = Finding(f.pass_id, f.rule, f.where,
                        f"{f.detail} [{n} occurrences]")
        out.append(f)
    return out


def lint_programs(programs, *, interpret: bool = True) -> List[Finding]:
    """Trace + lint every program in a catalog (see
    :func:`repro.analysis.programs.catalog`)."""
    findings: List[Finding] = []
    for prog in programs:
        findings.extend(lint_jaxpr(
            prog.trace(), program=prog.name,
            en_threshold=prog.spec.en_threshold,
            edge_threshold=prog.spec.edge_threshold))
    return findings
