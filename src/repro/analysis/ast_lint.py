"""Project AST lint: source-level convention checks for the engine surface.

Pure-stdlib (``ast``) rules over all of ``src/repro``:

- **AST-SEGMENT-REDUCE** — no direct ``segment_sum``/``segment_min``/
  ``segment_max``/``segment_prod`` calls in ``core/`` outside
  ``backend.py``: every sweep must go through :func:`repro.core.backend.
  push` (or the semiring's single dispatch point) so layouts, masks and
  sortedness flags can't drift per call site.
- **AST-PLUGIN-FROZEN** / **AST-PLUGIN-ARRAY-FIELD** — every
  ``StreamingAlgorithm`` subclass must be a ``@dataclass(frozen=True)``
  (it rides through jit as a *static*, hashable argument) and must never
  declare an array-typed field or an array default: per-query traced
  state belongs in ``per_query_params``/``init_state``, never on the
  plugin (the PR 6 contract, machine-checked).
- **AST-HOST-SYNC** — no ``.block_until_ready()``, ``jax.device_get``,
  ``np.asarray(...)`` or ``float(...)``/``int(...)`` coercions of
  computed values inside the hot modules (:data:`HOT_MODULES`): each one
  is a device→host sync that serializes the async dispatch pipeline.
  The engine/serving orchestration layers are the designated host
  boundary and are deliberately not in the hot list.
- **AST-KERNEL-GEOMETRY** — call sites must not hardcode literal
  ``tile_n=``/``chunk=`` kernel geometry outside the kernel/autotuner
  modules themselves: geometry flows from the autotune resolver through
  layout metadata (``EngineConfig.autotune`` → ``build_layout(tile_n=,
  chunk=)`` → ``push`` reads the stamp), so a literal at a call site
  silently pins an untuned shape.

Intentional violations are either allowlisted in
``benchmarks/analysis_baseline.json`` (with a reason) or waived inline
with a ``# analysis: allow(RULE): reason`` comment on the offending line
(or the line above) — see ``docs/analysis.md`` for when to use which.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set

from repro.analysis.findings import Finding

REPO_ROOT = Path(__file__).resolve().parents[3]

#: modules where a hidden device→host sync is a hot-path bug, not a
#: convenience: the propagation primitives, the fused query/summary path
#: and the layout/partition builders — everything that runs per query or
#: per applied update batch.  ``core/engine.py`` and ``serve/graph.py``
#: are the host orchestration boundary and intentionally absent.
HOT_MODULES: tuple = (
    "src/repro/core/backend.py",
    "src/repro/core/epoch.py",
    "src/repro/core/fused.py",
    "src/repro/core/hits.py",
    "src/repro/core/hotset.py",
    "src/repro/core/katz.py",
    "src/repro/core/pagerank.py",
    "src/repro/core/semiring.py",
    "src/repro/core/traversal.py",
    "src/repro/graph/csr.py",
    "src/repro/graph/partition.py",
    "src/repro/kernels/spmv/kernel.py",
    "src/repro/kernels/spmv/ops.py",
)

#: ``core/`` modules allowed to call XLA segment reduces directly: the
#: propagation backend itself (``push_coo``'s fallback lives there).
SEGMENT_REDUCE_ALLOWED: tuple = ("src/repro/core/backend.py",)

#: kernel entry points whose geometry kwargs must come from the autotune
#: resolver (a variable / layout stamp), never a literal at the call site
_KERNEL_ENTRY_POINTS = {
    "spmv_push", "spmv_push_batched",
    "spmv_reduce_push", "spmv_reduce_push_batched",
}
#: modules that *define* geometry: the kernels, their autotuner, and the
#: backend's layout builders (where the resolved geometry is stamped)
_GEOMETRY_ALLOWED: tuple = (
    "src/repro/kernels/spmv/",
    "src/repro/core/backend.py",
)

_SEGMENT_FNS = {"segment_sum", "segment_min", "segment_max", "segment_prod"}

_WAIVER_RE = re.compile(r"#\s*analysis:\s*allow\(([A-Z0-9\-, ]+)\)")

_ARRAY_ANNOTATIONS = re.compile(
    r"\b(jax\.Array|Array|jnp\.ndarray|np\.ndarray|numpy\.ndarray|"
    r"ArrayLike|DeviceArray)\b")
_ARRAY_FACTORIES = {"array", "asarray", "zeros", "ones", "full", "arange",
                    "linspace", "empty", "zeros_like", "ones_like",
                    "full_like"}


def _rel(path: Path) -> str:
    try:
        return path.resolve().relative_to(REPO_ROOT).as_posix()
    except ValueError:
        return path.as_posix()


def _call_name(node: ast.Call) -> str:
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return ""


def _dotted(node: ast.AST) -> str:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _waivers(source: str) -> Dict[int, Set[str]]:
    """Line → waived rule ids, from ``# analysis: allow(RULE): reason``."""
    out: Dict[int, Set[str]] = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = _WAIVER_RE.search(line)
        if m:
            rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
            out[i] = rules
    return out


class _ScopeVisitor(ast.NodeVisitor):
    """Tracks the enclosing def/class name for stable ``where`` keys."""

    def __init__(self):
        self.scope: List[str] = []

    def _scope_name(self) -> str:
        return ".".join(self.scope) if self.scope else "<module>"

    def visit_FunctionDef(self, node):
        self.scope.append(node.name)
        self.generic_visit(node)
        self.scope.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_ClassDef(self, node):
        self.scope.append(node.name)
        self.generic_visit(node)
        self.scope.pop()


class _Linter(_ScopeVisitor):
    def __init__(self, rel: str, source: str, *,
                 plugin_bases: Set[str]):
        super().__init__()
        self.rel = rel
        self.findings: List[Finding] = []
        self.waivers = _waivers(source)
        self.plugin_bases = plugin_bases
        self.in_core = rel.startswith("src/repro/core/")
        self.is_hot = rel in HOT_MODULES
        self.segment_ok = rel in SEGMENT_REDUCE_ALLOWED
        self.geometry_ok = any(rel == g or rel.startswith(g)
                               for g in _GEOMETRY_ALLOWED)

    def _emit(self, rule: str, node: ast.AST, detail: str) -> None:
        line = getattr(node, "lineno", 0)
        for waived_line in (line, line - 1):
            if rule in self.waivers.get(waived_line, set()):
                return
        self.findings.append(Finding(
            pass_id="ast", rule=rule,
            where=f"{self.rel}:{self._scope_name()}",
            detail=f"line {line}: {detail}"))

    # -- AST-SEGMENT-REDUCE / AST-HOST-SYNC / AST-KERNEL-GEOMETRY ----------

    def visit_Call(self, node: ast.Call):
        name = _call_name(node)
        dotted = _dotted(node.func)

        if (self.in_core and not self.segment_ok
                and isinstance(node.func, ast.Name)
                and name in _SEGMENT_FNS):
            self._emit(
                "AST-SEGMENT-REDUCE", node,
                f"direct {name}() in core/ — route the reduce through "
                f"repro.core.backend.push (or the semiring dispatch) so "
                f"sortedness/masking can't drift per site")

        if self.is_hot:
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "block_until_ready":
                self._emit(
                    "AST-HOST-SYNC", node,
                    "block_until_ready() in a hot module — a device sync "
                    "that stalls async dispatch; force results only at the "
                    "engine/serving host boundary")
            elif dotted in ("jax.device_get", "device_get"):
                self._emit(
                    "AST-HOST-SYNC", node,
                    "jax.device_get() in a hot module — device→host "
                    "transfer; return arrays and let the orchestration "
                    "layer fetch once per batch")
            elif dotted in ("np.asarray", "numpy.asarray", "onp.asarray"):
                self._emit(
                    "AST-HOST-SYNC", node,
                    "np.asarray() in a hot module forces a device→host "
                    "copy when handed a traced/device array; keep hot-path "
                    "data in jnp")
            elif (isinstance(node.func, ast.Name)
                  and node.func.id in ("float", "int")
                  and node.args
                  and isinstance(node.args[0],
                                 (ast.Call, ast.Subscript))):
                self._emit(
                    "AST-HOST-SYNC", node,
                    f"{node.func.id}(...) of a computed value in a hot "
                    f"module — an implicit device→host read; compare on "
                    f"device and transfer one verdict instead")

        if not self.geometry_ok and name in _KERNEL_ENTRY_POINTS:
            for kw in node.keywords:
                if kw.arg in ("tile_n", "chunk") and \
                        isinstance(kw.value, ast.Constant) and \
                        isinstance(kw.value.value, int):
                    self._emit(
                        "AST-KERNEL-GEOMETRY", node,
                        f"{name}({kw.arg}={kw.value.value}) hardcodes "
                        f"kernel geometry at the call site — route through "
                        f"the autotune resolver "
                        f"(repro.kernels.spmv.autotune.tune_for_push) or "
                        f"the layout's stamped tile_n/tile_chunk")
        self.generic_visit(node)

    def visit_Attribute(self, node: ast.Attribute):
        # references count too: stashing jax.ops.segment_sum in a dispatch
        # table is still a direct segment reduce at this site
        if (self.in_core and not self.segment_ok
                and node.attr in _SEGMENT_FNS):
            self._emit(
                "AST-SEGMENT-REDUCE", node,
                f"direct {_dotted(node)} in core/ — route the reduce "
                f"through repro.core.backend.push (or the semiring "
                f"dispatch) so sortedness/masking can't drift per site")
        self.generic_visit(node)

    # -- AST-PLUGIN-FROZEN / AST-PLUGIN-ARRAY-FIELD -------------------------

    def visit_ClassDef(self, node: ast.ClassDef):
        base_names = {_dotted(b) or getattr(b, "id", "") for b in node.bases}
        base_names = {b.split(".")[-1] for b in base_names if b}
        is_plugin = bool(base_names & self.plugin_bases)
        if is_plugin:
            self.plugin_bases.add(node.name)  # transitive subclasses
        self.scope.append(node.name)
        if is_plugin:
            self._check_plugin(node)
        self.generic_visit(node)
        self.scope.pop()

    def _check_plugin(self, node: ast.ClassDef) -> None:
        frozen = False
        for dec in node.decorator_list:
            if isinstance(dec, ast.Call) and \
                    _dotted(dec.func).split(".")[-1] == "dataclass":
                for kw in dec.keywords:
                    if kw.arg == "frozen" and \
                            isinstance(kw.value, ast.Constant) and \
                            kw.value.value is True:
                        frozen = True
        if not frozen:
            self._emit(
                "AST-PLUGIN-FROZEN", node,
                f"StreamingAlgorithm subclass {node.name!r} is not a "
                f"@dataclass(frozen=True) — plugins ride through jit as "
                f"static (hashable) arguments; a mutable plugin retraces "
                f"or silently stales")
        for item in node.body:
            if isinstance(item, ast.AnnAssign) and \
                    isinstance(item.target, ast.Name):
                ann = ast.unparse(item.annotation)
                if _ARRAY_ANNOTATIONS.search(ann):
                    self._emit(
                        "AST-PLUGIN-ARRAY-FIELD", item,
                        f"plugin field {item.target.id!r} annotated "
                        f"{ann!r} — plugins must never store traced "
                        f"arrays; per-query state belongs in "
                        f"init_state/per_query_params")
                value = item.value
            elif isinstance(item, ast.Assign):
                value = item.value
            else:
                continue
            if isinstance(value, ast.Call):
                mod = _dotted(value.func)
                if (value.func and _call_name(value) in _ARRAY_FACTORIES
                        and mod.split(".")[0] in ("jnp", "np", "jax",
                                                  "numpy")):
                    self._emit(
                        "AST-PLUGIN-ARRAY-FIELD", item,
                        f"plugin field default calls {mod}() — an array "
                        f"default makes the plugin unhashable (and leaks "
                        f"one array across every query); use "
                        f"init_state/per_query_params")


def iter_source_files(root: Path = REPO_ROOT) -> List[Path]:
    """Every python file under ``src/repro``."""
    return sorted((root / "src" / "repro").rglob("*.py"))


def lint_files(paths: Optional[Iterable[Path]] = None,
               *, plugin_bases: Optional[Set[str]] = None) -> List[Finding]:
    """Run every AST rule over ``paths`` (default: the scoped tree).

    ``plugin_bases`` seeds the ``StreamingAlgorithm`` lineage (tests pass
    it to lint fabricated files in isolation); subclasses found during the
    walk extend it, so transitive plugins in later files are covered.
    """
    findings: List[Finding] = []
    bases = plugin_bases if plugin_bases is not None else {
        "StreamingAlgorithm"}
    for path in (iter_source_files() if paths is None else list(paths)):
        source = Path(path).read_text(encoding="utf-8")
        try:
            tree = ast.parse(source)
        except SyntaxError as e:  # pragma: no cover - tree is parseable
            findings.append(Finding(
                pass_id="ast", rule="AST-SYNTAX",
                where=f"{_rel(Path(path))}:<module>",
                detail=f"unparseable: {e}"))
            continue
        linter = _Linter(_rel(Path(path)), source, plugin_bases=bases)
        linter.visit(tree)
        findings.extend(linter.findings)
    # aggregate repeats of one (rule, scope): the key is what baselines
    # match on, so N sites in one scope are one finding with a count
    seen: Dict[str, Finding] = {}
    counts: Dict[str, int] = {}
    for f in findings:
        if f.key not in seen:
            seen[f.key] = f
            counts[f.key] = 1
        else:
            counts[f.key] += 1
    out = []
    for key, f in seen.items():
        if counts[key] > 1:
            f = Finding(f.pass_id, f.rule, f.where,
                        f"{f.detail} [{counts[key]} occurrences]")
        out.append(f)
    return out
