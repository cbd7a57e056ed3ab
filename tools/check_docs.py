#!/usr/bin/env python3
"""Docs gate: intra-repo markdown links resolve + public API is documented.

Two pure-stdlib checks (no jax import, so the CI job needs no deps):

1. **Markdown links** — every relative link target in the repo's tracked
   ``*.md`` files must exist on disk (anchors are stripped; absolute URLs
   and ``mailto:`` are ignored).  Catches docs pointing at renamed files.
2. **Docstrings** — every *public* top-level function and class in the
   graph-system API modules (``PUBLIC_API_MODULES``) must carry a
   docstring, and so must every public method defined directly on the
   classes named in ``STRICT_CLASSES`` (the plugin/engine surfaces users
   subclass or call).  Checked via ``ast``, so decorated/jitted functions
   count like plain ones.

Exit status is non-zero with one line per violation — wire into CI:

    python tools/check_docs.py
"""

from __future__ import annotations

import ast
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

#: directories whose markdown is not ours to police
SKIP_DIRS = {".git", ".pytest_cache", "artifacts", "node_modules",
             ".claude", "__pycache__"}

#: the documented graph-system surface — every public top-level def/class
#: here must have a docstring
PUBLIC_API_MODULES = [
    "src/repro/analysis/ast_lint.py",
    "src/repro/analysis/findings.py",
    "src/repro/analysis/hlo_audit.py",
    "src/repro/analysis/jaxpr_lint.py",
    "src/repro/analysis/programs.py",
    "src/repro/analysis/retrace.py",
    "src/repro/api.py",
    "src/repro/compile_cache.py",
    "src/repro/core/algorithm.py",
    "src/repro/core/backend.py",
    "src/repro/core/engine.py",
    "src/repro/core/epoch.py",
    "src/repro/core/fused.py",
    "src/repro/core/hits.py",
    "src/repro/core/hotset.py",
    "src/repro/core/katz.py",
    "src/repro/core/pagerank.py",
    "src/repro/core/policies.py",
    "src/repro/core/semiring.py",
    "src/repro/core/traversal.py",
    "src/repro/graph/csr.py",
    "src/repro/graph/generators.py",
    "src/repro/graph/graph.py",
    "src/repro/graph/partition.py",
    "src/repro/hardware.py",
    "src/repro/kernels/spmv/ops.py",
    "src/repro/metrics/ranking.py",
    "src/repro/metrics/rbo.py",
    "src/repro/serve/graph.py",
    "src/repro/stream/stream.py",
]

#: classes whose *methods* are part of the public contract (subclassed by
#: users or called directly); public methods defined on them need docs too
STRICT_CLASSES = {"StreamingAlgorithm", "Semiring", "VeilGraphEngine",
                  "VeilGraphSession", "GraphState", "EdgeLayout",
                  "ShardedEdgeLayout", "SummaryBuffers",
                  "GraphServingEngine", "QueryTicket", "ServeStats"}

_LINK_RE = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
_CODE_FENCE_RE = re.compile(r"```.*?```", re.DOTALL)


def iter_markdown() -> list[Path]:
    out = []
    for p in REPO.rglob("*.md"):
        if not any(part in SKIP_DIRS for part in p.relative_to(REPO).parts):
            out.append(p)
    return sorted(out)


def check_links() -> list[str]:
    errors = []
    for md in iter_markdown():
        text = _CODE_FENCE_RE.sub("", md.read_text(encoding="utf-8"))
        for target in _LINK_RE.findall(text):
            if re.match(r"^[a-z][a-z0-9+.-]*:", target):  # http:, mailto:, …
                continue
            path_part = target.split("#", 1)[0]
            if not path_part:  # pure in-page anchor
                continue
            resolved = (md.parent / path_part).resolve()
            if not resolved.exists():
                errors.append(
                    f"{md.relative_to(REPO)}: broken link -> {target}")
    return errors


def _has_doc(node: ast.AST) -> bool:
    return ast.get_docstring(node) is not None


def check_docstrings() -> list[str]:
    errors = []
    for rel in PUBLIC_API_MODULES:
        path = REPO / rel
        if not path.exists():
            errors.append(f"{rel}: listed in PUBLIC_API_MODULES but missing")
            continue
        tree = ast.parse(path.read_text(encoding="utf-8"))
        if not _has_doc(tree):
            errors.append(f"{rel}: missing module docstring")
        for node in tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if not node.name.startswith("_") and not _has_doc(node):
                    errors.append(
                        f"{rel}:{node.lineno}: public function "
                        f"{node.name!r} missing docstring")
            elif isinstance(node, ast.ClassDef):
                if not node.name.startswith("_") and not _has_doc(node):
                    errors.append(
                        f"{rel}:{node.lineno}: public class "
                        f"{node.name!r} missing docstring")
                if node.name not in STRICT_CLASSES:
                    continue
                for item in node.body:
                    if (isinstance(item,
                                   (ast.FunctionDef, ast.AsyncFunctionDef))
                            and not item.name.startswith("_")
                            and not _has_doc(item)):
                        errors.append(
                            f"{rel}:{item.lineno}: public method "
                            f"{node.name}.{item.name} missing docstring")
    return errors


def main() -> int:
    errors = check_links() + check_docstrings()
    for e in errors:
        print(e)
    checked = len(PUBLIC_API_MODULES)
    if errors:
        print(f"\ncheck_docs: {len(errors)} violation(s) across "
              f"{checked} API modules + markdown tree")
        return 1
    print(f"check_docs: OK ({checked} API modules, "
          f"{len(iter_markdown())} markdown files)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
