"""Tier-1 tests for the repro.analysis static-analysis layer.

Two halves, mirroring docs/analysis.md:

- **Fabricated violations** — one per rule family (injected f64, an
  unsorted edge-scale scatter, a host callback, an [E, N]
  materialization, an oversized all-gather, a retrace-per-iteration
  loop, a plugin holding a traced array, a hot-module host sync) must
  each be caught with a precise, actionable diagnostic.
- **Clean tree** — the shipped source and the committed baseline agree:
  the AST pass plus a hot-program subset of the jaxpr pass produce zero
  non-baseline findings.
"""

import ast
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import findings as F
from repro.analysis import ast_lint, hlo_audit, jaxpr_lint
from repro.analysis import programs as PR
from repro.analysis.retrace import TraceMonitor

REPO = Path(__file__).resolve().parents[1]
BASELINE = REPO / "benchmarks" / "analysis_baseline.json"


# ---------------------------------------------------------------------------
# finding / baseline model
# ---------------------------------------------------------------------------


def test_baseline_entries_require_reasons(tmp_path):
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps(
        {"allow": [{"rule": "R1", "where": "prog:op", "reason": "  "}]}))
    with pytest.raises(ValueError, match="no reason"):
        F.load_baseline(p)


def test_missing_baseline_is_empty():
    assert F.load_baseline(None) == []
    assert F.load_baseline(Path("/nonexistent/baseline.json")) == []


def test_check_partitions_new_allowlisted_stale():
    found = [F.Finding("ast", "R1", "a:b", "d1"),
             F.Finding("ast", "R2", "c:d", "d2")]
    baseline = [F.BaselineEntry("R1", "a:b", "known"),
                F.BaselineEntry("R3", "e:f", "fixed long ago")]
    new, matched, stale = F.check(found, baseline)
    assert [f.key for f in new] == ["R2::c:d"]
    assert [f.key for f in matched] == ["R1::a:b"]
    assert [e.key for e in stale] == ["R3::e:f"]
    report = F.render_report(found, baseline, passes_run=["ast"])
    assert report["ok"] is False
    assert report["allowlisted"][0]["reason"] == "known"


def test_stale_scoped_to_passes_run():
    # an AST-only run must not declare the jaxpr allowlist obsolete: only
    # entries owned by a pass that actually ran can go stale
    baseline = [F.BaselineEntry("JXP-UNSORTED-SCATTER", "p:scatter", "known"),
                F.BaselineEntry("AST-HOST-SYNC", "f.py:g", "fixed")]
    _, _, stale = F.check([], baseline, passes_run=["ast"])
    assert [e.key for e in stale] == ["AST-HOST-SYNC::f.py:g"]
    _, _, stale = F.check([], baseline, passes_run=["ast", "jaxpr"])
    assert {e.rule for e in stale} == {"JXP-UNSORTED-SCATTER",
                                      "AST-HOST-SYNC"}
    assert F.pass_of_rule("HLO-ALLGATHER-BYTES") == "hlo"
    assert F.pass_of_rule("RT-RETRACE") == "retrace"
    assert F.pass_of_rule("UNKNOWN-RULE") is None


# ---------------------------------------------------------------------------
# fabricated jaxpr violations
# ---------------------------------------------------------------------------


def test_jaxpr_catches_injected_f64():
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        closed = jax.make_jaxpr(lambda x: x * 2.0)(
            jax.ShapeDtypeStruct((128,), jnp.float64))
    finally:
        jax.config.update("jax_enable_x64", prev)
    found = jaxpr_lint.lint_jaxpr(closed, program="fab[f64]")
    f64 = [f for f in found if f.rule == "JXP-F64"]
    assert f64, "injected float64 op not caught"
    assert "float64" in f64[0].detail and "fab[f64]" in f64[0].where


def test_jaxpr_catches_widening_convert():
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        closed = jax.make_jaxpr(lambda x: x.astype(jnp.float64))(
            jax.ShapeDtypeStruct((64,), jnp.float32))
    finally:
        jax.config.update("jax_enable_x64", prev)
    found = jaxpr_lint.lint_jaxpr(closed, program="fab[widen]")
    assert any(f.rule == "JXP-WIDEN64" and "float32" in f.detail
               for f in found), "f32→f64 widening convert not caught"


def test_jaxpr_catches_unsorted_edge_scale_scatter():
    def unsorted_push(v, seg):
        return jax.ops.segment_sum(v, seg, num_segments=64)

    closed = jax.make_jaxpr(unsorted_push)(
        jnp.zeros((4096,), jnp.float32), jnp.zeros((4096,), jnp.int32))
    found = jaxpr_lint.lint_jaxpr(closed, program="fab[scatter]",
                                  edge_threshold=1024)
    hits = [f for f in found if f.rule == "JXP-UNSORTED-SCATTER"]
    assert hits, "unsorted edge-scale scatter-add not caught"
    assert "indices_are_sorted=False" in hits[0].detail
    assert "4096" in hits[0].detail  # names the measured update size


def test_jaxpr_scatter_rule_exempts_chunk_scale():
    # the same scatter under the edge-scale threshold (degree bookkeeping
    # over an apply chunk) is not the O(E) failure class
    def chunk_update(deg, idx):
        return deg.at[idx].add(1)

    closed = jax.make_jaxpr(chunk_update)(
        jnp.zeros((1024,), jnp.int32), jnp.zeros((64,), jnp.int32))
    found = jaxpr_lint.lint_jaxpr(closed, program="fab[chunk]",
                                  edge_threshold=8192)
    assert not [f for f in found if f.rule == "JXP-UNSORTED-SCATTER"]


def test_jaxpr_catches_host_callback():
    def with_callback(x):
        return jax.pure_callback(
            lambda a: a, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    closed = jax.make_jaxpr(with_callback)(jnp.zeros((8,), jnp.float32))
    found = jaxpr_lint.lint_jaxpr(closed, program="fab[callback]")
    assert any(f.rule == "JXP-CALLBACK" and "host round-trip" in f.detail
               for f in found), "pure_callback in jitted program not caught"


def test_jaxpr_catches_edge_node_materialization():
    closed = jax.make_jaxpr(
        lambda e, n: e[:, None] * n[None, :])(
        jnp.zeros((512,), jnp.float32), jnp.zeros((256,), jnp.float32))
    found = jaxpr_lint.lint_jaxpr(closed, program="fab[EN]",
                                  en_threshold=512 * 256 // 2)
    hits = [f for f in found if f.rule == "JXP-EDGE-NODE-MATERIALIZE"]
    assert hits, "[E, N] outer-product intermediate not caught"
    assert "131072" in hits[0].detail  # the materialized element count


# ---------------------------------------------------------------------------
# fabricated HLO violations
# ---------------------------------------------------------------------------

_FAKE_HLO = """HloModule fake

ENTRY %main (p0: f32[4096]) -> f32[131072] {
  %p0 = f32[4096]{0} parameter(0)
  %ag = f32[131072]{0} all-gather(f32[4096]{0} %p0), replica_groups={}, dimensions={0}
  ROOT %r = f32[131072]{0} add(f32[131072]{0} %ag, f32[131072]{0} %ag)
}
"""


def test_hlo_catches_oversized_all_gather():
    # budget: one 4-byte edge buffer at E_cap=16384 = 64 KiB; the fake
    # module all-gathers 512 KiB (an edge stream replicated 8×)
    budgets = hlo_audit.CollectiveBudgets(all_gather_max=4.0 * 16384)
    found = hlo_audit.audit_hlo_text(_FAKE_HLO, budgets, program="fab[ag]")
    assert len(found) == 1 and found[0].rule == "HLO-ALLGATHER-BYTES"
    assert "5.243e+05" in found[0].detail  # measured bytes
    assert "6.554e+04" in found[0].detail  # the budget it broke


def test_hlo_within_budget_is_clean():
    budgets = hlo_audit.CollectiveBudgets(all_gather_max=1e9)
    assert hlo_audit.audit_hlo_text(_FAKE_HLO, budgets,
                                    program="fab[ag]") == []


def test_hlo_catches_peak_temp():
    budgets = hlo_audit.CollectiveBudgets(temp_bytes_max=1e6)
    found = hlo_audit.audit_hlo_text(
        _FAKE_HLO, budgets, program="fab[temp]", temp_bytes=2e9)
    assert [f.rule for f in found] == ["HLO-TEMP-BYTES"]


def test_spec_budgets_are_ordered():
    spec = PR.GraphSpec()
    b = hlo_audit.budgets_for_spec(spec)
    # bucket exchange ≪ edge buffer ≪ temp scratch — the budgets separate
    assert b.all_to_all_max < b.all_gather_max < b.temp_bytes_max
    assert spec.edge_threshold == spec.edge_capacity // 2
    assert spec.en_threshold == spec.edge_capacity * spec.node_capacity // 2


# ---------------------------------------------------------------------------
# fabricated retrace violations
# ---------------------------------------------------------------------------


def test_retrace_catches_per_iteration_retrace():
    @jax.jit
    def step(x):
        return x * 2.0

    with TraceMonitor() as mon:
        step(jnp.zeros((4,), jnp.float32))
        warm = mon.snapshot()
        for i in range(3):
            # shape changes per iteration — a fabricated geometry drift
            step(jnp.zeros((5 + i,), jnp.float32))
    found = mon.check_warm(warm, scenario="fab-loop")
    hits = [f for f in found if "step" in f.where]
    assert hits and hits[0].rule == "RT-RETRACE"
    assert "3×" in hits[0].detail  # one retrace per post-warm-up iteration


def test_retrace_stable_loop_is_clean():
    @jax.jit
    def step(x):
        return x + 1.0

    with TraceMonitor() as mon:
        step(jnp.zeros((4,), jnp.float32))
        warm = mon.snapshot()
        for _ in range(3):
            step(jnp.zeros((4,), jnp.float32))
    assert mon.check_warm(warm, scenario="fab-stable") == []


def test_retrace_budget_contract():
    @jax.jit
    def leaky(x):
        return x - 1.0

    with TraceMonitor() as mon:
        for i in range(4):
            leaky(jnp.zeros((2 + i,), jnp.float32))
    found = mon.check({"leaky": 1}, scenario="fab-budget")
    hits = [f for f in found if "leaky" in f.where]
    assert hits and "4×" in hits[0].detail and "budget 1" in hits[0].detail


# ---------------------------------------------------------------------------
# fabricated AST violations
# ---------------------------------------------------------------------------


def _lint_source(rel: str, source: str, *, plugin_bases=None):
    linter = ast_lint._Linter(rel, source,
                              plugin_bases=plugin_bases
                              if plugin_bases is not None
                              else {"StreamingAlgorithm"})
    linter.visit(ast.parse(source))
    return linter.findings


def test_ast_catches_plugin_violations(tmp_path):
    bad = tmp_path / "fab_plugins.py"
    bad.write_text(
        "from dataclasses import dataclass\n"
        "import jax\n"
        "import jax.numpy as jnp\n"
        "class NotFrozen(StreamingAlgorithm):\n"
        "    pass\n"
        "@dataclass(frozen=True)\n"
        "class HoldsArray(StreamingAlgorithm):\n"
        "    weights: jax.Array\n"
        "@dataclass(frozen=True)\n"
        "class ArrayDefault(StreamingAlgorithm):\n"
        "    ranks = jnp.zeros(4)\n"
        "class Transitive(NotFrozen):\n"
        "    pass\n")
    found = ast_lint.lint_files([bad],
                                plugin_bases={"StreamingAlgorithm"})
    by_rule = {}
    for f in found:
        by_rule.setdefault(f.rule, []).append(f)
    frozen = by_rule.get("AST-PLUGIN-FROZEN", [])
    # NotFrozen, ArrayDefault? no — ArrayDefault is frozen; Transitive
    # inherits from a plugin subclass and is itself unfrozen
    assert {f.where.split(":")[-1] for f in frozen} == {
        "NotFrozen", "Transitive"}
    arrays = by_rule.get("AST-PLUGIN-ARRAY-FIELD", [])
    details = " | ".join(f.detail for f in arrays)
    assert "weights" in details and "jnp.zeros" in details


def test_ast_catches_hot_module_host_sync():
    # lint a fabricated source *as if* it were a hot module
    rel = "src/repro/core/fused.py"
    found = _lint_source(rel, (
        "import jax\n"
        "import numpy as np\n"
        "def hot_step(x):\n"
        "    x.block_until_ready()\n"
        "    a = float(jax.numpy.sum(x))\n"
        "    b = np.asarray(x)\n"
        "    c = jax.device_get(x)\n"
        "    return a, b, c\n"))
    rules = [f.rule for f in found]
    assert rules.count("AST-HOST-SYNC") == 4
    assert all("hot_step" in f.where for f in found)


def test_ast_inline_waiver_suppresses():
    rel = "src/repro/core/fused.py"
    found = _lint_source(rel, (
        "import jax\n"
        "def hot_step(x):\n"
        "    # analysis: allow(AST-HOST-SYNC): fabricated waiver test\n"
        "    return jax.device_get(x)\n"))
    assert found == []


def test_ast_catches_direct_segment_reduce_in_core():
    found = _lint_source("src/repro/core/fake_algo.py", (
        "import jax\n"
        "def sweep(v, seg):\n"
        "    return jax.ops.segment_sum(v, seg, num_segments=8)\n"))
    assert [f.rule for f in found] == ["AST-SEGMENT-REDUCE"]
    # ... and backend.py itself is the designated dispatch point
    assert _lint_source("src/repro/core/backend.py", (
        "import jax\n"
        "def push_coo(v, seg):\n"
        "    return jax.ops.segment_sum(v, seg, num_segments=8)\n")) == []


def test_ast_catches_hardcoded_kernel_geometry():
    found = _lint_source("src/repro/core/fused.py", (
        "from repro.kernels.spmv.ops import spmv_push\n"
        "def sweep(v, lay):\n"
        "    return spmv_push(v, lay, tile_n=256)\n"))
    assert [f.rule for f in found] == ["AST-KERNEL-GEOMETRY"]
    assert "tile_n=256" in found[0].detail


def test_ast_skip_list_excludes_lm_substrate():
    """No file is exempt: the pass covers every module of ``src/repro``,
    and no language-model code is left there to exempt."""
    root = ast_lint.REPO_ROOT / "src" / "repro"
    files = {p.as_posix() for p in ast_lint.iter_source_files()}
    assert files == {p.as_posix() for p in root.rglob("*.py")}
    assert not any("/models/" in f or "/train/" in f for f in files)
    assert any(f.endswith("core/backend.py") for f in files)


# ---------------------------------------------------------------------------
# clean tree vs the committed baseline
# ---------------------------------------------------------------------------


def test_ast_pass_clean_against_baseline():
    baseline = F.load_baseline(BASELINE)
    found = ast_lint.lint_files()
    new, _, _ = F.check(found, baseline)
    assert new == [], "new AST findings:\n" + "\n".join(map(str, new))


def test_jaxpr_pass_clean_on_hot_programs():
    baseline = F.load_baseline(BASELINE)
    spec = PR.GraphSpec()
    cat = [p for p in PR.catalog(spec)
           if p.name.startswith(("push[", "push_coo", "build_summary",
                                 "engine_apply"))]
    assert len(cat) >= 6
    found = jaxpr_lint.lint_programs(cat)
    new, matched, _ = F.check(found, baseline)
    assert new == [], "new jaxpr findings:\n" + "\n".join(map(str, new))
    # the unsorted fallback is *in* the baseline, not silently unflagged
    assert any(f.where.startswith("push_coo") for f in matched)
    # the sorted push programs themselves are finding-free
    assert not [f for f in found if f.where.startswith("push[")]


def test_rebalance_decision_stays_on_device():
    from repro.graph.generators import gnm_edges
    from repro.graph.graph import from_edges
    from repro.graph.partition import (rebalance_decision,
                                       rebalance_sharded_layout,
                                       shard_slots)

    src, dst = gnm_edges(64, 256, seed=3)
    state = from_edges(src, dst, 64, 1024)
    slots = jnp.asarray(shard_slots(state.edge_capacity, 4))
    should, imb = rebalance_decision(state, slots, jnp.float32(1.0))
    # the verdict pair is a device computation, not a host float
    assert isinstance(should, jax.Array) and should.dtype == jnp.bool_
    assert isinstance(imb, jax.Array) and imb.dtype == jnp.float32
    # the compat wrapper agrees with the raw decision
    _, rebalanced, imbalance = rebalance_sharded_layout(
        state, num_shards=4, slots=slots, threshold=1.0)
    assert rebalanced == bool(should)
    assert imbalance == pytest.approx(float(imb))
