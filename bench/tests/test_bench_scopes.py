"""The reduction of the program's scopes and spans (``harness.scopes``)
on a small recorded trace: the window of a traced run of the test-sized
PageRank cell on one v5e, cut to its first answer, flattened with both
``harness.trace`` and ``harness.scopes``, op names cut to 48 characters.
"""

import copy
import json
from pathlib import Path

import pytest

import split
from harness import scopes as S
from harness import trace as T

DATA = Path(__file__).parent / "data"
STAGES = ("veilgraph.hotset", "veilgraph.summary", "veilgraph.sweep",
          "veilgraph.drift")


@pytest.fixture(scope="module")
def flat():
    return json.loads((DATA / "trace_scoped_small.json").read_text())


def _window(flat):
    (w,) = [s for s in flat["spans"] if s[0] == "window"]
    return w[1], w[1] + w[2]


def test_scope_path_keeps_the_program_segments():
    assert S.scope_path("jit(fused_query_step)/veilgraph.sweep/jit(f)/"
                        "while/body/veilgraph.push/gather:") == (
        "veilgraph.sweep/veilgraph.push")
    assert S.scope_path("jit(_add_edges_impl)/scatter:") is None


def test_each_scope_is_the_union_of_its_ops(flat):
    red = S.reduce(flat)
    lo, hi = _window(flat)
    assert set(red["scopes"]) >= set(STAGES[:3])
    for seg, secs in red["scopes"].items():
        # a sweep over the clipped ops' edges, counting covered time
        edges = []
        for path, start, dur in flat["scoped_ops"]:
            a, b = max(start, lo), min(start + dur, hi)
            if b > a and seg in path.split("/"):
                edges += [(a, 1), (b, -1)]
        covered, depth, last = 0, 0, None
        for t, d in sorted(edges):
            if depth > 0:
                covered += t - last
            depth, last = depth + d, t
        assert secs > 0
        assert secs == pytest.approx(covered * 1e-9)


def test_a_container_op_and_its_body_count_once(flat):
    sweep = [o for o in flat["scoped_ops"] if o[0].startswith(STAGES[2])]
    start = min(o[1] for o in sweep)
    end = max(o[1] + o[2] for o in sweep)
    nested = copy.deepcopy(flat)
    # a while op of the sweep, covering every op of its body
    nested["scoped_ops"].append([STAGES[2], start, end - start])
    red, base = S.reduce(nested), S.reduce(flat)
    assert red["scopes"][STAGES[2]] == pytest.approx((end - start) * 1e-9)
    assert red["scopes"][STAGES[2]] < (base["scopes"][STAGES[2]]
                                       + (end - start) * 1e-9)
    assert red["scopes"][STAGES[0]] == base["scopes"][STAGES[0]]


def test_the_stages_lie_inside_the_fused_step(flat):
    scopes = S.reduce(flat)["scopes"]
    fused = T.reduce(flat)["modules"]["jit_fused_query_step"]
    stages = sum(scopes.get(s, 0.0) for s in STAGES)
    assert 0.95 * fused <= stages <= fused
    for sub in ("veilgraph.hotset.kn", "veilgraph.hotset.kdelta"):
        assert scopes[sub] <= scopes["veilgraph.hotset"]
    for sub in ("summary.select", "summary.bin", "summary.order"):
        assert scopes["veilgraph." + sub] <= scopes["veilgraph.summary"]


def test_program_spans_and_the_gaps_they_label(flat):
    red = S.reduce(flat)
    spans = red["program_spans"]
    assert spans["veilgraph.query"][0] == 1
    syncs = {k: c for k, (c, _) in spans.items()
             if k.startswith("veilgraph.sync.")}
    assert syncs == {"veilgraph.sync.view": 4, "veilgraph.sync.fallback": 1,
                     "veilgraph.sync.ready": 1, "veilgraph.sync.stats": 1,
                     "veilgraph.sync.fetch": 1}
    lo, hi = _window(flat)
    assert all(0 < s <= (hi - lo) * 1e-9 for _, s in spans.values())
    gaps = red["idle_gaps_by_program_span"]
    assert 0 < len(gaps) <= 10
    assert gaps[0][0].startswith("veilgraph.")
    assert all(g[0].startswith("veilgraph.") or g[0] == "other"
               for g in gaps)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps),
                                          reverse=True)
    # the same gaps as the harness's own breakdown, labelled otherwise
    assert [g[1] for g in gaps] == [
        g[1] for g in T.reduce(flat)["breakdown"]["idle_gaps"]]


def test_split_reads_per_answer_numbers(flat):
    red = T.reduce(flat)
    extra = S.reduce(flat)
    red.update(scopes=extra["scopes"], program_spans=extra["program_spans"])
    red["breakdown"]["idle_gaps_by_program_span"] = extra[
        "idle_gaps_by_program_span"]
    out = split.split(red)
    assert out["answers"] == 1
    assert out["host_syncs"] == 8
    for s in STAGES[:3]:
        assert out["device_ms"][s] == pytest.approx(1e3 * extra["scopes"][s])
    assert out["fused_step_ms"] == pytest.approx(
        1e3 * red["modules"]["jit_fused_query_step"])
    assert out["spans"]["veilgraph.sync.fetch"] == pytest.approx(
        [1, 1e3 * extra["program_spans"]["veilgraph.sync.fetch"][1]])


def test_the_harness_reduction_is_unchanged_on_the_old_fixture():
    old = json.loads((DATA / "trace_small.json").read_text())
    want = T.reduce(old)
    with split.scoped_reduction():
        got = T.reduce(copy.deepcopy(old))
    assert T.reduce(old) == want  # restored on leaving
    for key, value in want.items():
        if key != "breakdown":
            assert got[key] == value
    for key, value in want["breakdown"].items():
        assert got["breakdown"][key] == value
    # the program of that trace emitted no scope and no span
    assert got["scopes"] == {} and got["program_spans"] == {}
    assert got["breakdown"]["idle_gaps_by_program_span"] == []
