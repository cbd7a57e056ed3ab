"""The trace reduction on a small recorded trace (the window of a traced
``pr22-stream`` run on one v5e, flattened, op names cut to 48
characters, committed as a fixture), and the per-layer metric readers
on it."""

import json
from pathlib import Path

import numpy as np
import pytest

from harness import trace as T
from harness.manifest import metric_reader
from harness.peaks import peaks

FIXTURE = Path(__file__).parent / "data" / "trace_small.json"


@pytest.fixture(scope="module")
def flat():
    return json.loads(FIXTURE.read_text())


def _window(flat):
    (w,) = [s for s in flat["spans"] if s[0] == "window"]
    return w[1], w[1] + w[2]


def test_busy_is_the_union_of_clipped_op_intervals(flat):
    red = T.reduce(flat)
    lo, hi = _window(flat)
    # brute force on a 10-microsecond grid
    grid = np.zeros(-(-(hi - lo) // 10_000), bool)
    for _, start, dur in flat["ops"]:
        a, b = max(start, lo) - lo, min(start + dur, hi) - lo
        if b > a:
            grid[a // 10_000: -(-b // 10_000)] = True
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["busy_s"] == pytest.approx(grid.sum() * 1e-5, rel=0.01)


def test_module_times_and_breakdown(flat):
    red = T.reduce(flat)
    lo, hi = _window(flat)
    for name, secs in red["modules"].items():
        want = sum(min(s + d, hi) - max(s, lo)
                   for n, s, d in flat["modules"]
                   if T.module_name(n) == name and min(s + d, hi) > max(s, lo))
        assert secs == pytest.approx(want * 1e-9)
    b = red["breakdown"]
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(g[0] in ("register", "query", "other") for g in b["idle_gaps"])
    gaps = [g[1] for g in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert red["busy_s"] + sum(gaps) <= red["window_s"] * (1 + 1e-9)


def test_readers_on_the_recorded_trace(flat):
    red = T.reduce(flat)
    n = sum(1 for s in flat["spans"] if s[0] == "query")
    answers = [{"action": "compute-approximate", "num_hot": 10,
                "num_nodes": 100, "num_edges": 1000, "iterations": 30}] * n
    record = {"answers": answers, "trace": red, "compiles_in_window": 0,
              "peaks": peaks("TPU v5 lite"), "window_s": red["window_s"]}
    idle = metric_reader("device_idle_share")(record)
    assert idle == pytest.approx(100 * (1 - red["busy_s"] / red["window_s"]))
    fused = metric_reader("fused_step_ms_per_answer")(record)
    assert fused == pytest.approx(
        1e3 * red["modules"]["jit_fused_query_step"] / n)
    assert metric_reader("hot_vertex_ratio")(record) == pytest.approx(0.1)
    assert metric_reader("compiles_in_window")(record) == 0
    layout = metric_reader("layout_ms_per_answer")(record)
    assert layout == pytest.approx(
        1e3 * red["modules"]["jit_build_layout"] / n)
    assert metric_reader("apply_ms_per_answer")(record) > 0
