"""The exact-answer cell ``pr22-exact``: its work count, its two readers,
its entry in BENCHMARK.json, and at a tiny size on the CPU a whole run
that is correct, and is not when the exact sweeps stop short."""

import copy
import dataclasses
import json
import time

import pytest

from harness.cell import Client, run_cell
from harness.exact_work import window_bytes
from harness.manifest import BENCH, load_cell, metric_reader
from harness.peaks import peaks

DATA = BENCH / "tests" / "data"
READERS = ("exact_ms_per_answer", "exact_sweep_roofline")


def answer(action="compute-exact", iterations=30, num_edges=1000,
           num_nodes=100):
    return {"action": action, "num_hot": 0, "num_nodes": num_nodes,
            "num_edges": num_edges, "iterations": iterations,
            "overflow_fallback": False}


def test_work_counts_the_exact_answers_and_nothing_else():
    answers = [answer(iterations=30, num_edges=100, num_nodes=10),
               answer("compute-approximate", iterations=30),
               answer(iterations=29, num_edges=50, num_nodes=5),
               answer("repeat-last-answer", iterations=0)]
    # 30 * (8*100 + 12*10) + 29 * (8*50 + 12*5)
    assert window_bytes(answers) == 27_600 + 13_340
    assert window_bytes(answers[1:2]) == 0


def test_readers_on_a_synthetic_record():
    trace = {"modules": {"jit_pagerank": 2.0, "jit_build_layout": 0.5,
                         "jit__add_edges_impl": 0.01},
             "window_s": 3.0, "busy_s": 2.6}
    answers = [answer(num_edges=1_000_000, num_nodes=100_000)] * 2
    record = {"answers": answers, "trace": trace, "compiles_in_window": 0,
              "peaks": peaks("TPU v5 lite"), "window_s": 3.0}
    assert metric_reader("exact_ms_per_answer")(record) == pytest.approx(
        1e3)
    # 2 answers * 30 * (8e6 + 1.2e6) B over 2 s at 819 GB/s
    assert metric_reader("exact_sweep_roofline")(record) == pytest.approx(
        100 * 552e6 / (2.0 * 819e9))
    untraced = dict(record, trace=None, peaks=None)
    no_sweep = dict(record, trace=dict(trace, modules={"jit_build_layout":
                                                       0.5}))
    for name in READERS:
        assert metric_reader(name)(untraced) is None
        assert metric_reader(name)(no_sweep) is None
    approximate = dict(record, answers=[answer("compute-approximate")])
    assert metric_reader("exact_sweep_roofline")(approximate) is None


def test_the_cell_loads_with_its_metrics_and_the_stream_configuration():
    cell = load_cell("pr22-exact")
    assert cell.chips == 1 and cell.traffic["action"] == "exact"
    assert {m["name"] for m in cell.end_to_end} == {
        "freshness_p95_ms", "answer_p95_ms", "answers_per_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == set(READERS)
    stream = load_cell("pr22-stream")
    for key in ("graph", "algorithm", "algorithm_params", "engine"):
        assert cell.config[key] == stream.config[key], key
    trf = {k: v for k, v in stream.traffic.items()
           if not k.startswith("rbo_")}
    assert cell.traffic == dict(trf, action="exact")
    assert cell.config["limits"]["pr_exact_rel_err"] > 0


class HalfTheIterations(Client):
    """Each answer's exact sweep stops at 15 of its 30 iterations.  (One
    iteration dropped is no control at this size: the 30th moves these
    ranks by about 1e-06 relative, inside the limit.  The cell's graph
    holds components of three vertices, u - c - w, whose ranks swing
    between two values and shrink the swing by only beta an iteration:
    there the 30th iteration moves ranks by about 5e-03.)"""

    def query(self, sess):
        algo = sess.engine.algorithm
        sess.engine.algorithm = dataclasses.replace(algo, num_iters=15)
        return sess.query()


def tiny_exact():
    cell = copy.deepcopy(load_cell("pr22-exact"))
    cell.config = json.loads((DATA / "tiny-pagerank.json").read_text())
    cell.traffic = json.loads((DATA / "tiny-inserts-exact.json").read_text())
    return cell


@pytest.mark.parametrize("client", [None, HalfTheIterations],
                         ids=["sound", "half-the-iterations"])
def test_tiny_exact_cell_is_correct_unless_its_sweeps_stop_short(
        client, tmp_path):
    r = run_cell(tiny_exact(), seed=5, seconds=0.4, trace=True,
                 out_dir=str(tmp_path), t_start=time.perf_counter(),
                 require_tpu=False, client=client and client())
    err = r["checks"]["pr_exact_rel_err"]
    assert r["checks"]["pr_start_rel_err"]["value"] < 1e-4
    if client is None:
        assert r["correct"], r["checks"]
    else:
        assert not r["correct"] and err["value"] > 10 * err["limit"], err
    # the CPU trace has no TPU plane: the readers find nothing, and raise
    # nothing
    assert r["metrics"] == {}
