"""A whole run of each cell at a tiny size on the CPU: sound, it comes out
``correct``; with the timed path broken underneath, or with its control
in the program's place, it does not."""

import copy
import json
import time
from types import SimpleNamespace

import numpy as np
import pytest

from control import control_args
from harness.cell import Client, run_cell
from harness.manifest import BENCH, load_cell

DATA = BENCH / "tests" / "data"
#: test-sized cells: (workload whose metrics it reports, config, traffic);
#: ``pr22-exact`` keeps the harness's exact-answer path under test
TINY = {"pr22-stream": ("pr22-stream", "tiny-pagerank", "tiny-inserts"),
        "bfs22-stream": ("bfs22-stream", "tiny-bfs", "tiny-inserts"),
        "pr22-exact": ("pr22-stream", "tiny-pagerank", "tiny-inserts-exact")}


def tiny_cell(name):
    base, conf, trf = TINY[name]
    cell = copy.deepcopy(load_cell(base))
    cell.name = name
    cell.config = json.loads((DATA / f"{conf}.json").read_text())
    cell.traffic = json.loads((DATA / f"{trf}.json").read_text())
    if cell.traffic["action"] == "exact":
        cell.end_to_end = [m for m in cell.end_to_end
                           if m["name"] != "rbo100_mean"]
    return cell


def run(name, tmp_path, client=None, overrides=None, seed=3):
    return run_cell(tiny_cell(name), seed=seed, seconds=0.4, trace=False,
                    out_dir=str(tmp_path), t_start=time.perf_counter(),
                    require_tpu=False, client=client,
                    engine_overrides=overrides)


class Unchanged(Client):
    """The step returns its state unchanged: every answer repeats the
    first one."""

    first = None

    def query(self, sess):
        res = sess.query()
        if self.first is None:
            self.first = res.scores
        return SimpleNamespace(scores=self.first.copy(), stats=res.stats)


class HalfTheBlocks(Client):
    """Half of the update blocks are left out."""

    n = 0

    def add(self, sess, src, dst):
        self.n += 1
        if self.n % 2:
            sess.add_edges(src, dst)


class Altered(Client):
    """One value of each answer, at a vertex drawn anew each time, is
    altered where it is produced."""

    rng = np.random.default_rng(0)

    def query(self, sess):
        res = sess.query()
        s = res.scores.copy()
        v = self.rng.choice(np.nonzero(np.isfinite(s) & (s > 0))[0])
        s[v] += 1
        return SimpleNamespace(scores=s, stats=res.stats)


class NoDeltaExpansion(Client):
    """The hot set's Δ expansion (paper Eqs. 4-5) is left out: the
    session runs with a Δ so large that no vertex joins by it."""

    overrides = {"delta": 1e9}


CELLS = sorted(TINY)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, tmp_path):
    r = run(name, tmp_path)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert {m["name"] for m in tiny_cell(name).end_to_end} == set(r["metrics"])


@pytest.mark.parametrize("fault", [Unchanged, HalfTheBlocks, Altered])
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_path_is_not_correct(name, fault, tmp_path):
    r = run(name, tmp_path, client=fault())
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("name", ["pr22-stream"])
def test_a_hot_set_without_its_delta_expansion_is_not_correct(name, tmp_path):
    r = run(name, tmp_path, overrides=NoDeltaExpansion.overrides)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_the_control_is_not_correct(name, tmp_path):
    client, overrides = control_args(tiny_cell(name))
    r = run(name, tmp_path, client=client, overrides=overrides)
    assert not r["correct"], r["checks"]
