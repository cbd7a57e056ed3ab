"""BENCHMARK.json against the benchmark's own rules, and the command's
refusal to run without a TPU."""

import json
import os
import re
import subprocess
import sys

import pytest

from harness.manifest import BENCH, MANIFEST, ROOT, load_cell, metric_reader

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    with open(MANIFEST) as f:
        return json.load(f)


def test_names_units_and_keys_are_legal(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    names = []
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["config"], w["traffic"]]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert all(NAME.match(n) for n in names)
    assert "setup_s" in {m["name"] for m in manifest["end_to_end"]}
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert 1 <= manifest["run_seconds"] <= 51


def test_every_cell_finds_its_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    for w in manifest["workloads"]:
        assert (ROOT / configs[w["config"]]["file"]).is_file()
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        cell = load_cell(w["name"])
        assert cell.per_layer and len(cell.end_to_end) >= 2
    for m in manifest["per_layer"]:
        assert callable(metric_reader(m["name"]))


def test_each_layer_metric_moves_a_metric_its_cells_report(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in moved.get("workloads", [cell])


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "pr22-stream",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=240)
    assert p.returncode != 0
    assert "metrics" not in p.stdout
    assert "no TPU" in p.stderr
