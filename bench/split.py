"""Run one cell of the benchmark traced, and split its answers by stage.

    python3 bench/split.py --workload pr22-stream --seed 7 --seconds 40

Runs ``bench/run.py`` with ``--trace 1`` and every argument given here,
with :mod:`harness.scopes` read from the same trace: the reduced trace
in ``--out`` then also holds ``scopes``, ``program_spans`` and
``breakdown.idle_gaps_by_program_span``.  After ``run.py``'s own lines
it prints one more JSON line, per answer in the window: the device ms
of each ``veilgraph.*`` scope and of the fused step's module, the count
and host ms of each ``veilgraph.*`` span, and the host syncs.  The
harness's ``trace.flatten`` and ``trace.reduce`` are wrapped for the
run, not changed.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

import run
from harness import scopes as S
from harness import trace as T

FUSED = "jit_fused_query_step"


@contextlib.contextmanager
def scoped_reduction():
    """Within it, the harness's ``trace.flatten`` and ``trace.reduce``
    also read :mod:`harness.scopes`; every key they had stays as it was."""
    flatten, reduce = T.flatten, T.reduce

    def flatten_both(trace_dir, device_index=0):
        flat = flatten(trace_dir, device_index)
        flat.update(S.flatten(trace_dir, device_index))
        return flat

    def reduce_both(flat, top=10):
        red = reduce(flat, top)
        extra = S.reduce(flat, top)
        red["scopes"] = extra["scopes"]
        red["program_spans"] = extra["program_spans"]
        red["breakdown"]["idle_gaps_by_program_span"] = extra[
            "idle_gaps_by_program_span"]
        return red

    T.flatten, T.reduce = flatten_both, reduce_both
    try:
        yield
    finally:
        T.flatten, T.reduce = flatten, reduce


def split(red) -> dict:
    """Per-answer numbers of one reduced trace (answers: the window's
    ``veilgraph.query`` spans)."""
    spans = red["program_spans"]
    n = spans.get("veilgraph.query", [0, 0.0])[0]
    if not n:
        return {"answers": 0}
    return {
        "answers": n,
        "device_ms": {k: 1e3 * v / n
                      for k, v in sorted(red["scopes"].items())},
        "fused_step_ms": 1e3 * red["modules"].get(FUSED, 0.0) / n,
        "spans": {k: [c / n, 1e3 * s / n]
                  for k, (c, s) in sorted(spans.items())},
        "host_syncs": sum(c for k, (c, _) in spans.items()
                          if k.startswith("veilgraph.sync.")) / n,
        "idle_gaps_by_program_span":
            red["breakdown"]["idle_gaps_by_program_span"],
    }


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    with scoped_reduction():
        rc = run.main(argv + ["--trace", "1"])
    if rc:
        return rc
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--out", default=str(run.ROOT / ".bench_out"))
    args, _ = ap.parse_known_args(argv)
    path = Path(args.out) / f"reduced-{args.workload}-{args.seed}.json"
    print(json.dumps({"split": split(json.loads(path.read_text()))}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
