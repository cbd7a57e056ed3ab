"""Readings that set the limits of ``correct``: sound runs and the control.

    python3 bench/control.py --workload pr22-stream --seeds 1,2,3 \\
        --seconds 0 --mode sound
    python3 bench/control.py --workload pr22-stream --seeds 1,2,3 \\
        --seconds 0 --mode control

Runs the cell once per seed in this one process (set-up is long, and the
chip belongs to one process), each run checked as ``bench/run.py``
checks it, and prints one JSON line per seed with the numbers compared.
With ``--seconds 0`` the window holds no answer, and the initial pass and
the warm-up answer, at the cell's own sizes, are what is compared.  The
benchmark's own runs never run this.

The control, per configuration:

- PageRank states float32; the control is the program's own
  lower-precision path, ``EngineConfig.weight_dtype="bfloat16"`` (edge
  weights stored in bfloat16).
- BFS states exact hop counts, which bfloat16 holds exactly below 256, so
  a lower precision changes no answer.  Its control breaks the guarantee
  that an answer includes every block registered before it: the last
  block before each query is held back until after that query.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))

from harness.cell import Client  # noqa: E402


class HoldLastBlock(Client):
    """Registers each block only when the next one arrives, so every
    answer misses the last block registered before it."""

    def __init__(self):
        self.held = None

    def add(self, sess, src, dst):
        if self.held is not None:
            sess.add_edges(*self.held)
        self.held = (src, dst)


def control_args(cell):
    """``(client, engine_overrides)`` of the cell's control."""
    if cell.config["algorithm"] == "pagerank":
        return None, {"weight_dtype": "bfloat16"}
    return HoldLastBlock(), None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--mode", choices=("sound", "control"), required=True)
    ap.add_argument("--out", default=str(ROOT / ".bench_out"))
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from harness.cell import run_cell
    from harness.manifest import load_cell

    cell = load_cell(args.workload)
    os.makedirs(args.out, exist_ok=True)
    client, over = (control_args(cell) if args.mode == "control"
                    else (None, None))
    t_start = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        if client is not None:
            client = type(client)()
        r = run_cell(cell, seed=seed, seconds=args.seconds, trace=False,
                     out_dir=args.out, t_start=t_start, client=client,
                     engine_overrides=over)
        print(json.dumps({"workload": cell.name, "mode": args.mode,
                          "seed": seed, "correct": r["correct"],
                          "attempted": r["attempted"],
                          "checks": r["checks"]}), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
