"""Run one cell of the VeilGraph benchmark once.

    python3 bench/run.py --workload pr22-stream --seed 7 --seconds 20 --trace 0

Prints what it does on standard error, each number compared with its
limit as the last lines there, and as the last line of standard output
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``.
It exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for.

JAX's persistent compilation cache is kept in ``.jax_cache`` at the root
of the checkout, for every program whatever its compile time or size, so
that only the first run of a cell in a checkout compiles.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=str(ROOT / ".bench_out"),
                    help="directory for the reduced trace")
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from harness.cell import NoChip, run_cell
    from harness.manifest import load_cell

    cell = load_cell(args.workload)
    os.makedirs(args.out, exist_ok=True)
    try:
        result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), out_dir=args.out,
                          t_start=T_START)
    except NoChip as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
