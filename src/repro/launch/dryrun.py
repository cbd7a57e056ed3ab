import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("REPRO_EXTRA_XLA_FLAGS", "") +
    " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (architecture × shape × mesh).

For each cell this lowers the real step function (train_step / prefill /
serve_step) with ShapeDtypeStruct inputs against the production mesh,
compiles it (SPMD partitioning — sharding mismatches, OOM-at-compile and
unsupported collectives all surface here), prints memory_analysis() and
cost_analysis(), and writes the roofline terms to
``artifacts/dryrun/<mesh>/<arch>__<shape>.json``.

Usage:
  python -m repro.launch.dryrun --arch yi_9b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh single
  python -m repro.launch.dryrun --all --mesh multi
  python -m repro.launch.dryrun --workload veilgraph --mesh single
"""

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ALIASES, ARCH_IDS, get_config
from repro.hardware import chip_peaks
from repro.launch import roofline as RL
from repro.launch.mesh import axis_sizes, make_production_mesh
from repro.launch.specs import cell_spec, input_specs, skip_reason
from repro.models.config import SHAPES
from repro.sharding.rules import axis_rules, rules_for_mesh

ART = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _ns(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str,
             verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    reason = skip_reason(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    if reason is not None:
        rec.update(status="skipped", reason=reason)
        return rec

    rules = rules_for_mesh(mesh)
    sizes = axis_sizes(mesh)
    t0 = time.time()
    try:
        with mesh:
            with axis_rules(rules):
                cell = cell_spec(cfg, arch, shape, rules, sizes)
                jitted = jax.jit(
                    cell.step_fn,
                    in_shardings=_ns(mesh, cell.in_pspecs),
                    out_shardings=_ns(mesh, cell.out_pspecs),
                    donate_argnums=cell.donate,
                )
                lowered = jitted.lower(*cell.args_sds)
                t_lower = time.time() - t0
                compiled = lowered.compile()
                t_compile = time.time() - t0 - t_lower
        chips = 1
        for v in sizes.values():
            chips *= v
        rf = RL.analyze(compiled, arch=arch, shape=shape, mesh_name=mesh_name,
                        chips=chips, cfg=cfg)
        mem = compiled.memory_analysis()
        if verbose:
            print(f"  memory_analysis: args={mem.argument_size_in_bytes/2**30:.2f}GiB "
                  f"out={mem.output_size_in_bytes/2**30:.2f}GiB "
                  f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB "
                  f"alias={mem.alias_size_in_bytes/2**30:.2f}GiB (per device)")
            ca = compiled.cost_analysis()
            print(f"  cost_analysis: flops={ca.get('flops', 0):.3e} "
                  f"bytes={ca.get('bytes accessed', 0):.3e} (per device)")
            print(f"  roofline: compute={rf.compute_s*1e3:.2f}ms "
                  f"memory={rf.memory_s*1e3:.2f}ms "
                  f"collective={rf.collective_s*1e3:.2f}ms "
                  f"dominant={rf.dominant} "
                  f"useful_ratio={rf.useful_flops_ratio:.3f} "
                  f"roofline_frac={rf.roofline_fraction:.3f}")
        rec.update(status="ok", lower_s=round(t_lower, 1),
                   compile_s=round(t_compile, 1), roofline=rf.to_dict())
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    return rec


def run_veilgraph_cell(mesh, mesh_name: str, *, nodes=2**25, edges=2**30,
                       backend: str = "auto") -> dict:
    """The paper-representative workload: one fused summarized-PageRank query
    over a pod-scale streaming graph, through the *sharded plugin path* —
    ``fused_query_step`` with ``mesh=`` builds one locally-sorted edge shard
    per device inline (a contiguous reshape of the 1-D edge sharding, then S
    independent axis-1 sorts) and runs every O(E) pass as a shard_map
    partial push + all-reduce.  Summary construction is the mesh-native
    distributed bucket sort (per-shard E_K selection + dst-sorted
    compaction, one capacity-padded all-to-all, shard-local row offsets).

    Three gates are asserted on the lowered/compiled program:

    - it traces **zero** unsorted ``push_coo`` calls (the pre-sharded cost
      model this replaced);
    - it contains **zero** all-gathers of a full edge-space buffer (the
      pre-sharded E_K compaction replicated ``e_src``/``e_dst`` that way —
      the wall-clock ceiling the sharded summary removes);
    - every pinned push shape in ``benchmarks/roofline_baseline.json``
      re-models within 10% of its committed HBM byte volume
      (:func:`repro.launch.roofline.check_push_baselines` — the
      "modeled HBM traffic must not regress" CI check; run
      ``check_push_baselines(..., update=True)`` and commit the diff after
      an intentional kernel-geometry or cost-model change).

    ``backend`` picks the per-shard propagation kernels ("auto" resolves
    per device: TPU → the Pallas MXU/VPU kernels inside each shard,
    otherwise the sorted segment-sum path)."""
    import jax.numpy as jnp
    from repro.core import backend as B
    from repro.core.algorithm import PageRankAlgorithm
    from repro.core.fused import fused_query_step
    from repro.graph.graph import GraphState
    from repro.sharding.rules import guarded_pspec

    rules = rules_for_mesh(mesh)
    sizes = axis_sizes(mesh)
    rec = {"arch": "veilgraph-pagerank", "shape": f"N=2^25,E=2^30",
           "mesh": mesh_name}
    e_spec = guarded_pspec((edges,), ("edges",), rules, sizes)
    n_spec = P()
    state_sds = GraphState(
        src=jax.ShapeDtypeStruct((edges,), jnp.int32),
        dst=jax.ShapeDtypeStruct((edges,), jnp.int32),
        edge_alive=jax.ShapeDtypeStruct((edges,), jnp.bool_),
        num_edges=jax.ShapeDtypeStruct((), jnp.int32),
        out_deg=jax.ShapeDtypeStruct((nodes,), jnp.int32),
        in_deg=jax.ShapeDtypeStruct((nodes,), jnp.int32),
        node_active=jax.ShapeDtypeStruct((nodes,), jnp.bool_),
    )
    state_ps = GraphState(
        src=e_spec, dst=e_spec, edge_alive=e_spec, num_edges=P(),
        out_deg=n_spec, in_deg=n_spec, node_active=n_spec)
    algo_sds = {"ranks": jax.ShapeDtypeStruct((nodes,), jnp.float32)}
    deg_sds = jax.ShapeDtypeStruct((nodes,), jnp.int32)
    act_sds = jax.ShapeDtypeStruct((nodes,), jnp.bool_)
    scal = jax.ShapeDtypeStruct((), jnp.float32)
    algo = PageRankAlgorithm(num_iters=30, tol=1e-6)
    backend_r = B.resolve_backend(backend)

    t0 = time.time()
    try:
        with mesh:
            with axis_rules(rules):
                fn = lambda st, a, dp, ap, rr, dd: fused_query_step(
                    st, a, dp, ap, rr, dd, algo=algo,
                    hot_node_capacity=2**21, hot_edge_capacity=2**26, n=1,
                    backend=backend_r, mesh=mesh)
                jitted = jax.jit(
                    fn,
                    in_shardings=(_ns(mesh, state_ps), None, None, None, None, None),
                )
                B.reset_trace_counts()
                lowered = jitted.lower(state_sds, algo_sds, deg_sds, act_sds,
                                       scal, scal)
                push_coo_traces = B.trace_count("push_coo")
                if push_coo_traces:
                    raise AssertionError(
                        f"sharded plugin path traced {push_coo_traces} "
                        f"unsorted push_coo call(s); the lowered hot loop "
                        f"must be cached-layout pushes only")
                t_lower = time.time() - t0
                compiled = lowered.compile()
                t_compile = time.time() - t0 - t_lower
        chips = 1
        for v in sizes.values():
            chips *= v
        from repro.launch.hlo_cost import analyze_hlo
        hc = analyze_hlo(compiled.as_text())
        cost = {"flops": hc.flops, "bytes accessed": hc.bytes}
        coll = dict(hc.coll)
        counts = dict(hc.coll_counts)
        # the sharded summary construction must never materialize a
        # replicated full-edge-space buffer: with 4-byte endpoints, an
        # all-gather at least edge-buffer-sized means some stage (the
        # pre-sharded E_K compaction gathered e_src/e_dst this way, ~9 GiB
        # per device at this shape) replicated the stream.  The bucket
        # exchange is an all-to-all of capacity-padded hot blocks — orders
        # of magnitude smaller.  The gate is the shared analysis pass
        # (repro.analysis.hlo_audit) so the dry-run and tools/analyze.py
        # can never disagree about the budget.
        from repro.analysis.hlo_audit import audit_cost, budgets_for_graph
        audit = audit_cost(hc, budgets_for_graph(edges),
                           program="veilgraph-cell[sharded]")
        if audit:
            raise AssertionError(
                "HLO collective audit failed for the sharded cell:\n"
                + "\n".join(f"  {f}" for f in audit))
        ag_max = hc.coll_max.get("all-gather", 0.0)
        # per-kernel roofline gate: every pinned push shape must re-model
        # within 10% of its committed HBM byte volume (AssertionError here
        # fails the dryrun cell, and CI with it)
        baseline_path = (Path(__file__).resolve().parents[3] /
                         "benchmarks" / "roofline_baseline.json")
        push_checks = RL.check_push_baselines(baseline_path)
        print(f"  push roofline: {len(push_checks)} pinned shapes within "
              f"10% of baseline HBM bytes")
        mem = compiled.memory_analysis()
        # "model flops" for the graph query: the paper's useful work = selection
        # + summary + 30 iterations over the hot subgraph; approximate with
        # 2 flops/edge-visit × (O(E) selection passes + 30·hot_edge_capacity)
        useful = 2.0 * (6 * edges + 30 * 2**26)
        peaks = chip_peaks()  # the v5e the pod-scale cell models
        rec.update(status="ok", lower_s=round(t_lower, 1),
                   compile_s=round(t_compile, 1),
                   push_roofline=push_checks,
                   backend=backend_r, push_coo_traces=push_coo_traces,
                   replicated_edge_buffer_gathers=0,
                   max_all_gather_bytes=ag_max,
                   roofline={
                       "arch": "veilgraph-pagerank", "shape": rec["shape"],
                       "mesh": mesh_name, "chips": chips,
                       "flops_per_device": float(cost.get("flops", 0.0)),
                       "bytes_per_device": float(cost.get("bytes accessed", 0.0)),
                       "collective_bytes_per_device": float(sum(coll.values())),
                       "collective_breakdown": {**coll, "counts": counts},
                       "model_flops": useful,
                       "compute_s": float(cost.get("flops", 0.0))
                       / peaks.flops_bf16,
                       "memory_s": float(cost.get("bytes accessed", 0.0))
                       / peaks.hbm_bytes_per_s,
                       "collective_s": float(sum(coll.values()))
                       / peaks.ici_bytes_per_s_link,
                       "memory_stats": {
                           "argument_bytes": mem.argument_size_in_bytes,
                           "output_bytes": mem.output_size_in_bytes,
                           "temp_bytes": mem.temp_size_in_bytes,
                       },
                   })
        print(f"  veilgraph memory: args={mem.argument_size_in_bytes/2**30:.2f}GiB "
              f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB; "
              f"flops={cost.get('flops', 0):.3e} bytes={cost.get('bytes accessed', 0):.3e}")
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", type=str, default="single",
                    choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--workload", type=str, default="lm",
                    choices=["lm", "veilgraph"])
    ap.add_argument("--backend", type=str, default="auto",
                    choices=["auto", "pallas", "segment_sum"],
                    help="per-shard propagation kernels for the veilgraph "
                    "workload (auto: TPU → pallas)")
    args = ap.parse_args(argv)

    mesh = make_production_mesh(multi_pod=args.mesh == "multi")
    out_dir = ART / args.mesh
    out_dir.mkdir(parents=True, exist_ok=True)
    print(f"mesh {args.mesh}: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"({mesh.devices.size} devices)")

    if args.workload == "veilgraph":
        rec = run_veilgraph_cell(mesh, args.mesh, backend=args.backend)
        (out_dir / "veilgraph__pagerank.json").write_text(json.dumps(rec, indent=1))
        print(json.dumps({k: rec[k] for k in ("arch", "status")}, indent=1))
        return 0 if rec["status"] == "ok" else 1

    if args.all:
        cells = [(a, s) for a in ARCH_IDS for s in SHAPES]
    else:
        arch = ALIASES.get(args.arch, args.arch)
        shapes = [args.shape] if args.shape else list(SHAPES)
        cells = [(arch, s) for s in shapes]

    failures = 0
    for arch, shape_name in cells:
        path = out_dir / f"{arch}__{shape_name}.json"
        print(f"[{arch} × {shape_name} × {args.mesh}]", flush=True)
        rec = run_cell(arch, shape_name, mesh, args.mesh)
        path.write_text(json.dumps(rec, indent=1))
        if rec["status"] == "error":
            failures += 1
            print(f"  ERROR: {rec['error']}", flush=True)
        elif rec["status"] == "skipped":
            print(f"  skipped: {rec['reason']}", flush=True)
        else:
            print(f"  ok (lower {rec['lower_s']}s compile {rec['compile_s']}s)",
                  flush=True)
    print(f"done: {len(cells)} cells, {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
