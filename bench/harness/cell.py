"""One run of one cell: set-up, the measured window, the checks.

Set-up generates the configuration's graph on the device, draws the
update stream from it by the seed, starts a ``repro.session`` on the rest
(the initial exact pass included) and warms up every shape the window
uses with answers over whole update blocks.  Update blocks are then
offered open-loop, each due at a fixed time on the generator's schedule,
while one client asks for answers back to back: before each ``query()``
it registers every block whose due time has passed.  The window opens
with the schedule ``lead_blocks`` blocks in, as if it had run through one
answer before, so the window's first answer takes as many blocks as a
steady answer does, whatever the warm-up took.  After the window the program's state is freed,
and the initial pass and every answer are checked against the plain
references.
"""

from __future__ import annotations

import gc
import json
import math
import os
import shutil
import sys
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from harness import kronecker, reference
from harness.manifest import Cell, metric_reader
from harness.rbo import rbo_from_scores


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (``q`` in (0, 100])."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    return float(v[max(1, math.ceil(q / 100.0 * len(v))) - 1])


class Client:
    """How the harness drives the program: register one block, ask for
    one answer.  Tests and the control subclass it to break the timed
    path underneath."""

    def add(self, sess, src: np.ndarray, dst: np.ndarray) -> None:
        sess.add_edges(src, dst)

    def query(self, sess):
        return sess.query()


@dataclass
class Answer:
    scores: np.ndarray
    t_req: float
    t_done: float
    blocks: List[int]
    stats: Dict = field(default_factory=dict)


class CompileLog:
    """Counts programs compiled or loaded from the persistent cache."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.programs = 0
        self.cache_hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.programs += 1
            self.seconds += duration

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


_COMPILES: Optional[CompileLog] = None


def compile_log() -> CompileLog:
    """The process's one compile listener (JAX keeps listeners for the
    life of the process, so each run reads differences of it)."""
    global _COMPILES
    if _COMPILES is None:
        _COMPILES = CompileLog()
    return _COMPILES


def _span(name: str, trace: bool):
    if not trace:
        return nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             out_dir: str, t_start: float, require_tpu: bool = True,
             client: Optional[Client] = None,
             engine_overrides: Optional[Dict] = None) -> Dict:
    """Run ``cell`` once; returns the result object the command prints."""
    import jax

    import repro
    from repro.core import Action
    from repro.core.policies import always

    client = client or Client()
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < cell.chips:
        raise NoChip(f"cell {cell.name} needs {cell.chips} chips, JAX "
                     f"found {len(devices)}")
    dev = devices[0]
    compiles = compile_log()
    base = (compiles.programs, compiles.cache_hits, compiles.seconds)
    cfg, trf = cell.config, cell.traffic
    g = cfg["graph"]
    block = int(trf["block_edges"])
    n_blocks = int(trf["holdout_blocks"])
    rate = float(trf["block_rate_per_s"])
    warm_answers = int(trf["warmup_answers"])
    warm_blocks = int(trf["warmup_blocks_per_answer"])
    # the window registers no block due after it closes; never wrap round
    need = (warm_answers * warm_blocks + int(trf["lead_blocks"])
            + math.ceil(rate * seconds) + 1)
    if need > n_blocks:
        raise ValueError(f"{seconds}s at {rate} blocks/s needs {need} "
                         f"blocks; the mix holds out {n_blocks}")

    t0 = time.perf_counter()
    kg = kronecker.generate(seed, graph_seed=g["graph_seed"],
                            scale=g["scale"], edge_factor=g["edge_factor"],
                            a=g["a"], b=g["b"], c=g["c"],
                            holdout_blocks=n_blocks, block=block)
    blocks_src = kg.stream_src.reshape(n_blocks, block)
    blocks_dst = kg.stream_dst.reshape(n_blocks, block)
    log(f"graph: {kg.num_vertices} vertex ids, {kg.init_src.size} initial "
        f"edges, {n_blocks} blocks of {block} held out; generated in "
        f"{time.perf_counter() - t0:.3f}s")

    algo_params = dict(cfg.get("algorithm_params", {}))
    source = None
    if cfg["algorithm"] == "sssp":
        # the initial graph's vertex of highest out-degree (lowest id on ties)
        source = int(np.argmax(np.bincount(kg.init_src,
                                           minlength=kg.num_vertices)))
        algo_params["sources"] = (source,)
    engine = dict(cfg["engine"], **(engine_overrides or {}))
    kw = {}
    if trf["action"] == "exact":
        kw["on_query"] = always(Action.EXACT)
    t0 = time.perf_counter()
    sess = repro.session((kg.init_src, kg.init_dst), cfg["algorithm"],
                         node_capacity=kg.num_vertices, **engine,
                         **algo_params, **kw)
    log(f"session: backend {sess.engine.backend}, start "
        f"{time.perf_counter() - t0:.3f}s")

    answers: List[Answer] = []
    block_answer: List[int] = []  # answer that applied each used block
    pending: List[int] = []
    next_block = 0

    def register(upto: int) -> None:
        nonlocal next_block
        for j in range(next_block, upto):
            client.add(sess, blocks_src[j], blocks_dst[j])
            pending.append(j)
        next_block = upto

    def ask() -> Answer:
        t_req = time.perf_counter()
        res = client.query(sess)
        t_done = time.perf_counter()
        ans = Answer(np.asarray(res.scores), t_req, t_done, list(pending),
                     {k: getattr(res.stats, k) for k in (
                         "action", "num_hot", "num_nodes", "num_edges",
                         "iterations", "overflow_fallback")})
        for _ in pending:
            block_answer.append(len(answers))
        pending.clear()
        answers.append(ans)
        return ans

    prev0 = np.asarray(sess.scores)
    for _ in range(warm_answers):
        register(next_block + warm_blocks)
        ask()
    warm_n = len(answers)
    compiled_setup = compiles.programs
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f}s: {compiles.programs - base[0]} programs "
        f"compiled or loaded ({compiles.cache_hits - base[1]} "
        f"persistent-cache hits, {compiles.seconds - base[2]:.3f}s)")

    trace_dir = os.path.join(out_dir, f"trace-{cell.name}-{seed}")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    first = next_block
    lag = []
    w0 = time.perf_counter()
    t_sched = w0 - int(trf["lead_blocks"]) / rate
    due = blocks_due(t_sched, first, rate)
    with _span("window", trace):
        while True:
            now = time.perf_counter()
            if now - w0 >= seconds:
                break
            upto = first + int((now - t_sched) * rate) + 1
            if upto > n_blocks:
                raise RuntimeError("update stream exhausted inside the window")
            with _span("register", trace):
                for j in range(next_block, upto):
                    lag.append(now - max(due(j), w0))
                register(upto)
            with _span("query", trace):
                ask()
    # a window of no answer (``seconds`` 0) serves the control's readings
    w1 = answers[-1].t_done if len(answers) > warm_n else w0
    in_window = compiles.programs - compiled_setup
    if trace:
        jax.profiler.stop_trace()
    window = answers[warm_n:]
    log(f"window {w1 - w0:.3f}s: {len(window)} answers of "
        f"{[len(a.blocks) for a in window]} blocks, {next_block - first} "
        f"blocks offered since the schedule began, at "
        f"{(next_block - first) / max(w1 - t_sched, 1e-9):.3f} blocks/s "
        f"(rate {rate}), mean registration lag "
        f"{1e3 * float(np.mean(lag)) if lag else 0.0:.3f} ms, "
        f"{in_window} programs compiled or loaded in the window")

    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))

    # the engine's graph after the window, then free the program's state
    st = sess.engine.state
    mask = np.asarray(st.edge_mask())
    live_src = np.asarray(st.src)[mask]
    live_dst = np.asarray(st.dst)[mask]
    got_graph = {
        "out": np.asarray(st.out_deg).astype(np.int64),
        "in": np.asarray(st.in_deg).astype(np.int64),
        "live": int(live_src.size),
        "hash": reference.edge_hash(live_src, live_dst),
    }
    del st, mask, live_src, live_dst, sess
    gc.collect()

    t_ref = time.perf_counter()
    checks, rbo = _check(cell, kg, blocks_src, blocks_dst, block_answer,
                         answers, prev0, warm_n, got_graph, source)
    log(f"reference {time.perf_counter() - t_ref:.3f}s")

    record = {
        "answers": [{"t_req": a.t_req, "t_done": a.t_done,
                     "blocks": len(a.blocks), **a.stats} for a in window],
        "window_s": w1 - w0,
        "compiles_in_window": in_window,
        "trace": None,
        "peaks": None,
    }
    result = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(window),
        "failed": 0,
        "metrics": {},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak},
    }
    if trace:
        from harness import peaks as P
        from harness import trace as T

        flat = T.flatten(trace_dir)
        red = T.reduce(flat)
        record["trace"] = red
        record["peaks"] = P.peaks(dev.device_kind) if require_tpu else None
        with open(os.path.join(out_dir, f"reduced-{cell.name}-{seed}.json"),
                  "w") as f:
            json.dump(red, f)
        shutil.rmtree(trace_dir, ignore_errors=True)
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        for m in cell.per_layer:
            value = metric_reader(m["name"])(record)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["breakdown"] = red["breakdown"]
    elif window:
        e2e = end_to_end(window, due, w1 - w0, setup_s, rbo)
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    result["checks"] = checks
    return result


def blocks_due(t_sched: float, first: int, rate: float):
    """Due time of block ``j`` on the generator's schedule, which offers
    block ``first`` at ``t_sched`` and then ``rate`` blocks a second."""
    return lambda j: t_sched + (j - first) / rate


def end_to_end(window: List[Answer], due, window_s: float, setup_s: float,
               rbo: List[float]) -> Dict[str, float]:
    """The end-to-end metrics of one run."""
    fresh = [1e3 * (a.t_done - due(j)) for a in window for j in a.blocks]
    out = {
        "freshness_p95_ms": percentile(fresh, 95),
        "answer_p95_ms": percentile(
            [1e3 * (a.t_done - a.t_req) for a in window], 95),
        "answers_per_s": len(window) / window_s,
        "setup_s": setup_s,
    }
    if rbo:
        out["rbo100_mean"] = float(np.mean(rbo))
    return out


def _check(cell, kg, blocks_src, blocks_dst, block_answer, answers, prev0,
           warm_n, got_graph, source):
    """Every number compared, each with its limit, and the RBO values of
    the window's answers.  The initial pass and every answer, warm-up
    included, are checked, so each answer's previous one is checked too."""
    cfg, trf = cell.config, cell.traffic
    limits = cfg["limits"]
    used = len(block_answer)
    hist = reference.GraphHistory(
        kg.init_src, kg.init_dst, list(blocks_src[:used]),
        list(blocks_dst[:used]), block_answer, kg.num_vertices)
    last = len(answers) - 1
    final = hist.at(last)
    mismatch = (int(np.abs(got_graph["out"] - final.out_deg).sum())
                + int(np.abs(got_graph["in"] - final.in_deg).sum())
                + abs(got_graph["live"] - final.num_edges)
                + int(got_graph["hash"] != final.hash))
    checks = {"apply_mismatch": {"value": mismatch,
                                 "limit": limits["apply_mismatch"]}}

    params = cfg.get("algorithm_params", {})
    beta = float(params.get("beta", 0.85))
    iters = int(params.get("num_iters", 30))
    eng = cfg["engine"]
    rule = dict(r=float(eng.get("r", 0.2)), n=int(eng.get("n", 1)),
                delta=float(eng.get("delta", 0.1)),
                hop_cap=int(eng.get("delta_hop_cap", 4)))
    start = hist.at(-1)
    if cfg["algorithm"] == "pagerank":
        want = reference.exact_pagerank(start, beta=beta, num_iters=iters)
        checks["pr_start_rel_err"] = {
            "value": reference.rel_err(prev0, want, 1.0 - beta),
            "limit": limits["pr_start_rel_err"]}
        # the state before the initial pass: nothing has changed
        churn = np.zeros(kg.num_vertices)
    else:
        want = reference.exact_bfs(start, source)
        bad = int(np.sum(prev0.astype(np.float64) != want))
        init = np.where(np.arange(kg.num_vertices) == source, 0.0, np.inf)
        churn = reference.finite_churn(prev0, init)
    worst, rbo = 0.0, []
    for i, a in enumerate(answers):
        g, gp = hist.at(i), hist.at(i - 1)
        ans = a.scores
        prev = answers[i - 1].scores if i > 0 else prev0
        if cfg["algorithm"] == "pagerank" and trf["action"] == "exact":
            want = reference.exact_pagerank(g, beta=beta, num_iters=iters)
            worst = max(worst, reference.rel_err(ans, want, 1.0 - beta))
            continue
        v_s = prev if cfg["algorithm"] == "pagerank" else churn
        hot = reference.changed(ans, prev) | reference.rule_hot(
            g, gp, v_s.astype(np.float64), **rule)
        if cfg["algorithm"] == "pagerank":
            want = reference.summarized_pagerank(g, hot, prev, beta=beta,
                                                 num_iters=iters)
            worst = max(worst, reference.rel_err(ans[hot], want, 1.0 - beta))
            if i >= warm_n:
                exact = reference.exact_pagerank(g, beta=beta,
                                                 num_iters=iters)
                rbo.append(rbo_from_scores(
                    ans, exact, depth=int(trf.get("rbo_depth", 100)),
                    p=float(trf.get("rbo_p", 0.99)), active=g.active))
        else:
            want = reference.bfs_fixed_point(g, hot, ans, prev, source)
            bad += int(np.sum(ans[hot].astype(np.float64) != want))
            churn = reference.finite_churn(ans, prev)
        log(f"checked answer {i}: {int(hot.sum())} hot vertices in the "
            f"reference, {a.stats['num_hot']} in the program")
    name = _number_name(cfg, trf)
    checks[name] = {"value": bad if name == "bfs_mismatch" else worst,
                    "limit": limits[name]}
    return checks, rbo


def _number_name(cfg, trf) -> str:
    if cfg["algorithm"] == "sssp":
        return "bfs_mismatch"
    return "pr_exact_rel_err" if trf["action"] == "exact" else "pr_hot_rel_err"
