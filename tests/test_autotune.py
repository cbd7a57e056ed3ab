"""Autotuned geometry + quantized weights acceptance.

Four contracts from the kernel-perf redesign:

- **parity grid**: both push kernel variants (one-hot matmul sum, rank-
  stream masked reduce) produce backend-parity results at *every*
  candidate ``(tile_n, chunk)`` geometry, for all four registered
  semirings — tuning can change speed, never results;
- **tuner semantics**: ``cached`` mode is deterministic and never writes
  the cache (it holds measured/JSON-loaded tunings only), ``full`` mode
  measures once per key and is skipped by cache hits, and the JSON cache
  round-trips;
- **bf16 edge weights**: storage-only narrowing — f32 accumulation keeps
  plus_times within quantization tolerance and min_plus bitwise when the
  lengths are bf16-representable; non-f32 algebras reject the option;
- **roofline gate**: the CI byte-volume check shares ``modeled_push_cost``
  with the tuner (they can never disagree), the committed baseline file
  verifies clean, and a fabricated regression trips the AssertionError.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend as B
from repro.core.semiring import resolve_semiring
from repro.graph import from_edges
from repro.graph.generators import gnm_edges
from repro.kernels.spmv import autotune as AT

SEMIRING_WEIGHT = [("plus_times", "inv_out"), ("min_plus", "length"),
                   ("min_min", "unit"), ("max_times", "unit")]
GRID = [(t, c) for t in (128, 256, 512) for c in (128, 512, 1024)]


def _graph(n=300, m=1500, seed=0):
    src, dst = gnm_edges(n, m, seed=seed)
    return from_edges(src, dst, n, m + 64)


def _values(s, n, seed):
    rng = np.random.default_rng(seed)
    if np.issubdtype(s.np_dtype, np.floating):
        v = rng.random(n).astype(s.np_dtype)
        if s.name == "min_plus":
            v = np.where(rng.random(n) < 0.1, v, np.inf).astype(s.np_dtype)
        return jnp.asarray(v)
    return jnp.asarray(rng.integers(0, n, n).astype(s.np_dtype))


# ------------------------------------------------ geometry parity grid
@pytest.mark.parametrize("tile_n,chunk", GRID)
@pytest.mark.parametrize("name,weight", SEMIRING_WEIGHT)
def test_geometry_parity_grid(name, weight, tile_n, chunk):
    s = resolve_semiring(name)
    g = _graph(seed=1)
    layout = B.build_layout(g, weight=weight, semiring=name,
                            tile_n=tile_n, chunk=chunk)
    assert layout.tile_n == tile_n and layout.tile_chunk == chunk
    v = _values(s, 300, seed=2)
    ref = B.push(v, layout, semiring=name, backend="segment_sum")
    out = B.push(v, layout, semiring=name, backend="pallas", interpret=True)
    if s.add == "sum":
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)
    else:  # min/max reduces are reassociation-exact
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("name,weight", [("plus_times", "inv_out"),
                                         ("min_plus", "length")])
def test_geometry_parity_batched(name, weight):
    s = resolve_semiring(name)
    g = _graph(seed=3)
    layout = B.build_layout(g, weight=weight, semiring=name,
                            tile_n=128, chunk=256)
    rng = np.random.default_rng(4)
    v = jnp.asarray(rng.random((3, 300)).astype(np.float32))
    ref = B.push(v, layout, semiring=name, backend="segment_sum")
    out = B.push(v, layout, semiring=name, backend="pallas", interpret=True)
    if s.add == "sum":
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)
    else:
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ------------------------------------------------------ tuner semantics
def _key(reduce="sum", **over):
    kw = dict(e_pad=8192, n=1024, b=1, dtype="float32", reduce=reduce,
              platform=jax.default_backend())
    kw.update(over)
    return AT.TuneKey(**kw)


def test_tune_off_returns_defaults_without_cache_interaction():
    AT.clear_cache()
    assert AT.tune(_key(), "off") == (AT.TILE_N, AT.CHUNK)
    assert AT.cache_entries() == {} and AT.run_count() == 0


def test_tune_cached_is_deterministic_and_does_not_write_cache():
    AT.clear_cache()
    first = AT.tune(_key(), "cached")
    assert first == AT.tune(_key(), "cached")
    assert first == AT.candidates(_key())[0]  # the analytic argmin
    # cached mode must not populate the cache: a later "full" run still
    # gets to time candidates
    assert AT.cache_entries() == {}
    measured = AT.tune(_key(), "full", measure_top=2)
    assert AT.run_count() == 1
    assert measured in AT.candidates(_key())[:2]
    AT.clear_cache()


def test_tune_full_cache_hit_skips_timing():
    AT.clear_cache()
    best = AT.tune(_key(reduce="min"), "full", measure_top=2)
    assert AT.run_count() == 1
    again = AT.tune(_key(reduce="min"), "full", measure_top=2)
    assert again == best
    assert AT.run_count() == 1          # hit — no second measurement
    assert AT.cache_hits() == 1
    AT.clear_cache()


def test_cache_save_load_round_trip(tmp_path):
    AT.clear_cache()
    best = AT.tune(_key(), "full", measure_top=2)
    path = tmp_path / "cache.json"
    AT.save_cache(path)
    AT.clear_cache()
    assert AT.load_cache(path) == 1
    # the loaded entry answers cached mode with zero measurements
    assert AT.tune(_key(), "cached") == best
    assert AT.run_count() == 0
    assert AT.load_cache(tmp_path / "missing.json") == 0
    AT.clear_cache()


def test_candidates_are_vmem_pruned_and_model_ranked():
    key = _key(b=64, reduce="min")      # wide batch inflates the working set
    cands = AT.candidates(key)
    assert 0 < len(cands) <= len(AT.TILE_N_CANDIDATES) * len(
        AT.CHUNK_CANDIDATES)
    for tile_n, chunk in cands:
        cost = AT.modeled_push_cost(e_pad=key.e_pad, n=key.n, b=key.b,
                                    reduce=key.reduce, tile_n=tile_n,
                                    chunk=chunk)
        assert cost.vmem_bytes <= AT.VMEM_LIMIT_BYTES
    bounds = [AT.modeled_push_cost(e_pad=key.e_pad, n=key.n, b=key.b,
                                   reduce=key.reduce, tile_n=t,
                                   chunk=c).bound_time_s
              for t, c in cands]
    assert bounds == sorted(bounds)


def test_tune_key_string_round_trip():
    key = _key(b=8, reduce="max")
    assert AT.TuneKey.from_str(key.as_str()) == key


# ------------------------------------------------- bf16 edge weights
def test_bf16_weights_plus_times_within_quantization_tolerance():
    g = _graph(seed=5)
    v = jnp.asarray(np.random.default_rng(6).random(300).astype(np.float32))
    full = B.build_layout(g, weight="inv_out")
    comp = B.build_layout(g, weight="inv_out", weight_dtype="bfloat16")
    assert comp.weight.dtype == jnp.bfloat16
    ref = B.push(v, full, backend="segment_sum")
    out = B.push(v, comp, backend="segment_sum")
    # bf16 has ~3 decimal digits; accumulation stays f32
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-2, atol=1e-3)
    pal = B.push(v, comp, backend="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(out),
                               rtol=1e-5, atol=1e-6)


def test_bf16_weights_min_plus_bitwise_for_representable_lengths():
    rng = np.random.default_rng(7)
    src, dst = gnm_edges(300, 1500, seed=8)
    lengths = rng.choice([0.25, 0.5, 1.0, 2.0], len(src)).astype(np.float32)
    g = from_edges(src, dst, 300, len(src) + 64, weights=lengths)
    v = _values(resolve_semiring("min_plus"), 300, seed=9)
    full = B.build_layout(g, weight="length", semiring="min_plus")
    comp = B.build_layout(g, weight="length", semiring="min_plus",
                          weight_dtype="bfloat16")
    ref = B.push(v, full, semiring="min_plus", backend="segment_sum")
    out = B.push(v, comp, semiring="min_plus", backend="segment_sum")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_bf16_weights_rejected_for_non_f32_semirings():
    g = _graph(seed=10)
    with pytest.raises(ValueError, match="weight_dtype"):
        B.build_layout(g, weight="unit", semiring="min_min",
                       weight_dtype="bfloat16")


# -------------------------------------------- engine + session threading
def test_session_threads_autotune_and_weight_dtype():
    from repro import api

    src, dst = gnm_edges(256, 1200, seed=11)
    AT.clear_cache()
    sess = api.session((src, dst), "pagerank", node_capacity=256,
                       edge_capacity=1536, hot_node_capacity=256,
                       hot_edge_capacity=1536, autotune="cached",
                       weight_dtype="bfloat16")
    eng = sess.engine
    assert eng.config.autotune == "cached"
    (layout,) = eng.edge_layouts()
    # cached mode resolved a concrete geometry and stamped it on the layout
    assert (layout.tile_n, layout.tile_chunk) == AT.tune_for_push(
        edge_capacity=1536, num_segments=256, mode="cached")
    assert layout.weight.dtype == jnp.bfloat16
    assert eng.autotune_runs == 0       # cached mode never measures
    AT.clear_cache()


def test_engine_weight_dtype_skipped_for_integer_algebra():
    from repro.core.engine import EngineConfig, VeilGraphEngine

    eng = VeilGraphEngine(EngineConfig(
        node_capacity=128, edge_capacity=256, hot_node_capacity=128,
        hot_edge_capacity=256, weight_dtype="bfloat16"))
    # min_min is int32: compression is silently skipped, not an error
    assert eng._weight_dtype_for("min_min") is None
    assert eng._weight_dtype_for("plus_times") == "bfloat16"


# ------------------------------------------------------- roofline gate
def test_roofline_gate_shares_the_tuner_cost_model():
    from repro.launch import roofline as RL

    rec = RL.push_roofline_check(edge_capacity=10_000, num_segments=2_048,
                                 reduce="min", tile_n=128, chunk=256)
    e_pad = (10_000 // AT.CHUNK + 2) * AT.CHUNK
    cost = AT.modeled_push_cost(e_pad=e_pad, n=2_048, reduce="min",
                                tile_n=128, chunk=256)
    assert rec["hbm_bytes"] == cost.hbm_bytes
    assert rec["flops"] == cost.flops
    assert rec["dominant"] in ("memory", "compute")


def test_committed_roofline_baseline_verifies_clean():
    from pathlib import Path
    from repro.launch import roofline as RL

    path = (Path(__file__).resolve().parents[1] / "benchmarks" /
            "roofline_baseline.json")
    checks = RL.check_push_baselines(path)
    assert len(checks) >= 5


def test_roofline_gate_trips_on_regression():
    from repro.launch import roofline as RL

    rec = RL.push_roofline_check(edge_capacity=10_000, num_segments=2_048)
    shrunk = dict(rec, hbm_bytes=rec["hbm_bytes"] / 1.25)
    with pytest.raises(AssertionError, match="regressed"):
        RL.push_roofline_check(edge_capacity=10_000, num_segments=2_048,
                               baseline=shrunk)
