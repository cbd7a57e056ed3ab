"""Compile the SpMV kernels for a TPU v5e at ``chip_smoke.py``'s shapes,
and the split approximate step of every registered algorithm.

Nothing runs: each test lowers and compiles for one chip of a described
``v5e:2x2`` topology, so the TPU compiler refuses here — without a chip —
what interpret mode cannot see (unaligned DMA slices, unsupported vector
shapes, VMEM overruns).  The kernels' shapes are those of the smoke's
graph: a 1M-vertex Barabási–Albert graph with ~20.8M edges, sized by
``repro.session``'s default capacities.  The split step
(``hot_set_step``, then ``summarized_step`` on the Pallas kernels) is
compiled at a small shape: its program's structure does not depend on
the capacities.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import available_algorithms, make_algorithm
from repro.core import backend as B
from repro.core.fused import hot_set_step, summarized_step
from repro.graph import from_edges
from repro.graph.generators import gnm_edges
from repro.kernels.spmv import kernel as K

EDGE_CAPACITY = int(20_800_000 * 1.15) + 1024   # session's default sizing
NODE_CAPACITY = int(1_000_000 * 1.1) + 16
E_PAD = B.padded_length(EDGE_CAPACITY, K.CHUNK)
NUM_TILES = -(-NODE_CAPACITY // K.TILE_N)
BATCH = 4                                         # serve_session(slots=4)
HBM_BYTES = 16 * 10**9                            # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off (a
    compile for a described chip is written but can never be read)."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


KERNELS = {
    "sum": (1, jnp.float32, None),
    "min_f32": (1, jnp.float32, "min"),
    "min_i32": (1, jnp.int32, "min"),
    "sum_batched": (BATCH, jnp.float32, None),
    "min_batched": (BATCH, jnp.float32, "min"),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    batch, dtype, op = KERNELS[name]
    contrib = _spec(one_chip, (E_PAD,) if batch == 1 else (batch, E_PAD),
                    dtype)
    stream = _spec(one_chip, (E_PAD,), jnp.int32)
    tile_start = _spec(one_chip, (NUM_TILES + 1,), jnp.int32)
    if op is None:
        fn = K.spmv_push if batch == 1 else K.spmv_push_batched
        _compile(lambda c, d, t: fn(c, d, t, num_tiles=NUM_TILES),
                 contrib, stream, tile_start)
    else:
        fn = K.spmv_reduce_push if batch == 1 else K.spmv_reduce_push_batched
        _compile(lambda c, d, r, t: fn(c, d, r, t, num_tiles=NUM_TILES,
                                       op=op),
                 contrib, stream, stream, tile_start)


@pytest.mark.parametrize("semiring,weight", [("plus_times", "inv_out"),
                                             ("min_plus", "length")])
def test_backend_push_compiles_for_v5e(one_chip, semiring, weight):
    """The whole push — contribution gather, tile-start lookup, kernel —
    through the wrapper the sweeps call, fitting one chip's HBM."""
    s = B.resolve_semiring(semiring)
    spec = lambda shape, dtype: _spec(one_chip, shape, dtype)
    layout = B.EdgeLayout(
        spec((E_PAD,), jnp.int32), spec((E_PAD,), jnp.int32),
        spec((E_PAD,), s.dtype), spec((E_PAD,), jnp.bool_),
        spec((NODE_CAPACITY + 1,), jnp.int32), None,
        None if s.add == "sum" else spec((E_PAD,), jnp.int32),
        weight_mode=weight, semiring=semiring)
    values = spec((NODE_CAPACITY,), s.dtype)
    compiled = _compile(
        lambda v, lay: B.push(v, lay, semiring=semiring, backend="pallas",
                              interpret=False),
        values, layout)
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES


@pytest.mark.parametrize("stage", ["hot_set_step", "summarized_step"])
@pytest.mark.parametrize("name", available_algorithms())
def test_split_step_compiles_for_v5e(one_chip, monkeypatch, name, stage):
    """Each stage of the approximate step the single-chip engine runs, for
    every algorithm, with the summarized sweep on the compiled kernels."""
    monkeypatch.setattr(B, "default_interpret", lambda: False)
    algo = make_algorithm(name)
    src, dst = gnm_edges(1000, 6000, seed=0)
    graph = from_edges(src, dst, 1024, 8192)
    on_chip = lambda tree: jax.tree.map(
        lambda x: _spec(one_chip, x.shape, x.dtype), tree)
    args = on_chip((graph, algo.init_state(graph), graph.out_deg,
                    graph.node_active, jnp.float32(0.2), jnp.float32(0.1)))
    select = lambda *a: hot_set_step(*a, algo=algo)
    if stage == "hot_set_step":
        jax.jit(select).lower(*args).compile()
        return
    hot, hstats, _ = on_chip(jax.eval_shape(select, *args))
    layouts = on_chip(jax.eval_shape(lambda g: tuple(
        B.build_layout(g, weight=w, reverse=rev, semiring=s)
        for w, rev, s in map(B.normalize_layout_spec, algo.layout_specs)),
        graph))
    _compile(lambda st, a, h, hs, lay: summarized_step(
        st, a, h, hs, algo=algo, hot_node_capacity=256,
        hot_edge_capacity=2048, layouts=lay, backend="pallas"),
        args[0], args[1], hot, hstats, layouts)
