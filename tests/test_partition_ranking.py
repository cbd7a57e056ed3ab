"""Graph partitioning helpers + ranking utilities."""

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.graph import from_edges
from repro.graph.partition import (RULES_MULTI_POD, RULES_SINGLE_POD,
                                   edge_sharding, graph_shardings,
                                   guarded_pspec, host_edge_slice,
                                   rules_for_mesh)
from repro.metrics.ranking import l1_delta, linf_delta, top_k_ids


def test_edge_sharding_spec():
    mesh = jax.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    sh = edge_sharding(mesh, 1024)
    assert sh.spec == P(("data", "model"))


def test_graph_shardings_structure():
    mesh = jax.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    g = from_edges(np.array([0], np.int32), np.array([1], np.int32), 4, 8)
    sh = graph_shardings(mesh, g)
    assert sh.src.spec == P(("data", "model"))
    assert sh.out_deg.spec == P()


def test_guarded_pspec_shards_a_divisible_dimension():
    sizes = {"pod": 2, "data": 16, "model": 16}
    assert guarded_pspec((2**20,), ("edges",), RULES_SINGLE_POD,
                         sizes) == P(("data", "model"))
    assert guarded_pspec((2**20,), ("edges",), RULES_MULTI_POD,
                         sizes) == P(("pod", "data", "model"))
    # an unnamed dimension stays replicated beside a sharded one
    assert guarded_pspec((3, 2**20), (None, "edges"), RULES_SINGLE_POD,
                         sizes) == P(None, ("data", "model"))


def test_guarded_pspec_replicates_a_non_divisible_dimension():
    sizes = {"data": 16, "model": 16}
    # 1000 is no multiple of 16: neither axis applies
    assert guarded_pspec((1000,), ("edges",), RULES_SINGLE_POD,
                         sizes) == P()
    # 16 * 3 divides by data (16) but not by data * model (256)
    assert guarded_pspec((48,), ("edges",), RULES_SINGLE_POD,
                         sizes) == P("data")


def test_no_mesh_means_no_rules():
    assert rules_for_mesh(None) == {}
    assert guarded_pspec((2**20,), ("edges",), rules_for_mesh(None),
                         {"data": 16, "model": 16}) == P()
    mesh = jax.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    assert rules_for_mesh(mesh) is RULES_SINGLE_POD


def test_host_edge_slice_covers_all():
    ranges = [host_edge_slice(103, p, 4) for p in range(4)]
    covered = []
    for lo, hi in ranges:
        covered.extend(range(lo, hi))
    assert covered == list(range(103))


def test_top_k_ids_deterministic_ties():
    s = np.array([1.0, 2.0, 2.0, 0.5])
    np.testing.assert_array_equal(top_k_ids(s, 3), [1, 2, 0])


def test_deltas():
    a = np.array([1.0, 2.0, 3.0])
    b = np.array([1.0, 1.0, 5.0])
    assert l1_delta(a, b) == 3.0
    assert linf_delta(a, b) == 2.0
    active = np.array([True, True, False])
    assert l1_delta(a, b, active) == 1.0
