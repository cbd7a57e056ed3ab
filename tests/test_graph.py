"""Graph substrate: streaming updates, degrees, capacities."""

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.graph import graph as G


def _rand_edges(rng, n_nodes, m):
    src = rng.integers(0, n_nodes, m).astype(np.int32)
    dst = rng.integers(0, n_nodes, m).astype(np.int32)
    return src, dst


def test_from_edges_basic():
    src = np.array([0, 1, 2, 0], np.int32)
    dst = np.array([1, 2, 0, 2], np.int32)
    g = G.from_edges(src, dst, node_capacity=8, edge_capacity=16)
    assert int(g.num_edges) == 4
    assert int(g.num_live_edges()) == 4
    assert int(g.num_active_nodes()) == 3
    np.testing.assert_array_equal(np.asarray(g.out_deg)[:4], [2, 1, 1, 0])
    np.testing.assert_array_equal(np.asarray(g.in_deg)[:4], [1, 1, 2, 0])


def test_from_edges_capacity_checks():
    with pytest.raises(ValueError):
        G.from_edges(np.zeros(10, np.int32), np.zeros(10, np.int32), 4, 5)
    with pytest.raises(ValueError):
        G.from_edges(np.array([9], np.int32), np.array([0], np.int32), 4, 5)


@settings(max_examples=25, deadline=None)
@given(
    n_init=st.integers(0, 40),
    n_add=st.integers(1, 40),
    seed=st.integers(0, 2**16),
)
def test_incremental_degrees_match_recompute(n_init, n_add, seed):
    """Property: incrementally-maintained degrees equal a full recount."""
    rng = np.random.default_rng(seed)
    n_nodes = 16
    s0, d0 = _rand_edges(rng, n_nodes, n_init)
    g = G.from_edges(s0, d0, node_capacity=n_nodes, edge_capacity=128)
    s1, d1 = _rand_edges(rng, n_nodes, n_add)
    g = G.add_edges(g, jnp.asarray(s1), jnp.asarray(d1))
    out_ref, in_ref = G.recompute_degrees(g)
    np.testing.assert_array_equal(np.asarray(g.out_deg), np.asarray(out_ref))
    np.testing.assert_array_equal(np.asarray(g.in_deg), np.asarray(in_ref))


def test_add_edges_beyond_capacity_drops():
    g = G.from_edges(np.array([0], np.int32), np.array([1], np.int32), 4, 3)
    g = G.add_edges(g, jnp.array([1, 2, 3], jnp.int32), jnp.array([0, 0, 0], jnp.int32))
    assert int(g.num_edges) == 3       # capped at capacity
    assert int(g.num_live_edges()) == 3
    # the dropped edge (3->0) must not contribute to degrees
    assert int(np.asarray(g.out_deg)[3]) == 0


def test_remove_edges_tombstones():
    src = np.array([0, 1, 2], np.int32)
    dst = np.array([1, 2, 0], np.int32)
    g = G.from_edges(src, dst, 4, 8)
    slots = G.find_edge_slots(g, np.array([1]), np.array([2]))
    assert slots[0] == 1
    g = G.remove_edges_by_slot(g, jnp.asarray(slots))
    assert int(g.num_live_edges()) == 2
    assert int(np.asarray(g.out_deg)[1]) == 0
    assert int(np.asarray(g.in_deg)[2]) == 0
    # double removal is a no-op
    g = G.remove_edges_by_slot(g, jnp.asarray(slots))
    assert int(g.num_live_edges()) == 2
    assert int(np.asarray(g.out_deg)[1]) == 0


def test_add_edges_past_capacity_keeps_the_last_slot():
    """Dropped appends must not write over the last slot in range."""
    g = G.from_edges(np.array([0], np.int32), np.array([1], np.int32), 8, 3)
    g = G.add_edges(g, jnp.array([1, 2, 3, 4], jnp.int32),
                    jnp.array([5, 6, 7, 7], jnp.int32))
    np.testing.assert_array_equal(np.asarray(g.src), [0, 1, 2])
    np.testing.assert_array_equal(np.asarray(g.dst), [1, 5, 6])
    out_ref, in_ref = G.recompute_degrees(g)
    np.testing.assert_array_equal(np.asarray(g.out_deg), np.asarray(out_ref))
    np.testing.assert_array_equal(np.asarray(g.in_deg), np.asarray(in_ref))
    assert not bool(np.asarray(g.node_active)[7])


@pytest.mark.parametrize("repeats", [1, 2, 3])
def test_duplicate_removals_count_each_slot_once(repeats):
    """A batch that names one slot several times (find_edge_slots maps
    repeated requests for an edge to its slot) decrements its degrees
    once, so the counters stay the row offsets of the sorted layout."""
    from repro.graph.csr import sort_by_dst

    rng = np.random.default_rng(repeats)
    src, dst = _rand_edges(rng, 32, 200)
    g = G.from_edges(src, dst, node_capacity=32, edge_capacity=256)
    pick = rng.choice(200, 5, replace=False)
    twice = np.concatenate([pick] + [pick[:2]] * repeats)
    slots = np.append(G.find_edge_slots(g, src[twice], dst[twice]), -1)
    g = G.remove_edges_by_slot(g, jnp.asarray(rng.permutation(slots)))
    assert int(g.num_live_edges()) == 195
    out_ref, in_ref = G.recompute_degrees(g)
    np.testing.assert_array_equal(np.asarray(g.out_deg), np.asarray(out_ref))
    np.testing.assert_array_equal(np.asarray(g.in_deg), np.asarray(in_ref))
    for reverse in (False, True):
        se = sort_by_dst(g, reverse=reverse)
        want = np.searchsorted(np.asarray(se.dst), np.arange(33), "left")
        np.testing.assert_array_equal(np.asarray(se.row_offsets), want)


def test_compact_reclaims_tombstones():
    src = np.array([0, 1, 2], np.int32)
    dst = np.array([1, 2, 0], np.int32)
    g = G.from_edges(src, dst, 4, 8)
    g = G.remove_edges_by_slot(g, jnp.array([0], jnp.int32))
    g2 = G.compact(g)
    assert int(g2.num_edges) == 2
    out_ref, in_ref = G.recompute_degrees(g2)
    np.testing.assert_array_equal(np.asarray(g2.out_deg), np.asarray(out_ref))


def test_networkx_roundtrip():
    rng = np.random.default_rng(0)
    src, dst = _rand_edges(rng, 20, 50)
    g = G.from_edges(src, dst, 20, 64)
    nxg = G.to_networkx(g)
    # COO may contain duplicate edges; networkx dedupes
    uniq = {(int(a), int(b)) for a, b in zip(src, dst)}
    assert nxg.number_of_edges() == len(uniq)
