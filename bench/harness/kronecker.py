"""Graph500 Kronecker graphs, generated on the device from a seed.

The generator follows the Graph500 specification's reference
(``kronecker_generator.m``): ``edge_factor * 2**scale`` pairs, each built
bit by bit over ``scale`` levels from the initiator probabilities A, B, C
(D = 1 - A - B - C); then the vertex labels are permuted at random.  The
graph is undirected, as the specification and LDBC Graphalytics' graph500
datasets have it: each pair is taken unordered, self-loops and repeated
pairs are dropped (the datasets hold neither), and every kept pair is an
edge in both directions.  The kept pairs come out in a uniformly shuffled
order, so that the first ``holdout`` of them are a uniform sample to
stream in as updates.

The graph is the configuration's: its pairs and labels come from the
configuration's ``graph_seed``, as a Graphalytics dataset is one fixed
file.  The run's seed draws only the shuffle, so every run works on the
same graph, with another sample of its edges streamed in another order.

Everything runs in one jitted call, so set-up pays one device program and
one transfer instead of a host loop over vertices.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class KroneckerGraph(NamedTuple):
    """Host copy of one generated graph, int32: the initial graph's
    directed edges (each kept pair both ways), and the held-out update
    stream in blocks of ``block`` directed edges, each block ``block / 2``
    pairs both ways."""

    init_src: np.ndarray
    init_dst: np.ndarray
    stream_src: np.ndarray
    stream_dst: np.ndarray
    num_vertices: int


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed up to 2**64 - 1 (the seed's
    two 32-bit halves are folded in one after the other)."""
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    key = jax.random.key(0)
    key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


@functools.partial(jax.jit, static_argnames=("scale", "edge_factor", "abc"))
def _generate(graph_key, stream_key, *, scale: int, edge_factor: int,
              abc: tuple):
    a, b, c = abc
    m = edge_factor << scale
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    k_bits, k_perm = jax.random.split(graph_key)

    def level(i, carry):
        ii, jj = carry
        u = jax.random.uniform(jax.random.fold_in(k_bits, i), (2, m))
        ii_bit = u[0] > ab
        jj_bit = u[1] > jnp.where(ii_bit, c_norm, a_norm)
        return (ii | (ii_bit.astype(jnp.int32) << i),
                jj | (jj_bit.astype(jnp.int32) << i))

    zero = jnp.zeros((m,), jnp.int32)
    ii, jj = jax.lax.fori_loop(0, scale, level, (zero, zero))
    perm = jax.random.permutation(k_perm, 1 << scale).astype(jnp.int32)
    u, v = perm[ii], perm[jj]
    src, dst = jax.lax.sort((jnp.minimum(u, v), jnp.maximum(u, v)),
                            num_keys=2)
    repeat = (src[1:] == src[:-1]) & (dst[1:] == dst[:-1])
    keep = (src != dst) & ~jnp.concatenate([jnp.zeros((1,), bool), repeat])
    # kept pairs first, in a uniformly random order; dropped ones last
    bits = jax.random.bits(stream_key, (m,), jnp.uint32) >> 1
    order_key = jnp.where(keep, bits, jnp.uint32(0xFFFFFFFF))
    _, src, dst = jax.lax.sort((order_key, src, dst), num_keys=1)
    return src, dst, jnp.sum(keep.astype(jnp.int32))


def generate(seed: int, *, graph_seed: int, scale: int, edge_factor: int,
             a: float, b: float, c: float, holdout_blocks: int,
             block: int) -> KroneckerGraph:
    """Generate the graph of ``graph_seed`` on the default device, shuffle
    its pairs by ``seed`` and split off the first ``holdout_blocks *
    block / 2`` of them as the update stream."""
    if block % 2:
        raise ValueError(f"a block of {block} directed edges is no whole "
                         "number of pairs")
    half = block // 2
    holdout = holdout_blocks * half
    src, dst, kept = _generate(seed_key(graph_seed), seed_key(seed),
                               scale=scale, edge_factor=edge_factor,
                               abc=(float(a), float(b), float(c)))
    kept = int(kept)
    if holdout >= kept:
        raise ValueError(f"holdout {holdout} >= {kept} generated pairs")
    # slice on the host: a device slice would compile once per edge count
    src = np.asarray(src)[:kept]
    dst = np.asarray(dst)[:kept]
    u, v = src[holdout:], dst[holdout:]
    su = src[:holdout].reshape(holdout_blocks, half)
    sv = dst[:holdout].reshape(holdout_blocks, half)
    return KroneckerGraph(np.concatenate([u, v]), np.concatenate([v, u]),
                          np.concatenate([su, sv], axis=1).reshape(-1),
                          np.concatenate([sv, su], axis=1).reshape(-1),
                          1 << scale)
