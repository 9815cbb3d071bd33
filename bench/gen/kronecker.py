"""Graph500 Kronecker edge generator (Graph500 specification, "Kernel 1"
input): `edgefactor · 2^scale` undirected edges, each placed by `scale`
independent choices of a quadrant of the adjacency matrix with
probabilities A, B, C, D = 1 - A - B - C; then the vertex labels are
permuted and the edge list shuffled. Self-loops and repeated edges are
kept, as the specification generates them.

The quadrant bits, the graph's structure, come from `structure_seed`; the
labels and the edge order from `seed`. So every seed gives the same graph
up to its labelling, in another order: the same work, on other inputs.
The bits are drawn level by level, as the specification's own reference
code (`kronecker_generator.m`) does, in one jitted call on the default
device (threefry bits: the same on every backend).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnums=(1, 2))
def _quadrant_bits(key, scale: int, m: int, ab, c_norm, a_norm):
    def level(i, uv):
        u, v = uv
        ku, kv = jax.random.split(jax.random.fold_in(key, i))
        u_bit = jax.random.uniform(ku, (m,)) > ab
        v_bit = jax.random.uniform(kv, (m,)) > jnp.where(u_bit, c_norm,
                                                          a_norm)
        return (u | (u_bit.astype(jnp.int32) << i),
                v | (v_bit.astype(jnp.int32) << i))

    zero = jnp.zeros((m,), jnp.int32)
    return jax.lax.fori_loop(0, scale, level, (zero, zero))


def labelling(scale: int, seed: int) -> np.ndarray:
    """The permutation `kronecker_edges` applies to the vertex labels:
    structural vertex i is labelled `labelling(scale, seed)[i]`."""
    return np.random.default_rng([seed, 1]).permutation(1 << scale)


def kronecker_edges(scale: int, edgefactor: int = 16, a: float = 0.57,
                    b: float = 0.19, c: float = 0.19, seed: int = 0,
                    structure_seed: int = 0):
    """(u, v) int64 arrays of `edgefactor · 2^scale` undirected edges over
    `2^scale` vertices."""
    m = edgefactor << scale
    k0, k1 = (int(x) for x in np.random.default_rng(structure_seed)
              .integers(0, 1 << 31, 2))
    key = jax.random.fold_in(jax.random.PRNGKey(k0), k1)
    ab = a + b
    u, v = _quadrant_bits(key, scale, m, np.float32(ab),
                          np.float32(c / (1.0 - ab)), np.float32(a / ab))
    perm = labelling(scale, seed)
    order = np.random.default_rng([seed, 2]).permutation(m)
    return (perm[np.asarray(u, np.int64)][order],
            perm[np.asarray(v, np.int64)][order])
