"""A directed power-law graph with given degree exponents and largest
degrees (the Chung-Lu model): every vertex gets an out-weight and an
in-weight, each a power law over its rank, and each of the `n_edges`
edges draws its source by out-weight and its destination by in-weight,
independently. A vertex's expected degree is then proportional to its
weight, so the degrees follow P(deg = k) ~ k^-exponent up to the largest,
which is `max_*_share` of the edges.

Rank r holds the mass of (x + r0)^(-1 / (exponent - 1)) over [r, r + 1),
with r0 chosen so that rank 0 holds `max_*_share` of it all; a rank is
drawn by inverting that distribution in closed form. Ranks are scattered
over the vertex ids by two independent permutations from the seed.
Self-loops and repeated edges are kept as drawn (a few, between hubs).
"""
from __future__ import annotations

import numpy as np


def _mass(a: float, b: float, r0: float, q: float) -> float:
    """The integral of (x + r0)^-beta over [a, b), times q = 1 - beta."""
    return (b + r0) ** q - (a + r0) ** q


def rank_offset(n: int, exponent: float, max_share: float) -> float:
    """The r0 that gives rank 0 of n the share `max_share`."""
    if not 1.0 / n < max_share < 1.0:
        raise ValueError(f"max_share {max_share} outside (1/n, 1)")
    q = 1.0 - 1.0 / (exponent - 1.0)
    lo, hi = 1e-12, 1e12          # rank 0's share falls as r0 grows
    for _ in range(200):
        mid = np.sqrt(lo * hi)
        if _mass(0, 1, mid, q) / _mass(0, n, mid, q) > max_share:
            lo = mid
        else:
            hi = mid
    return float(np.sqrt(lo * hi))


def draw_ranks(rng, n: int, m: int, exponent: float,
               max_share: float) -> np.ndarray:
    """`m` ranks in [0, n), drawn by the inverse of the distribution."""
    q = 1.0 - 1.0 / (exponent - 1.0)
    r0 = rank_offset(n, exponent, max_share)
    lo = r0 ** q
    x = (lo + rng.random(m) * _mass(0, n, r0, q)) ** (1.0 / q) - r0
    return np.clip(x.astype(np.int64), 0, n - 1)


def chung_lu_edges(n_vertices: int, n_edges: int, in_exponent: float,
                   out_exponent: float, max_in_share: float,
                   max_out_share: float, seed: int = 0):
    """(src, dst) int64 arrays of `n_edges` directed edges over
    `n_vertices` vertices."""
    rng = np.random.default_rng([seed, 0xC1])
    out_ids = rng.permutation(n_vertices)
    in_ids = rng.permutation(n_vertices)
    src = out_ids[draw_ranks(rng, n_vertices, n_edges, out_exponent,
                             max_out_share)]
    dst = in_ids[draw_ranks(rng, n_vertices, n_edges, in_exponent,
                            max_in_share)]
    return src, dst
