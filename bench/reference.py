"""The plain reference: the same semantics as the store's answers, from
the generated edge list alone, in straightforward numpy. It imports
nothing of the program and takes nothing the program made."""
from __future__ import annotations

import numpy as np


class CSR:
    """Adjacency of a directed edge list: neighbours of v are
    `nbr[off[v]:off[v + 1]]`."""

    def __init__(self, src: np.ndarray, dst: np.ndarray, n: int):
        order = np.argsort(src, kind="stable")
        self.nbr = np.asarray(dst, np.int64)[order]
        self.off = np.zeros(n + 1, np.int64)
        np.cumsum(np.bincount(src, minlength=n), out=self.off[1:])
        self.n = n

    def expand(self, frontier: np.ndarray) -> np.ndarray:
        """All neighbours of the frontier's vertices (with repeats)."""
        starts, ends = self.off[frontier], self.off[frontier + 1]
        lens = ends - starts
        total = int(lens.sum())
        if total == 0:
            return np.empty(0, np.int64)
        base = np.repeat(starts - np.cumsum(lens) + lens, lens)
        return self.nbr[base + np.arange(total)]


def bfs_depths(csr: CSR, root: int, k: int) -> np.ndarray:
    """Level-synchronous BFS from `root` along the CSR's edges, at most `k`
    hops: depth of every vertex, -1 where it is not reached."""
    depth = np.full(csr.n, -1, np.int32)
    depth[root] = 0
    frontier = np.array([root], np.int64)
    for d in range(1, k + 1):
        touched = np.zeros(csr.n, bool)
        touched[csr.expand(frontier)] = True
        frontier = np.flatnonzero(touched & (depth < 0))
        if frontier.shape[0] == 0:
            break
        depth[frontier] = d
    return depth


def jacobi_pagerank(src, dst, n, iters, damping=0.85):
    """Plain float64 synchronous PageRank over a COO edge list, and the
    bound a float32 evaluation of it is held to: a first-order bound on
    each vertex's rounding error. Per iteration, a sum of d in-edge terms
    taken in any order is within γ(d) = d·u/(1 − d·u) of its value
    (u = 2^-24; d + 2 covers the float32 damping factor and its multiply),
    the divide and multiply of each contribution round once each, every
    in-neighbour's own bound carries over through its contribution (and
    through the sum, whose γ(d) applies to the carried error too: at a hub
    γ is near 1), and the float32 constant 1 − damping and the final add
    round once each. Returns (ranks, bound). (Copied from the repository's
    `chip_smoke.jacobi_pagerank`.)"""
    u = 2.0 ** -24
    inv_deg = 1.0 / np.maximum(np.bincount(src, minlength=n), 1)
    du = (np.bincount(dst, minlength=n) + 2) * u
    gamma = np.where(du < 1, du / np.maximum(1 - du, u), np.inf)
    r, bound = np.ones(n), np.zeros(n)
    for _ in range(iters):
        acc = np.bincount(dst, weights=(r * inv_deg)[src], minlength=n)
        carried = np.bincount(dst, weights=((bound + 2 * u * r) * inv_deg)[src],
                              minlength=n)
        r = (1 - damping) + damping * acc
        bound = (damping * (gamma * (acc + carried) + carried)
                 + u * ((1 - damping) + r))
    return r, bound


def psw_internal_ids(n: int, n_partitions: int, interval_len: int):
    """Where the store's device layout keeps each original vertex id: the
    paper's reversible interval hash (GraphChi-DB §7.2),
    internal = (id mod P)·L + id div P."""
    ids = np.arange(n, dtype=np.int64)
    return (ids % n_partitions) * interval_len + ids // n_partitions
