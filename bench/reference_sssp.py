"""The plain reference of the SSSP cells: float64 single-source shortest
paths from the generated edge list and weights alone, in straightforward
numpy, and the bound a float32 evaluation is held to. It imports nothing
of the program and takes nothing the program made.

A label-correcting Bellman-Ford over the stored (directed) edges: each
round relaxes the edges leaving pending vertices (those whose value fell
and whose edges have not been relaxed since), and takes the least
candidate per destination, so parallel edges count with their least
weight. A round takes the pending vertices within `DELTA` of the least
pending distance (Meyer and Sanders' delta-stepping order): the order
changes how often an edge is relaxed, not the answer, which is the least
path sum over every path. Weights are float32 draws, multiples of 2^-24
below 1, and a path sum stays below 2^7: float64 adds them exactly, so
`d64` is the exact least path sum.

The bound. A float32 evaluation (the program's) ends at the least float32
left-to-right path sum d[v] = min over paths of fl(...fl(0 + w1) + ...),
since w >= 0 makes fl(a + w) monotone in a and w. For each add, with
u = 2^-24, (a + w)(1 - u) <= fl(a + w) <= (a + w)(1 + u), and
fl(a + w) >= a. So the same relaxation with the step
    lo: max(a, (a + w)(1 - u))      hi: (a + w)(1 + u)
gives, by induction along the relaxation trees, lo[v] <= d[v] <= hi[v]:
lo's tree covers the program's own path (every path's float32 sum is at
least its lo value) and hi's the reference's (the program's sum is at
most that path's float32 sum). Over a path of h edges the two steps carry
the factors (1 -/+ u)^h, so hi - d64 and d64 - lo are first-order
γ(h)·d64 = h·u/(1 - h·u)·d64, with h covering the edges on both the
program's and the reference's paths, carried per edge instead of
bounded by one h. The `max(a, ·)` keeps lo from falling round a cycle of
tiny weights, as float32 does not. The float64 rounding of the steps
themselves (2^-53 per operation) is left out: first order. The bound of
vertex v is max(d64 - lo, hi - d64); a sound float32 run reads
|d - d64| / bound <= 1, and 0 where the bound is 0 (a distance of 0 is
exact).
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

U = 2.0 ** -24
DELTA = 1.0 / 128   # bucket width: about 2 relaxations per edge here


class WeightedCSR:
    """Out-edges of a directed weighted edge list: the edges of v are
    positions `off[v]:off[v] + deg[v]` of `nbr` and `w`."""

    def __init__(self, src: np.ndarray, dst: np.ndarray, w: np.ndarray,
                 n: int):
        order = np.argsort(src, kind="stable")
        self.nbr = np.asarray(dst, np.int64)[order]
        self.w = np.asarray(w, np.float64)[order]
        self.deg = np.bincount(src, minlength=n)
        self.off = np.cumsum(self.deg) - self.deg
        self.n = n

    def edges_of(self, frontier: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """(position, index into frontier) of every edge leaving it: the
        ranges `off[v]:off[v] + deg[v]` laid end to end by one cumsum of
        steps, +1 inside a range and a jump to the next range's start."""
        has = np.flatnonzero(self.deg[frontier])
        lens, starts = self.deg[frontier[has]], self.off[frontier[has]]
        if not has.shape[0]:
            return np.empty(0, np.int64), np.empty(0, np.int64)
        ends = np.cumsum(lens)
        steps = np.ones(int(ends[-1]), np.int64)
        steps[0] = starts[0]
        steps[ends[:-1]] = starts[1:] - starts[:-1] - lens[:-1] + 1
        return np.cumsum(steps), np.repeat(has, lens)


def sssp_bounds(csr: WeightedCSR, root: int):
    """(d64, lo, hi) from `root`: the exact least path sums and the
    float32 envelope of the module's docstring; +inf where unreached."""
    steps = (lambda a, w: a + w,
             lambda a, w: np.maximum(a, (a + w) * (1 - U)),
             lambda a, w: (a + w) * (1 + U))
    vals = [np.full(csr.n, np.inf) for _ in steps]
    for d in vals:
        d[root] = 0.0
    pending = np.zeros(csr.n, bool)
    pending[root] = True
    while pending.any():
        waiting = np.flatnonzero(pending)
        near = vals[0][waiting]
        frontier = waiting[near <= near.min() + DELTA]
        pending[frontier] = False
        pos, owner = csr.edges_of(frontier)
        tgt, w = csr.nbr[pos], csr.w[pos]
        for d, step in zip(vals, steps):
            best = np.full(csr.n, np.inf)
            np.minimum.at(best, tgt, step(d[frontier][owner], w))
            better = best < d
            d[better] = best[better]
            pending |= better
    return tuple(vals)


def err_over_bound(d: np.ndarray, d64: np.ndarray, lo: np.ndarray,
                   hi: np.ndarray) -> float:
    """The largest |d - d64| over its vertex's bound, over the vertices
    both reach: 0 where d is exact, +inf where the bound is 0 and d is
    not exact."""
    both = np.isfinite(d) & np.isfinite(d64)
    err = np.abs(np.asarray(d, np.float64)[both] - d64[both])
    bound = np.maximum(d64[both] - lo[both], hi[both] - d64[both])
    ratio = np.zeros(err.shape[0])
    nz = err > 0
    with np.errstate(divide="ignore"):
        ratio[nz] = err[nz] / bound[nz]
    return float(ratio.max()) if ratio.shape[0] else 0.0
