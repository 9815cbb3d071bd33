"""Synthetic edge streams for the ingestion benchmarks."""
from __future__ import annotations

import numpy as np

__all__ = ["GraphStream"]


class GraphStream:
    """Power-law edge stream (preferential-attachment-flavoured) for the
    online-insert benchmarks and incremental PageRank — the paper's twitter-
    2010-like ingestion workload, at configurable scale."""

    def __init__(self, n_vertices: int, alpha: float = 1.8, seed: int = 0):
        self.n = n_vertices
        self.alpha = alpha
        self.rng = np.random.default_rng(seed)

    def next_edges(self, k: int):
        """Returns (src, dst): sources uniform, destinations zipf-hot."""
        src = self.rng.integers(0, self.n, k)
        dst = (self.rng.zipf(self.alpha, k) - 1) % self.n
        # hash the hot head across the id space (paper's graphs have hot ids
        # scattered, not concentrated at 0)
        dst = (dst * 2654435761) % self.n
        return src, dst
