"""Edge weights of the Graph500 SSSP kernel (Graph500 specification,
kernel 3): one float32 weight per input edge, uniform on [0, 1), drawn
from the run's seed in a stream of its own, so the graph's structure, its
labels and its edge order are those the same seed gives without weights.
Each draw is a multiple of 2^-24; a zero comes about once in 2^24 draws."""
from __future__ import annotations

import numpy as np

STREAM = 0x5555  # the weights' own stream of the seed


def edge_weights(seed: int, m: int) -> np.ndarray:
    """(m,) float32 weights, the i-th for the i-th input edge."""
    return np.random.default_rng([seed, STREAM]).random(m, dtype=np.float32)
