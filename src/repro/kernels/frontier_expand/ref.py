"""Oracles for the frontier-expansion kernel: a jit'd jnp K-loop (the
device-less default of `frontier_expand_counts`) and a plain numpy K-loop
(the test oracle)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["frontier_expand_ref", "frontier_expand_np"]


def frontier_expand_np(idx, mask, x):
    """Numpy oracle, K-loop so peak memory stays (R, B) instead of the
    (R, K, B) a one-shot fancy-gather would allocate."""
    acc = np.zeros((idx.shape[0], x.shape[1]), x.dtype)
    for k in range(idx.shape[1]):
        acc += np.where(mask[:, k:k + 1], x[idx[:, k]], 0)
    return acc


@jax.jit
def frontier_expand_ref(idx, mask, x):
    """idx/mask: (R, K); x: (M, B). out[r] = Σ_k mask[r,k]·x[idx[r,k]].
    Same K-loop shape as the kernel (bounded memory at 1M+ edges)."""
    def body(k, acc):
        rows = x[jax.lax.dynamic_index_in_dim(idx, k, 1, False)]
        m = jax.lax.dynamic_index_in_dim(mask, k, 1, False)
        return acc + jnp.where(m[:, None], rows, 0)

    acc0 = jnp.zeros((idx.shape[0], x.shape[1]), x.dtype)
    return jax.lax.fori_loop(0, idx.shape[1], body, acc0)
