"""Pallas TPU kernel: ELL-padded neighbor gather-reduce.

The PAL layout bounds per-vertex in-degree by |E|/P (paper §4.1 constraint),
so a destination-node block's neighbor lists pad to a fixed K — the ELL
format. out[n] = Σ_k mask[n,k]·x[idx[n,k]] is exactly the frontier-expansion
kernel's masked row sum, so this is that kernel under the ELL names: idx
blocks in SMEM, x in HBM, one row DMA per occupied slot
(kernels/frontier_expand/frontier_expand.py).
"""
from __future__ import annotations

import functools

import jax

from ..frontier_expand.frontier_expand import frontier_expand_pallas

__all__ = ["segment_ell_pallas"]


@functools.partial(jax.jit, static_argnames=("n_block", "interpret"))
def segment_ell_pallas(idx, mask, x, *, n_block: int = 128, interpret=None):
    """idx/mask: (N, K); x: (M, F). N % n_block == 0, F % 128 == 0.
    Returns (N, F) masked neighbor sums."""
    return frontier_expand_pallas(idx, mask, x, r_block=n_block,
                                  interpret=interpret)
