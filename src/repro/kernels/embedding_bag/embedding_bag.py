"""Pallas TPU kernel: EmbeddingBag (ragged gather + weighted segment-reduce).

JAX has no native EmbeddingBag; this is the recsys hot path (huge sparse
table, many small bags) implemented as a TPU kernel. Bags are padded to K
slots (multi-hot layout). The PAL reversible hash (paper §7.2) spreads hot
rows across table shards; within a shard this kernel does the positional
lookup — the paper's 'edge position is the attribute key' discipline.

Tiling: grid = (n_bag_blocks,). idx/weight blocks sit in SMEM;
the table stays in HBM and rows stream in with one DMA per (bag, slot);
weighted float32 accumulation on the VPU — the shared row-DMA gather of
kernels/frontier_expand. Padded slots carry weight 0 and index 0 (row 0
fetched, multiplied by zero).
"""
from __future__ import annotations

import functools

import jax

from ..frontier_expand.frontier_expand import gather_rows_sum

__all__ = ["embedding_bag_pallas"]


@functools.partial(jax.jit, static_argnames=("bag_block", "interpret"))
def embedding_bag_pallas(idx, weights, table, *, bag_block: int = 128,
                         interpret=None):
    """idx/weights: (B, K); table: (V, D). B % bag_block == 0,
    D % 128 == 0. Returns (B, D) weighted sums."""
    return gather_rows_sum(idx, table, weights, r_block=bag_block,
                           interpret=interpret, name="embedding_bag")
