"""Pallas TPU kernel: virtual-row ELL frontier expansion.

The multi-hop dense path (core/multihop.py, DESIGN.md §10.3) lays the
store's deduplicated edge set out destination-grouped in rows of at most K
sources — a destination of degree d spans ceil(d/K) VIRTUAL rows, so the
layout is linear in |E| where padding every vertex to the largest degree
would explode on power-law degree tails. The kernel accumulates, per
virtual row, the masked sum of frontier-indicator rows; the
per-destination reduction over virtual rows happens outside (a sorted
segment-sum keyed by the plan's `row_dst`).

Tiling: grid = (n_row_blocks,). The plan's mask is folded into idx (an
empty slot holds -1) and one (Br·K,) block of it sits in SMEM, where the
scalar core reads the DMA addresses. The indicator panel x stays in HBM
(`pl.ANY`): per virtual row, every occupied slot starts one DMA of the
whole row x[src] into its own VMEM buffer, then the row waits for its
copies and adds them in slot order in float32. Empty slots issue no DMA.

`gather_rows_sum` is that row-DMA gather over slots with the mask folded
in; `frontier_expand_pallas` takes the plan's (idx, mask) pair.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import default_interpret

__all__ = ["frontier_expand_pallas", "gather_rows_sum"]

LANES = 128


def _kernel(slots_ref, x_hbm, o_ref, buf, sems, *, k_slots: int,
            n_chunks: int):
    def copy(src, k):
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(src * n_chunks, n_chunks)], buf.at[k], sems.at[k])

    def row_body(i, carry):
        base = i * k_slots
        srcs = [slots_ref[base + k] for k in range(k_slots)]
        for k, src in enumerate(srcs):
            @pl.when(src >= 0)
            def _start():
                copy(src, k).start()
        acc = jnp.zeros((n_chunks, LANES), jnp.float32)
        for k, src in enumerate(srcs):
            @pl.when(src >= 0)
            def _wait():
                copy(src, k).wait()
            row = buf[k]
            acc = acc + jnp.where(src >= 0, row, 0.0)
        o_ref[pl.ds(i * n_chunks, n_chunks), :] = acc
        return carry

    jax.lax.fori_loop(0, o_ref.shape[0] // n_chunks, row_body, 0)


def gather_rows_sum(slots, x, *, r_block: int = 128, interpret=None):
    """out[r] = Σ_k x[slots[r, k]] over the slots that are >= 0,
    accumulated in float32 in slot order. slots: (R, K); x: (M, B) with
    R % r_block == 0 and B % 128 == 0. Returns (R, B) in x's dtype.

    Mosaic DMAs whole (8, 128) tiles of a tiled HBM array, except from a
    (M, 128) float32 array, whose rows it can copy one by one. So x goes
    in as float32 rows of 128 lanes: a row of B lanes is B/128 consecutive
    such rows, and the output comes back in the same layout."""
    if interpret is None:
        interpret = default_interpret()
    R, K = slots.shape
    M, B = x.shape
    assert R % r_block == 0 and B % LANES == 0
    n_chunks = B // LANES

    out = pl.pallas_call(
        functools.partial(_kernel, k_slots=K, n_chunks=n_chunks),
        grid=(R // r_block,),
        in_specs=[pl.BlockSpec((r_block * K,), lambda r: (r,),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],  # x stays in HBM
        out_specs=pl.BlockSpec((r_block * n_chunks, LANES), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((R * n_chunks, LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((K, n_chunks, LANES), jnp.float32),
                        pltpu.SemaphoreType.DMA((K,))],
        interpret=interpret,
        name="frontier_expand",
    )(slots.astype(jnp.int32).reshape(-1),
      x.astype(jnp.float32).reshape(M * n_chunks, LANES))
    return out.reshape(R, B).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("r_block", "interpret"))
def frontier_expand_pallas(idx, mask, x, *, r_block: int = 128,
                           interpret=None):
    """idx/mask: (R, K) virtual-row source slots; x: (M, B) frontier
    indicator panel. R % r_block == 0, B % 128 == 0. Returns (R, B)
    per-virtual-row masked sums (pre-reduction)."""
    return gather_rows_sum(jnp.where(mask, idx, -1), x, r_block=r_block,
                           interpret=interpret)
