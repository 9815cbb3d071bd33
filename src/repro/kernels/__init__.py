"""The device kernels of the store's analytics path (interpret mode on CPU).

One package, `frontier_expand`: frontier_expand.py (the Mosaic row-DMA
gather, `pl.pallas_call` + BlockSpec tiling), ops.py (the plan builder and
the jit'd launches over a resident plan) and ref.py (the jnp and numpy
oracles). common.py holds the backend test and the rounding helpers.
"""
