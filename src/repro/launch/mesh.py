"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "TPU_V5E"]

# TPU v5e hardware constants (per chip) for the roofline model
TPU_V5E = {
    "peak_flops_bf16": 197e12,   # FLOP/s
    "hbm_bytes_per_s": 819e9,    # HBM bandwidth
    "ici_bytes_per_s": 50e9,     # per ICI link
    "hbm_bytes": 16e9,
    "vmem_bytes": 128 * 2**20,
}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
