"""Peaks of the devices the benchmark runs on, and the algorithmic work of
each layer it reads a roofline share of.

A share of a roofline is the least time the chip could take for the work
the algorithm needs (the larger of operations over peak operations per
second and bytes over peak bytes per second) over the time the device was
busy. The counts use only the sizes of the input (|V|, |E|, the useful
frontier columns), never a program's padded layout, so they read the same
work whatever implements the layer.
"""
from __future__ import annotations

from typing import Any, Dict

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 394 TOP/s int8,
# 16 GB HBM at 819 GB/s per chip. JAX names the chip "TPU v5 lite".
_V5E = {"flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e"}
PEAKS: Dict[str, Dict[str, Any]] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks(device_kind: str) -> Dict[str, Any]:
    """The peaks of `device_kind`; a kind missing from the table is an
    error, not a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it "
                       f"to bench/roofline.py with its source") from None


def least_seconds(flops: float, nbytes: float, pk: Dict[str, Any]) -> float:
    return max(flops / pk["flops"], nbytes / pk["hbm_bytes_per_s"])


def frontier_hop_work(n_vertices: int, n_edges: int, columns: int):
    """One dense frontier hop over every distinct edge: read each edge's
    source id (4 bytes), read the frontier indicator (4 bytes per vertex
    and column) and write the per-vertex counts (4 bytes per vertex and
    column); one add per edge and column. Returns (flops, bytes)."""
    flops = n_edges * columns
    nbytes = 4 * n_edges + 8 * n_vertices * columns
    return flops, nbytes


def pagerank_sweep_work(n_vertices: int, n_edges: int):
    """One PageRank iteration over every stored edge: read each edge's
    source and destination ids (8 bytes), read each vertex's rank and
    out-degree and write its new rank (12 bytes); a multiply-add per edge,
    a divide, multiply and add per vertex. Returns (flops, bytes)."""
    flops = 2 * n_edges + 3 * n_vertices
    nbytes = 8 * n_edges + 12 * n_vertices
    return flops, nbytes
