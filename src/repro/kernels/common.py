"""Shared kernel utilities."""
from __future__ import annotations

import jax


def default_interpret() -> bool:
    """Pallas kernels target TPU; on the CPU backend we validate with
    interpret=True (the kernel body executes as JAX ops)."""
    return jax.default_backend() != "tpu"


def round_up(a: int, b: int) -> int:
    return -(-a // b) * b
