"""Distributed PSW operators (shard_map): the paper's sliding windows on TPU.

GraphChi streams each partition's windows sequentially through RAM; here the
node-state shards stream around the device ring via collective-permute. One
full revolution delivers every remote source row exactly once — an
all-gather's bytes with an x-shard-sized memory footprint (DESIGN.md §2).

Ops (all differentiable; ring_gather has a custom VJP whose backward is a
REVERSE grad-ring, so nothing is checkpointed per step):

  ring_gather(x, idx)        x row-sharded, idx arbitrary global rows
  local_gather(x, idx)       idx guaranteed local to the shard (PAL dst!)
  local_scatter_sum(v, idx)  scatter into shard-local rows
  local_edge_softmax(s, idx) softmax grouped by shard-local destination

`ring_mesh(mesh)` reshapes any production mesh into the 1-D ring view these
ops use (same devices, flattened order).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from jax.sharding import AxisType

__all__ = ["ring_mesh", "ring_gather", "ring_scatter_sum", "local_gather",
           "local_scatter_sum", "local_edge_softmax"]


def ring_mesh(mesh: Mesh) -> Mesh:
    """1-D view of a production mesh (same devices, flattened)."""
    return Mesh(mesh.devices.reshape(-1), ("ring",), axis_types=(AxisType.Auto,))


def _expand(sel, ndim):
    return sel.reshape(sel.shape + (1,) * (ndim - 1))


# ---------------------------------------------------------------------------
# ring gather with reverse-ring VJP
# ---------------------------------------------------------------------------
def _ring_fwd_local(x_loc, idx_loc, *, P_size: int, n_loc: int):
    my = jax.lax.axis_index("ring")
    fwd_perm = [(j, (j + 1) % P_size) for j in range(P_size)]
    out0 = jax.lax.pcast(
        jnp.zeros((idx_loc.shape[0],) + x_loc.shape[1:], x_loc.dtype),
        ("ring",), to="varying")

    def step(carry, s):
        x_rot, out = carry
        owner = jax.lax.rem(my - s + P_size, P_size)
        sel = (idx_loc // n_loc) == owner
        local_row = jnp.clip(idx_loc - owner * n_loc, 0, n_loc - 1)
        rows = jnp.take(x_rot, local_row, axis=0)
        out = out + jnp.where(_expand(sel, rows.ndim), rows, 0)
        x_rot = jax.lax.ppermute(x_rot, "ring", fwd_perm)
        return (x_rot, out), None

    (_, out), _ = jax.lax.scan(step, (x_loc, out0), jnp.arange(P_size))
    return out


def _ring_bwd_local(idx_loc, g_loc, *, P_size: int, n_loc: int,
                    feat_shape, dtype):
    """Reverse grad-ring: a per-shard gradient buffer circulates backward;
    each device scatter-adds its contribution when the owner's buffer is
    resident; after P steps every buffer is home, fully accumulated."""
    my = jax.lax.axis_index("ring")
    bwd_perm = [(j, (j - 1) % P_size) for j in range(P_size)]
    g32 = g_loc.astype(jnp.float32)

    def step(gbuf, s):
        owner = jax.lax.rem(my + s, P_size)
        sel = (idx_loc // n_loc) == owner
        local_row = jnp.clip(idx_loc - owner * n_loc, 0, n_loc - 1)
        contrib = jax.ops.segment_sum(
            jnp.where(_expand(sel, g32.ndim), g32, 0), local_row,
            num_segments=n_loc)
        gbuf = gbuf + contrib
        gbuf = jax.lax.ppermute(gbuf, "ring", bwd_perm)
        return gbuf, None

    gbuf0 = jax.lax.pcast(jnp.zeros((n_loc,) + feat_shape, jnp.float32),
                          ("ring",), to="varying")
    gbuf, _ = jax.lax.scan(step, gbuf0, jnp.arange(P_size))
    return gbuf.astype(dtype)


def ring_gather(x: jnp.ndarray, idx: jnp.ndarray, mesh: Mesh) -> jnp.ndarray:
    """x: (N, ...) row-sharded over the ring; idx: (E,) global row ids,
    edge-sharded. Returns x[idx], edge-sharded. N and E must divide the ring."""
    rmesh = ring_mesh(mesh)
    P_size = rmesh.devices.size
    n_loc = x.shape[0] // P_size
    spec = P("ring")
    feat_shape, x_dtype = x.shape[1:], x.dtype  # static, captured in closure

    @jax.custom_vjp
    def _gather(x, idx):
        f = functools.partial(_ring_fwd_local, P_size=P_size, n_loc=n_loc)
        return shard_map(f, mesh=rmesh, in_specs=(spec, spec),
                         out_specs=spec)(x, idx)

    def _fwd(x, idx):
        return _gather(x, idx), idx

    def _bwd(idx, g):
        b = functools.partial(_ring_bwd_local, P_size=P_size, n_loc=n_loc,
                              feat_shape=feat_shape, dtype=x_dtype)
        gx = shard_map(b, mesh=rmesh, in_specs=(spec, spec),
                       out_specs=spec)(idx, g)
        return gx, None

    _gather.defvjp(_fwd, _bwd)
    return _gather(x, idx)


def ring_scatter_sum(vals: jnp.ndarray, idx: jnp.ndarray, n: int,
                     mesh: Mesh) -> jnp.ndarray:
    """Transpose of ring_gather: scatter-add rows `vals` (edge-sharded) into
    global rows idx of an (n, ...) output (row-sharded over the ring), via
    the reverse grad-ring — never materializing a replicated (n, ...) array.
    VJP is a ring_gather of the cotangent."""
    rmesh = ring_mesh(mesh)
    P_size = rmesh.devices.size
    n_loc = n // P_size
    spec = P("ring")
    feat_shape, v_dtype = vals.shape[1:], vals.dtype

    @jax.custom_vjp
    def _scatter(vals, idx):
        def f(v_loc, idx_loc):
            return _ring_bwd_local(idx_loc, v_loc, P_size=P_size, n_loc=n_loc,
                                   feat_shape=feat_shape, dtype=v_dtype)
        return shard_map(f, mesh=rmesh, in_specs=(spec, spec),
                         out_specs=spec)(vals, idx)

    def _fwd(vals, idx):
        return _scatter(vals, idx), idx

    def _bwd(idx, g):
        f = functools.partial(_ring_fwd_local, P_size=P_size, n_loc=n_loc)
        gv = shard_map(f, mesh=rmesh, in_specs=(spec, spec),
                       out_specs=spec)(g, idx)
        return gv.astype(v_dtype), None

    _scatter.defvjp(_fwd, _bwd)
    return _scatter(vals, idx)


# ---------------------------------------------------------------------------
# shard-local ops (PAL guarantees destination locality)
# ---------------------------------------------------------------------------
def local_gather(x: jnp.ndarray, idx: jnp.ndarray, mesh: Mesh) -> jnp.ndarray:
    """x[idx] where every idx is owned by the same shard as the edge —
    exactly the PAL property for destination rows. Zero communication."""
    rmesh = ring_mesh(mesh)
    P_size = rmesh.devices.size
    n_loc = x.shape[0] // P_size
    spec = P("ring")

    def f(x_loc, idx_loc):
        my = jax.lax.axis_index("ring")
        return jnp.take(x_loc, jnp.clip(idx_loc - my * n_loc, 0, n_loc - 1),
                        axis=0)

    return shard_map(f, mesh=rmesh, in_specs=(spec, spec), out_specs=spec)(x, idx)


def local_scatter_sum(vals: jnp.ndarray, idx: jnp.ndarray, n: int,
                      mesh: Mesh) -> jnp.ndarray:
    """segment-sum into shard-local destination rows. Zero communication."""
    rmesh = ring_mesh(mesh)
    P_size = rmesh.devices.size
    n_loc = n // P_size
    spec = P("ring")

    def f(v_loc, idx_loc):
        my = jax.lax.axis_index("ring")
        return jax.ops.segment_sum(
            v_loc, jnp.clip(idx_loc - my * n_loc, 0, n_loc - 1),
            num_segments=n_loc)

    return shard_map(f, mesh=rmesh, in_specs=(spec, spec), out_specs=spec)(
        vals, idx)


def local_edge_softmax(scores: jnp.ndarray, idx: jnp.ndarray, n: int,
                       mesh: Mesh) -> jnp.ndarray:
    """edge_softmax grouped by shard-local destinations."""
    from .segment_ops import edge_softmax
    rmesh = ring_mesh(mesh)
    P_size = rmesh.devices.size
    n_loc = n // P_size
    spec = P("ring")

    def f(s_loc, idx_loc):
        my = jax.lax.axis_index("ring")
        loc = jnp.clip(idx_loc - my * n_loc, 0, n_loc - 1)
        if s_loc.ndim == 1:
            return edge_softmax(s_loc, loc, n_loc)
        return jax.vmap(lambda col: edge_softmax(col, loc, n_loc),
                        in_axes=1, out_axes=1)(s_loc)

    return shard_map(f, mesh=rmesh, in_specs=(spec, spec), out_specs=spec)(
        scores, idx)
