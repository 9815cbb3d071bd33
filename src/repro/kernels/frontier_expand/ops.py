"""Plan builder + jit'd wrapper for the frontier-expansion kernel.

Virtual-row ELL: the deduplicated edge set, grouped by destination, is
split into rows of at most `k_slots` sources — a destination of degree d
occupies ceil(d/k) rows, so the plan is linear in |E|. Compare the two
existing device layouts at 1M+ edges: `psw_spmm`'s dense tiles materialize
O(n_blocks²·B²) memory, and `pad_to_ell` pads every vertex to the max
degree (quadratic-ish on power-law tails, and truncating). The virtual-row
plan is exact and costs (|E|/k + n_present_dsts) rows.

`row_dst` maps each virtual row to its destination, destination-sorted;
padding rows map to `n_dst` so one sorted segment-sum both reduces the
virtual rows and discards padding.

A one-column panel (a single-root hop) skips the kernel: one flat XLA
gather of a value per slot, summed per virtual row, is the whole launch.

A weighted plan carries one float32 weight per slot (the least over
parallel edges) in the same layout, for the min-plus relax of
single-source shortest paths (`relax_launch`): gather each slot's source
distance, add its weight, take the least per virtual row, then a sorted
segment-min per destination.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...core import telemetry
from ..common import default_interpret, round_up
from .frontier_expand import LANES, gather_rows_sum
from .ref import frontier_expand_ref

__all__ = ["DevicePlan", "FrontierPlan", "build_frontier_plan",
           "expand_staged", "frontier_expand_counts",
           "frontier_expand_launch", "relax_launch", "relax_staged",
           "stage_frontier", "upload_plan"]

_M_LAUNCHES = telemetry.counter("multihop.kernel.launches")


@dataclasses.dataclass(frozen=True)
class FrontierPlan:
    """Device layout of one store's deduplicated edge set (one direction)."""

    idx: np.ndarray       # (R, K) int32 source id per slot
    mask: np.ndarray      # (R, K) bool, True where a slot holds an edge
    row_dst: np.ndarray   # (R,) int32 destination per row; padding -> n_dst
    n_src: int
    n_dst: int
    n_edges: int          # deduplicated edge count packed into the plan
    k_slots: int
    weight: Optional[np.ndarray] = None  # (R, K) float32, +inf where empty


def _dedup_min(keys: np.ndarray, weight):
    """Sorted-unique keys and, per key, the least of its weights."""
    order = np.argsort(keys)
    keys = keys[order]
    first = np.empty(keys.shape[0], bool)
    first[:1] = True
    first[1:] = keys[1:] != keys[:-1]
    start = np.flatnonzero(first)
    w = np.asarray(weight, np.float32).ravel()[order]
    return keys[start], np.minimum.reduceat(w, start)


def build_frontier_plan(src, dst, n_src: int, n_dst: int,
                        k_slots: int = 32, weight=None) -> FrontierPlan:
    """Host-side, fully vectorized: dedup + destination-major sort via one
    packed-key unique, ranks within destination groups via run-length
    arithmetic, then one scatter into the (R, K) slot grid. With a per-edge
    `weight`, each distinct (src, dst) keeps the least of its weights, in a
    float32 (R, K) grid beside the slots."""
    src = np.asarray(src, np.int64).ravel()
    dst = np.asarray(dst, np.int64).ravel()
    packed = dst * np.int64(n_src) + src
    if weight is None:
        keys, wkeys = np.unique(packed), None
    else:
        keys, wkeys = _dedup_min(packed, weight)
    E = keys.shape[0]
    if E == 0:
        return FrontierPlan(np.zeros((128, k_slots), np.int32),
                            np.zeros((128, k_slots), bool),
                            np.full(128, n_dst, np.int32),
                            int(n_src), int(n_dst), 0, k_slots,
                            None if weight is None else
                            np.full((128, k_slots), np.inf, np.float32))
    d = keys // n_src
    s = keys % n_src
    newgrp = np.empty(E, bool)
    newgrp[0] = True
    newgrp[1:] = d[1:] != d[:-1]
    gstart = np.flatnonzero(newgrp)
    gid = np.cumsum(newgrp) - 1
    rank = np.arange(E) - gstart[gid]
    gcount = np.diff(np.append(gstart, E))
    vrows = -(-gcount // k_slots)                  # ceil: rows per group
    vbase = np.cumsum(vrows) - vrows
    row = vbase[gid] + rank // k_slots
    col = rank % k_slots
    R = int(vrows.sum())
    Rp = round_up(R, 128)
    idx = np.zeros((Rp, k_slots), np.int32)
    mask = np.zeros((Rp, k_slots), bool)
    idx[row, col] = s
    mask[row, col] = True
    row_dst = np.full(Rp, n_dst, np.int32)
    row_dst[:R] = np.repeat(d[gstart], vrows)
    wgrid = None
    if wkeys is not None:
        wgrid = np.full((Rp, k_slots), np.inf, np.float32)
        wgrid[row, col] = wkeys
    return FrontierPlan(idx, mask, row_dst, int(n_src), int(n_dst), int(E),
                        k_slots, wgrid)


@dataclasses.dataclass(frozen=True)
class DevicePlan:
    """A plan's operands resident on the device (`upload_plan`)."""

    slots: jax.Array      # (R·K,) int32 source per slot, -1 where empty
    row_dst: jax.Array    # (R,) int32 destination per row
    n_dst: int
    nbytes: int           # bytes of the host arrays handed over
    weight: Optional[jax.Array] = None  # (R·K,) float32, a weighted plan's


def upload_plan(plan: FrontierPlan) -> DevicePlan:
    """Fold the plan's mask into its slots (an empty slot holds -1) and
    hand the slots and `row_dst` to the device, and a weighted plan's
    weights beside them, waiting until they have landed. A caller that
    keeps the result launches any number of expansions over the plan
    without sending it again. The slots go flat, as the kernel reads them:
    a (R, K) int32 array of K < 128 would be laid out in padded lane tiles
    and copied flat at every launch."""
    host = [np.where(plan.mask, plan.idx, np.int32(-1)).reshape(-1),
            plan.row_dst]
    if plan.weight is not None:
        host.append(plan.weight.reshape(-1))
    dev = jax.block_until_ready([jnp.asarray(a) for a in host])
    return DevicePlan(dev[0], dev[1], plan.n_dst,
                      sum(a.nbytes for a in host),
                      dev[2] if plan.weight is not None else None)


def stage_frontier(x):
    """Hand one launch's (n_src, B) indicator panel to the device as it is,
    unpadded, waiting until it has landed. Returns the device panel and
    the bytes of the host array handed over."""
    x = np.ascontiguousarray(x, np.float32)
    return jax.block_until_ready(jnp.asarray(x)), x.nbytes


@functools.partial(jax.jit,
                   static_argnames=("n_dst", "use_kernel", "interpret"))
def frontier_expand_launch(slots, row_dst, x, *, n_dst: int,
                           use_kernel: bool, interpret=None):
    """One expansion as one program: (n_dst, B) counts of the (n_src, B)
    panel x over a device plan's flat slots and `row_dst`. The virtual
    rows are folded per destination by a sorted segment-sum, whose padding
    rows land in segment n_dst and are sliced away.

    A one-column panel (B = 1, a single-root hop) is a flat XLA gather of
    one value per slot, as in `relax_launch`, whatever `use_kernel` says:
    the kernel would fetch a whole 128-lane row per slot for one lane.
    Wider panels take the kernel, which reads whole 128-lane rows, so the
    panel is padded here, on the device; or, without `use_kernel`, the
    jnp K-loop."""
    B = x.shape[1]
    if B == 1:
        v = jnp.where(slots >= 0, x[:, 0][jnp.maximum(slots, 0)], 0.0)
        rows = v.reshape(row_dst.shape[0], -1).sum(axis=1)
        seg = jax.ops.segment_sum(rows, row_dst, num_segments=n_dst + 1,
                                  indices_are_sorted=True)
        return seg[:n_dst, None]
    slots = slots.reshape(row_dst.shape[0], -1)
    if use_kernel:
        xp = jnp.pad(x, ((0, 0), (0, round_up(B, LANES) - B)))
        rows = gather_rows_sum(slots, xp, interpret=interpret,
                               name="frontier_expand")
    else:
        rows = frontier_expand_ref(jnp.maximum(slots, 0), slots >= 0, x)
    seg = jax.ops.segment_sum(rows, row_dst, num_segments=n_dst + 1,
                              indices_are_sorted=True)
    return seg[:n_dst, :B]


@functools.partial(jax.jit, static_argnames=("n_dst",))
def relax_launch(slots, weight, row_dst, x, *, n_dst: int):
    """One min-plus relax as one program: the (n_dst, 1) candidate
    distances of the (n_src, 1) distance panel x (+inf off the frontier)
    over a weighted device plan. Each slot's candidate is x[source] +
    weight, one float32 add; an empty slot's is +inf. The least candidate
    of each virtual row is folded per destination by a sorted segment-min,
    whose padding rows land in segment n_dst and are sliced away; a
    destination no slot reaches reads +inf. The work stays one-dimensional:
    a (·, 1) array would be laid out in 128-lane tiles on the device."""
    cand = jnp.where(slots >= 0, x[:, 0][jnp.maximum(slots, 0)] + weight,
                     jnp.inf)
    rows = cand.reshape(row_dst.shape[0], -1).min(axis=1)
    seg = jax.ops.segment_min(rows, row_dst, num_segments=n_dst + 1,
                              indices_are_sorted=True)
    return seg[:n_dst, None]


def relax_staged(dplan: DevicePlan, x) -> np.ndarray:
    """Launch the relax of a staged distance panel over a weighted device
    plan and read the candidates back."""
    return np.asarray(relax_launch(dplan.slots, dplan.weight, dplan.row_dst,
                                   x, n_dst=dplan.n_dst))


def expand_staged(dplan: DevicePlan, x, use_kernel=None,
                  interpret=None) -> np.ndarray:
    """Launch the expansion of a staged panel over a device plan and read
    the counts back, counting the launch under the path it takes."""
    if use_kernel is None:
        # the Mosaic kernel is the TPU path; off-TPU it would run in
        # interpret mode (a correctness tool, ~1000x slow) — the jit'd ref
        # K-loop is the device-less default
        use_kernel = not default_interpret()
    _M_LAUNCHES.inc(label="gather" if x.shape[1] == 1
                    else "mosaic" if use_kernel else "ref")
    return np.asarray(frontier_expand_launch(
        dplan.slots, dplan.row_dst, x, n_dst=dplan.n_dst,
        use_kernel=bool(use_kernel), interpret=interpret))


def frontier_expand_counts(plan: FrontierPlan, x, use_kernel=None,
                           interpret=None) -> np.ndarray:
    """out (n_dst, B): out[d, j] = Σ_{(s,d) in plan} x[s, j]. With 0/1
    indicator columns this is each destination's count of DISTINCT frontier
    in-neighbors — expand + distinct + aggregate in one launch. float32
    accumulation is integer-exact below 2**24, far above any degree here.
    Uploads the plan on every call; hold an `upload_plan` result and call
    `expand_staged` to launch over a resident one."""
    staged, _ = stage_frontier(x)
    return expand_staged(upload_plan(plan), staged, use_kernel, interpret)
