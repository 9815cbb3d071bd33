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
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..common import default_interpret, round_up
from .frontier_expand import frontier_expand_pallas
from .ref import frontier_expand_ref

__all__ = ["FrontierPlan", "StagedFrontier", "build_frontier_plan",
           "expand_staged", "frontier_expand_counts", "stage_frontier"]


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


def build_frontier_plan(src, dst, n_src: int, n_dst: int,
                        k_slots: int = 32) -> FrontierPlan:
    """Host-side, fully vectorized: dedup + destination-major sort via one
    packed-key unique, ranks within destination groups via run-length
    arithmetic, then one scatter into the (R, K) slot grid."""
    src = np.asarray(src, np.int64).ravel()
    dst = np.asarray(dst, np.int64).ravel()
    keys = np.unique(dst * np.int64(n_src) + src)
    E = keys.shape[0]
    if E == 0:
        return FrontierPlan(np.zeros((128, k_slots), np.int32),
                            np.zeros((128, k_slots), bool),
                            np.full(128, n_dst, np.int32),
                            int(n_src), int(n_dst), 0, k_slots)
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
    return FrontierPlan(idx, mask, row_dst, int(n_src), int(n_dst), int(E),
                        k_slots)


@dataclasses.dataclass(frozen=True)
class StagedFrontier:
    """One launch's operands on the device (`stage_frontier`)."""

    idx: jax.Array        # the plan's (R, K) slots
    mask: jax.Array
    row_dst: jax.Array
    x: jax.Array          # (n_src, Bp) indicator panel, padded to lanes
    n_cols: int           # B, the panel's useful columns


def stage_frontier(plan: FrontierPlan, x):
    """First half of `frontier_expand_counts`: pad the (n_src, B) panel to
    whole 128-lane tiles and hand it and the plan to the device, waiting
    until every upload has landed. Returns the staged operands and the
    bytes of the host arrays handed over."""
    x = np.ascontiguousarray(np.asarray(x, np.float32))
    B = x.shape[1]
    host = (plan.idx, plan.mask, plan.row_dst,
            np.pad(x, ((0, 0), (0, round_up(B, 128) - B))))
    dev = jax.block_until_ready([jnp.asarray(a) for a in host])
    return StagedFrontier(*dev, n_cols=B), sum(a.nbytes for a in host)


def expand_staged(plan: FrontierPlan, staged: StagedFrontier,
                  use_kernel=None, interpret=None) -> np.ndarray:
    """Second half of `frontier_expand_counts`: launch the expansion and
    the segment-sum over staged operands and read the counts back."""
    if use_kernel is None:
        # the Mosaic kernel is the TPU path; off-TPU it would run in
        # interpret mode (a correctness tool, ~1000x slow) — the jit'd ref
        # K-loop is the device-less default
        use_kernel = not default_interpret()
    if use_kernel:
        rows = frontier_expand_pallas(staged.idx, staged.mask, staged.x,
                                      interpret=interpret)
    else:
        rows = frontier_expand_ref(staged.idx, staged.mask, staged.x)
    # virtual rows are destination-sorted; padding rows land in segment
    # n_dst and are sliced away
    seg = jax.ops.segment_sum(rows, staged.row_dst,
                              num_segments=plan.n_dst + 1,
                              indices_are_sorted=True)
    return np.asarray(seg[:plan.n_dst, :staged.n_cols])


def frontier_expand_counts(plan: FrontierPlan, x, use_kernel=None,
                           interpret=None) -> np.ndarray:
    """out (n_dst, B): out[d, j] = Σ_{(s,d) in plan} x[s, j]. With 0/1
    indicator columns this is each destination's count of DISTINCT frontier
    in-neighbors — expand + distinct + aggregate in one launch. float32
    accumulation is integer-exact below 2**24, far above any degree here."""
    staged, _ = stage_frontier(plan, x)
    return expand_staged(plan, staged, use_kernel, interpret)
