"""Parallel Sliding Windows (paper §6) — host-faithful and TPU-distributed.

Two execution engines:

1. `psw_sweep_host` / `pagerank_host`: Algorithm 2 verbatim — sweep the P
   vertex intervals; for interval i load the subgraph (in-edges = the whole
   owner partition, out-edges = one contiguous *window* per partition, found
   via the source-sorted order), run the vertex update, write back. This is
   the paper's engine and is what the paper-table benchmarks run.

2. `DeviceGraph` + `edge_centric_sweep`: the TPU adaptation (DESIGN.md §2).
   Each mesh device owns one vertex interval and its destination partition.
   A sweep needs source-vertex state that lives on other devices; the paper's
   Θ(P²) window *seeks* become ONE `all_to_all` of precomputed window rows
   (`mode="psw_windows"`), or an `all_gather` of the full vertex state for
   small state (`mode="dense_gather"`, the paper's §6.1.1 edge-centric model
   that keeps O(V) state in memory).

The pure-jnp "virtual device" path (`axis_name=None`) computes the identical
math with transposes standing in for the collectives, so all of it is
testable on CPU, and it is the only path any caller runs today
(`snapshot()` -> `pagerank_device` on one chip). The collectives under
`shard_map` (a v5e:2x2 mesh) have no caller yet; ROADMAP queue 2 holds
that four-chip analytics path.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .lsm import LSMTree
from .pal import GraphPAL, IntervalMap

GraphLike = Union[GraphPAL, LSMTree]


def _host_partitions(g: GraphLike) -> list:
    """Every physical partition of the store (all LSM levels, the PAL
    partition list, or a pinned ManifestView's partition proxies) —
    duck-typed, no storage-class branching. A `ManifestView`
    (core/manifest.py) satisfies the whole contract this module needs
    (`all_partitions` with stable `dead` refs, `buffers` as frozen staging
    shims, `to_coo`, `intervals`), so out-of-core PSW streaming and
    DeviceGraph compilation run against one epoch-pinned state while the
    writer and maintenance keep going (ISSUE 5)."""
    all_parts = getattr(g, "all_partitions", None)
    return list(all_parts()) if all_parts is not None else list(g.partitions)


__all__ = [
    "DeviceGraph",
    "build_device_graph",
    "edge_centric_sweep",
    "pagerank_device",
    "pagerank_out_of_core",
    "psw_sweep_host",
    "pagerank_host",
    "stream_interval_buckets",
]


# ---------------------------------------------------------------------------
# Host-side PSW (Algorithm 2)
# ---------------------------------------------------------------------------
def psw_sweep_host(
    g: GraphLike,
    update_interval: Callable[..., None],
) -> int:
    """One PSW iteration (paper Alg. 2). For each interval i the callback gets:

        update_interval(i, owner_partition, in_pos, windows)

    where `in_pos` are the dst-sorted edge positions of the owner partition
    and `windows` is a list of (partition, a, b) contiguous out-edge ranges —
    the sliding windows. Returns the number of random accesses a disk would
    have issued (Θ(P²)), for the benchmark I/O-proxy.
    """
    iv = g.intervals
    # PAL: one owner partition per interval; LSM: one owner per level +
    # windows from every partition (duck-typed on the partition layout)
    parts = g.partitions if not hasattr(g, "all_partitions") else None
    seeks = 0
    for i in range(iv.n_partitions):
        lo, hi = iv.interval_range(i)
        if parts is not None:
            owner = parts[i]
            all_parts = parts
        else:
            all_parts = g.all_partitions()
            owner = None
        windows = []
        for part in all_parts:
            a, b = part.window((lo, hi))
            windows.append((part, a, b))
            seeks += 1  # one seek per window (paper §6.1)
        if parts is not None:
            update_interval(i, owner, windows)
            seeks += 1  # owner partition sequential load
        else:
            owners = [
                p for p in all_parts if p.interval[0] <= lo < p.interval[1]
            ]
            update_interval(i, owners, windows)
            seeks += len(owners)
    return seeks


def pagerank_host(g: GraphLike, n_iters: int = 5, damping: float = 0.85) -> np.ndarray:
    """Vertex-centric PageRank with PSW, state on edges (paper §6.1).

    The edge state rank(src)/outdeg(src) lives in a fresh per-partition
    OVERLAY keyed by partition identity — the store's attribute columns are
    never written (they used to be clobbered in place, the ROADMAP-flagged
    wart; tests/test_psw_query.py now pins source columns bitwise). Each
    sweep computes an interval's new ranks from its in-edge state and
    refreshes its out-edge state through the sliding windows. Returns ranks
    indexed by internal ID.
    """
    iv = g.intervals
    n = iv.max_vertices
    # PSW windows only cover partitions, so an LSM store merges its buffers
    # first (read-only analytics use snapshot() instead)
    flush_all = getattr(g, "flush_all", None)
    if flush_all is not None:
        flush_all()
    parts = _host_partitions(g)

    # out-degree (global pass)
    outdeg = np.zeros(n, dtype=np.int64)
    for p in parts:
        if p.n_edges:
            live = np.ones(p.n_edges, bool) if p.dead is None else ~p.dead
            np.add.at(outdeg, p.src[live], 1)
    ranks = np.full(n, 1.0, dtype=np.float64)
    # `parts` (and the window partitions psw_sweep_host hands back) are the
    # store's own stable partition objects, so identity keys are stable for
    # the whole run; `parts` holds them alive
    pr = {}
    for p in parts:
        if p.n_edges:
            pr[id(p)] = ranks[p.src] / np.maximum(outdeg[p.src], 1)
        else:
            pr[id(p)] = np.zeros(0, dtype=np.float64)

    def sweep(i, owner, windows):
        lo, hi = iv.interval_range(i)
        owners = owner if isinstance(owner, list) else [owner]
        acc = np.zeros(hi - lo, dtype=np.float64)
        for p in owners:
            if p.n_edges == 0:
                continue
            live = np.ones(p.n_edges, bool) if p.dead is None else ~p.dead
            sel = live & (p.dst >= lo) & (p.dst < hi)
            np.add.at(acc, p.dst[sel] - lo, pr[id(p)][sel])
        new_rank = (1 - damping) + damping * acc
        ranks[lo:hi] = new_rank
        # refresh out-edge state through the windows
        for p, a, b in windows:
            if b > a:
                s = p.src[a:b]
                pr[id(p)][a:b] = ranks[s] / np.maximum(outdeg[s], 1)

    for _ in range(n_iters):
        psw_sweep_host(g, sweep)
    return ranks


# ---------------------------------------------------------------------------
# Out-of-core PSW (disk tier, paper §6.1): stream buckets, never materialize
# ---------------------------------------------------------------------------
def stream_interval_buckets(g: GraphLike, evict_each: bool = False):
    """Yield `(i, src, dst)` per destination interval, internal IDs,
    canonically (dst, src)-sorted — exactly the rows `build_device_graph`
    would pack, produced ONE interval at a time so the whole edge set is
    never resident.

    Per interval, each owning partition contributes one contiguous slice of
    its dst-sorted permutation (read from mmap if the partition is
    disk-backed), buffers contribute a masked scan, and one small stable
    lexsort canonicalizes the bucket. Chunk concatenation follows the
    `to_coo` order, so the per-bucket sort is bit-identical to the global
    lexsort restricted to the bucket (property-tested). With `evict_each`,
    disk partitions drop their mappings after every bucket, bounding
    resident memory by one bucket + the pinned indexes.
    """
    iv = g.intervals
    parts = _host_partitions(g)
    buffers = getattr(g, "buffers", None) or []
    for i in range(iv.n_partitions):
        lo, hi = iv.interval_range(i)
        chunks_s: list = []
        chunks_d: list = []
        for part in parts:
            plo, phi = part.interval
            if phi <= lo or plo >= hi or part.n_edges == 0:
                continue
            # disk partitions resolve the bucket's perm range against the
            # compressed resident index; RAM partitions use the arrays
            bounds = getattr(part, "dst_ptr_bounds", None)
            res = bounds(lo, hi) if bounds is not None else None
            if res is not None:
                pa, pb = res
            else:
                dv = part.dst_vertices
                a = int(np.searchsorted(dv, lo, side="left"))
                b = int(np.searchsorted(dv, hi, side="left"))
                pa, pb = int(part.dst_ptr[a]), int(part.dst_ptr[b])
            if pb == pa:
                continue
            # perm slice → ascending edge-array positions = to_coo order
            pos = np.sort(np.asarray(part.dst_perm[pa:pb], np.int64))
            if part.dead is not None:
                pos = pos[~part.dead[pos]]
            if pos.size:
                chunks_s.append(np.asarray(part.src[pos], np.int64))
                chunks_d.append(np.asarray(part.dst[pos], np.int64))
        for buf in buffers:
            if len(buf):
                st = buf.staging()
                m = (st.dst >= lo) & (st.dst < hi)
                if m.any():
                    chunks_s.append(st.src[m].astype(np.int64))
                    chunks_d.append(st.dst[m].astype(np.int64))
        if chunks_s:
            s = np.concatenate(chunks_s)
            d = np.concatenate(chunks_d)
            order = np.lexsort((s, d))
            s, d = s[order], d[order]
        else:
            s = np.empty(0, np.int64)
            d = np.empty(0, np.int64)
        yield i, s, d
        if evict_each:
            for part in parts:
                # a swept bucket's pages won't be re-read this pass: hint
                # the kernel to drop them (madvise DONTNEED) so streaming
                # the store doesn't churn hotter data out of the page
                # cache, then unmap
                advise = getattr(part, "advise_dontneed", None)
                if advise is not None:
                    advise()
                ev = getattr(part, "evict", None)
                if ev is not None:
                    ev()


def pagerank_out_of_core(g: GraphLike, n_iters: int = 5,
                         damping: float = 0.85,
                         evict_each: bool = True) -> np.ndarray:
    """Edge-centric PageRank streaming one destination-interval bucket at a
    time from the store — the paper's §6.1.1 model executed out-of-core:
    O(V) vertex state resident, one bucket of edges in flight, everything
    else on disk. Same synchronous iteration as `pagerank_device` (verified
    to agree in the tests). Returns ranks indexed by internal ID."""
    iv = g.intervals
    n = iv.max_vertices
    outdeg = np.zeros(n, np.int64)
    for i, s, d in stream_interval_buckets(g, evict_each=evict_each):
        if s.size:
            outdeg += np.bincount(s, minlength=n)
    ranks = np.ones(n, np.float64)
    inv_deg = 1.0 / np.maximum(outdeg, 1)
    for _ in range(n_iters):
        contrib = ranks * inv_deg
        acc = np.zeros(n, np.float64)
        for i, s, d in stream_interval_buckets(g, evict_each=evict_each):
            if s.size:
                lo, hi = iv.interval_range(i)
                acc[lo:hi] = np.bincount(d - lo, weights=contrib[s],
                                         minlength=hi - lo)
        ranks = (1.0 - damping) + damping * acc
    return ranks


# ---------------------------------------------------------------------------
# Device PSW (TPU adaptation)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class DeviceGraph:
    """Interval-sharded immutable graph arrays (struct-of-arrays, padded).

    Leading axis P = number of intervals = mesh shards. Edges of partition i
    are dst-sorted (so segment ops see monotone ids) and padded to E_max.
    """

    n_partitions: int
    interval_len: int
    n_edges: int
    src: jnp.ndarray        # (P, E) int32 global internal source IDs
    dst_local: jnp.ndarray  # (P, E) int32 local destination offsets
    mask: jnp.ndarray       # (P, E) bool  (False = padding)
    outdeg: jnp.ndarray     # (P, L) int32 out-degree of owned vertices
    # PSW window-exchange plan (None until build_window_plan)
    send_idx: Optional[jnp.ndarray] = None   # (P, P, W) owner-local rows
    edge_owner: Optional[jnp.ndarray] = None  # (P, E) src owner interval
    edge_slot: Optional[jnp.ndarray] = None   # (P, E) row in recv buffer

    @property
    def window_width(self) -> int:
        return 0 if self.send_idx is None else int(self.send_idx.shape[-1])


def build_device_graph(g: GraphLike, with_window_plan: bool = True) -> DeviceGraph:
    iv = g.intervals
    P, L = iv.n_partitions, iv.interval_len
    src_o, dst_o = g.to_coo()
    src = np.asarray(iv.to_internal(src_o))
    dst = np.asarray(iv.to_internal(dst_o))
    # ONE global (dst, src) lexsort canonically orders every bucket at once:
    # sorting by dst groups the destination intervals contiguously and
    # ascending, and within a bucket (dst, src)-order equals the per-bucket
    # sort — bit-identical to sorting each bucket separately, so an
    # LSMTree.snapshot() (which feeds the live staging views through
    # `to_coo`) stays bit-identical to a bulk-built GraphPAL's DeviceGraph.
    order = np.lexsort((src, dst))
    s_sorted, d_sorted = src[order], dst[order]
    counts = np.bincount(d_sorted // L, minlength=P)
    bounds = np.concatenate([[0], np.cumsum(counts)])
    e_max = max(1, int(counts.max(initial=0)))
    # round up to a lane-friendly multiple (TPU tiles are 128-wide)
    e_max = -(-e_max // 128) * 128
    S = np.zeros((P, e_max), np.int32)
    D = np.zeros((P, e_max), np.int32)
    M = np.zeros((P, e_max), bool)
    for i in range(P):
        a, b = int(bounds[i]), int(bounds[i + 1])
        S[i, : b - a] = s_sorted[a:b]
        D[i, : b - a] = d_sorted[a:b] - i * L
        M[i, : b - a] = True
    outdeg = np.zeros(P * L, np.int32)
    np.add.at(outdeg, src, 1)
    dg = DeviceGraph(
        n_partitions=P, interval_len=L, n_edges=int(src.shape[0]),
        src=jnp.asarray(S), dst_local=jnp.asarray(D), mask=jnp.asarray(M),
        outdeg=jnp.asarray(outdeg.reshape(P, L)),
    )
    if with_window_plan:
        _build_window_plan(dg, S, M)
    return dg


def _build_window_plan(dg: DeviceGraph, S: np.ndarray, M: np.ndarray) -> None:
    """Precompute the PSW window exchange: which owner rows each consumer
    needs (unique srcs per (owner, consumer) pair), and per-edge slots into
    the receive buffer. Host-side, immutable alongside the partitions."""
    P, L = dg.n_partitions, dg.interval_len
    uniq: Dict[Tuple[int, int], np.ndarray] = {}
    w_max = 1
    for j in range(P):  # consumer partition j
        s = S[j][M[j]]
        owner = s // L
        for i in range(P):
            u = np.unique(s[owner == i])
            uniq[(i, j)] = u
            w_max = max(w_max, u.shape[0])
    w_max = -(-w_max // 128) * 128
    send_idx = np.zeros((P, P, w_max), np.int32)
    for (i, j), u in uniq.items():
        send_idx[i, j, : u.shape[0]] = (u - i * L).astype(np.int32)
    edge_owner = np.zeros_like(S)
    edge_slot = np.zeros_like(S)
    for j in range(P):
        s = S[j]
        own = s // L
        edge_owner[j] = own
        for i in range(P):
            m = (own == i) & M[j]
            if m.any():
                edge_slot[j][m] = np.searchsorted(uniq[(i, j)], s[m]).astype(np.int32)
    dg.send_idx = jnp.asarray(send_idx)
    dg.edge_owner = jnp.asarray(edge_owner.astype(np.int32))
    dg.edge_slot = jnp.asarray(edge_slot.astype(np.int32))


# -- collectives with a pure-jnp virtual-device fallback ----------------------
def _exchange_windows(x: jnp.ndarray, send_idx: jnp.ndarray,
                      axis_name: Optional[str]) -> jnp.ndarray:
    """PSW window exchange.

    x: (P_local, L, d) owner-local vertex state; send_idx: (P_local, P, W)
    owner-local rows destined for each global consumer. Returns
    recv: (P_local, P, W, d) with recv[b, o] = x_owner_o[send_idx_o[·, this]].
    Under shard_map this is ONE all_to_all — the TPU sliding window; without
    an axis name it is the same math via a transpose (virtual devices).
    """
    send = jnp.take_along_axis(x[:, None], send_idx[..., None], axis=2)
    # send: (P_local owner, P consumer, W, d)
    if axis_name is None:
        return jnp.swapaxes(send, 0, 1)  # (P consumer, P owner, W, d)
    out = jax.lax.all_to_all(send, axis_name, split_axis=1, concat_axis=0)
    # out: (P global owner, P_local consumer, W, d)
    return jnp.swapaxes(out, 0, 1)


def _gather_all(x: jnp.ndarray, axis_name: Optional[str]) -> jnp.ndarray:
    if axis_name is None:
        return x.reshape(-1, *x.shape[2:])
    return jax.lax.all_gather(x, axis_name).reshape(-1, *x.shape[2:])


def edge_centric_sweep_arrays(
    src: jnp.ndarray,          # (Pl, E) global src IDs
    dst_local: jnp.ndarray,    # (Pl, E)
    mask: jnp.ndarray,         # (Pl, E)
    interval_len: int,
    x: jnp.ndarray,            # (Pl, L, d) vertex state (owner-local rows)
    msg_fn: Callable[[jnp.ndarray], jnp.ndarray],
    mode: str = "psw_windows",
    axis_name: Optional[str] = None,
    send_idx: Optional[jnp.ndarray] = None,     # (Pl, P, W)
    edge_owner: Optional[jnp.ndarray] = None,   # (Pl, E)
    edge_slot: Optional[jnp.ndarray] = None,    # (Pl, E)
) -> jnp.ndarray:
    """One edge-centric PSW sweep over per-shard arrays: gather source state
    (via all_gather or the PSW window all_to_all), apply `msg_fn`,
    segment-sum into local destinations. Returns (Pl, L, d') sums."""
    L = interval_len
    if x.ndim == 2:
        x = x[..., None]
    if mode == "dense_gather":
        x_all = _gather_all(x, axis_name)            # (P*L, d)
        src_state = x_all[src]                       # (Pl, E, d)
    elif mode == "psw_windows":
        assert send_idx is not None, "window plan not built"
        recv = _exchange_windows(x, send_idx, axis_name)  # (Pl, P, W, d)
        w = recv.shape[2]
        flat = recv.reshape(recv.shape[0], -1, x.shape[-1])  # (Pl, P*W, d)
        idx = edge_owner * w + edge_slot
        src_state = jnp.take_along_axis(flat, idx[..., None], axis=1)
    else:
        raise ValueError(mode)
    msgs = msg_fn(src_state) * mask[..., None]
    # dst-sorted per partition → segment_sum with monotone ids
    seg = jax.vmap(lambda m, d: jax.ops.segment_sum(m, d, num_segments=L))(
        msgs, dst_local
    )
    return seg


def edge_centric_sweep(
    dg: DeviceGraph,
    x: jnp.ndarray,
    msg_fn: Callable[[jnp.ndarray], jnp.ndarray],
    mode: str = "psw_windows",
    axis_name: Optional[str] = None,
) -> jnp.ndarray:
    """Sweep over the whole DeviceGraph (virtual devices; under a caller's
    shard_map, pass axis_name and per-shard arrays to
    `edge_centric_sweep_arrays`)."""
    return edge_centric_sweep_arrays(
        dg.src, dg.dst_local, dg.mask, dg.interval_len, x, msg_fn,
        mode=mode, axis_name=axis_name, send_idx=dg.send_idx,
        edge_owner=dg.edge_owner, edge_slot=dg.edge_slot,
    )


def pagerank_device(dg: DeviceGraph, n_iters: int = 5, damping: float = 0.85,
                    mode: str = "psw_windows",
                    axis_name: Optional[str] = None) -> jnp.ndarray:
    """PageRank with the device PSW engine. Returns (P, L) ranks."""
    P, L = dg.n_partitions, dg.interval_len
    inv_deg = 1.0 / jnp.maximum(dg.outdeg.astype(jnp.float32), 1.0)

    def body(r, _):
        contrib = (r * inv_deg)[..., None]           # (P, L, 1)
        acc = edge_centric_sweep(dg, contrib, lambda s: s, mode, axis_name)
        r_new = (1.0 - damping) + damping * acc[..., 0]
        return r_new, None

    r0 = jnp.ones((P, L), jnp.float32)
    r, _ = jax.lax.scan(body, r0, None, length=n_iters)
    return r
