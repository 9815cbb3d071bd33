"""Vectorized multi-hop execution over PAL/LSM slabs (DESIGN.md §10).

The query layer's single-hop primitives (engine.py) already beat per-vertex
calls ~40x by batching a whole frontier per slab probe; this module applies
the same set-at-a-time treatment ACROSS hops. A multi-hop query is composed
from four columnar operators — the factorized-list style of Gupta et al.
(PAPERS.md) over the paper's partitioned adjacency lists:

  * `expand`    — one hop for the whole frontier at once: flat
                  (owner, neighbor) pairs straight off the slab scan, no
                  per-vertex regrouping (engine.expand_frontier);
  * `filter`    — `EdgePredicate`, pushed INTO the slab scan: the predicate
                  is evaluated on edge-array positions before the endpoint
                  gather, so non-matching edges never materialize;
  * `semijoin`  — membership of packed keys against a sorted key set
                  (searchsorted), used for per-seed exclusion sets,
                  visited-set subtraction, and edge-set closure probes;
  * `aggregate` — distinct/count reduction of packed (group, value) keys
                  via one sort-unique.

Everything between engine calls is columnar numpy on packed int64 keys
(`group * n_internal_vertices + vertex`); per-hop dedup and frontier
compaction are sort/unique/searchsorted, never a Python loop over vertices.

Dense frontiers additionally get a device path: a `FrontierPlan`
(kernels/frontier_expand) lays the store's deduplicated edge set out as
virtual-row ELL tiles and a Pallas kernel expands indicator columns on the
accelerator; `khop(dense="auto")` picks sparse probes, a bottom-up edge
stream, or the kernel by frontier density (§10.3). Plans, their device
copies and packed edge-key sets are memoized on the engine's `plan_cache()`
keyed by `cache_token()`, so a `ManifestView` shares them across every
reader of one publication and a mutated store can never serve a stale plan.

`sssp` is the weighted traversal: single-source shortest paths over an
edge-weight column, a frontier-driven Bellman-Ford whose dense rounds run
a min-plus relax over a weighted plan on the device (DESIGN.md §10,
"Weighted traversal").

All operators speak only the `StorageEngine` protocol — they run identically
on a live `LSMTree`, a bulk `GraphPAL`, an mmap-backed `GraphDB`, and a
lock-free `ManifestView` epoch snapshot.
"""
from __future__ import annotations

import dataclasses
import operator
from typing import Any, Optional, Sequence

import numpy as np

from . import telemetry
from .engine import EdgeChunk, StorageEngine, _expand_ranges, as_engine

_M_HOPS = telemetry.counter("multihop.hops")
_M_H2D = telemetry.counter("multihop.kernel.h2d_bytes")
_M_PLAN_UPLOADS = telemetry.counter("multihop.kernel.plan_uploads")
_M_RELAX_ROUNDS = telemetry.counter("multihop.relax.rounds")
_M_RELAX_IMPROVED = telemetry.counter("multihop.relax.improved")

GraphLike = Any

__all__ = [
    "EdgePredicate",
    "KHopResult",
    "TwoHopResult",
    "aggregate_counts",
    "compact_frontier",
    "dense_plan",
    "expand",
    "khop",
    "semijoin",
    "sssp",
    "triangle_count",
    "two_hop_counts",
]

# dense plans keep (n_internal_vertices × frontier_block) float32 indicator
# panels resident; past this vertex count the panel alone would dwarf the
# frontier work, so `dense="auto"` never picks the kernel path above it
DENSE_MAX_VERTICES = 4_000_000
# frontiers above this share of |V| are dense (direction-optimizing BFS's)
_DENSE_THRESHOLD = 0.05
_SEED_BLOCK = 128  # dense 2-hop: one kernel feature-tile of seed columns


# ---------------------------------------------------------------------------
# Columnar set primitives (sorted int64 arrays)
# ---------------------------------------------------------------------------
def compact_frontier(ids) -> np.ndarray:
    """Sorted-unique int64 frontier from any raw id batch."""
    return np.unique(np.asarray(ids, np.int64).ravel())


def semijoin(keys: np.ndarray, table: np.ndarray) -> np.ndarray:
    """Membership mask of `keys` (any order) against a SORTED key set —
    one searchsorted, the operator behind exclusion sets and closure
    probes."""
    keys = np.asarray(keys, np.int64)
    if table.shape[0] == 0:
        return np.zeros(keys.shape[0], bool)
    i = np.minimum(np.searchsorted(table, keys), table.shape[0] - 1)
    return table[i] == keys


def aggregate_counts(keys: np.ndarray):
    """Distinct packed keys + multiplicities: one sort-unique, the columnar
    GROUP BY COUNT over (group, value) keys."""
    return np.unique(np.asarray(keys, np.int64), return_counts=True)


def _setdiff_sorted(a: np.ndarray, table: np.ndarray) -> np.ndarray:
    """a (sorted) minus a sorted key set, order preserved."""
    return a[~semijoin(a, table)]


def _union_sorted(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Union of two sorted arrays with b disjoint from a (one merge pass)."""
    if a.shape[0] == 0:
        return b
    if b.shape[0] == 0:
        return a
    return np.insert(a, np.searchsorted(a, b), b)


def _csr_offsets(groups: np.ndarray, n_groups: int) -> np.ndarray:
    offsets = np.zeros(n_groups + 1, np.int64)
    np.cumsum(np.bincount(groups, minlength=n_groups), out=offsets[1:])
    return offsets


# ---------------------------------------------------------------------------
# filter — predicate pushdown into the slab scan
# ---------------------------------------------------------------------------
_OPS = {
    "==": operator.eq, "!=": operator.ne,
    "<": operator.lt, "<=": operator.le,
    ">": operator.gt, ">=": operator.ge,
}


@dataclasses.dataclass(frozen=True)
class EdgePredicate:
    """Edge filter evaluated per slab on edge-array POSITIONS, before any
    endpoint gather — the engine drops failing positions, so filtered-out
    edges never reach the query layer (only their etype/attribute cells are
    read, positionally, per the paper's columnar edge-value layout §4.3).

    `etype` filters the type column; `column`/`op`/`value` filter one named
    attribute column. Both present means AND."""

    etype: Optional[int] = None
    column: Optional[str] = None
    op: str = ">="
    value: float = 0.0

    def mask(self, slab, pos: np.ndarray) -> np.ndarray:
        keep = np.ones(pos.shape[0], bool)
        if self.etype is not None:
            keep &= np.asarray(slab.etype_at(pos)) == self.etype
        if self.column is not None:
            col = np.asarray(slab.column_at(self.column, pos, np.float64))
            keep &= _OPS[self.op](col, self.value)
        return keep


# ---------------------------------------------------------------------------
# expand — one whole-frontier hop
# ---------------------------------------------------------------------------
def expand(g: GraphLike, frontier, direction: str = "out",
           predicate: Optional[EdgePredicate] = None):
    """One hop for the whole frontier: flat (owner index, neighbor) pairs in
    original ids, ungrouped. The multi-hop building block — downstream
    operators re-sort by packed keys anyway, so the per-vertex CSR regroup
    of `out_neighbors_batch` is skipped."""
    return as_engine(g).expand_frontier(frontier, direction, predicate)


def _expand_grouped(eng: StorageEngine, vs: np.ndarray, direction: str,
                    predicate: Optional[EdgePredicate]):
    """CSR regrouping of expand() by owner: (values, offsets) like
    `out_neighbors_batch`, but predicate-capable."""
    owner, nb = eng.expand_frontier(vs, direction, predicate)
    order = np.argsort(owner, kind="stable")
    return nb[order], _csr_offsets(owner, vs.shape[0])


def _expand_stream(eng: StorageEngine, frontier: np.ndarray,
                   direction: str = "out") -> np.ndarray:
    """Bottom-up expansion (Beamer / paper §7.4): stream every live edge
    once and keep endpoints whose other side is in the frontier — O(|E|)
    sequential, cheaper than per-slab probes once the frontier is a large
    fraction of V."""
    iv = eng.intervals
    n = eng.n_internal_vertices
    mask = np.zeros(n + 1, bool)
    mask[np.minimum(frontier, n)] = True
    out = []
    for chunk in eng.edge_chunks():
        key = chunk.src if direction == "out" else chunk.dst
        m = mask[np.asarray(iv.to_original(key), np.int64)]
        if m.any():
            other = chunk.dst if direction == "out" else chunk.src
            out.append(np.asarray(iv.to_original(other[m]), np.int64))
    if not out:
        return np.empty(0, np.int64)
    return np.unique(np.concatenate(out))


# ---------------------------------------------------------------------------
# Dense path: virtual-row ELL plan + Pallas frontier-expansion kernel
# ---------------------------------------------------------------------------
_PLAN_KEY = "multihop:dense_plan"
_DEVICE_PLAN_KEY = "multihop:dense_plan_device"
_WPLAN_KEY = "multihop:weighted_plan"
_DEVICE_WPLAN_KEY = "multihop:weighted_plan_device"
_EDGE_KEYS = "multihop:edge_keys"
_NO_EDGES = EdgeChunk(np.empty(0, np.int64), np.empty(0, np.int64),
                      np.empty(0, np.float32))


def _memoized(eng: StorageEngine, name, builder):
    token = eng.cache_token()
    if token is None:
        return builder()
    cache = eng.plan_cache()
    key = (name, token)
    val = cache.get(key)
    if val is None:
        # a new token means the store moved on: no reader can ask for an
        # older token's entry again, so drop it (a device plan holds HBM)
        for old in [k for k in list(cache) if isinstance(k, tuple)
                    and len(k) == 2 and k[0] == name]:
            cache.pop(old, None)
        val = cache[key] = builder()
    return val


def _edge_keys_internal(eng: StorageEngine) -> np.ndarray:
    """Sorted-unique packed (src * M + dst) keys of the live edge set,
    internal ids — the closure table for semijoin probes (triangles) and
    the input to dense plans. Memoized per store content."""
    def build():
        M = np.int64(eng.n_internal_vertices)
        parts = [np.asarray(c.src, np.int64) * M + np.asarray(c.dst, np.int64)
                 for c in eng.edge_chunks()]
        if not parts:
            return np.empty(0, np.int64)
        return np.unique(np.concatenate(parts))
    return _memoized(eng, _EDGE_KEYS, build)


def _plan_name(direction: str, weight: Optional[str] = None):
    """The memo name of a host plan: `(_PLAN_KEY, direction)`, or for the
    weighted plan over column `weight`, `(_WPLAN_KEY, weight, direction)`."""
    if weight is None:
        return (_PLAN_KEY, direction)
    return (_WPLAN_KEY, weight, direction)


def dense_plan(g: GraphLike, direction: str = "out",
               weight: Optional[str] = None):
    """Build (or fetch the memoized) frontier-expansion plan: the store's
    deduplicated edge set as destination-grouped virtual-row ELL tiles
    (kernels/frontier_expand). `direction="in"` builds the transposed
    plan. With `weight`, the weighted plan `sssp` relaxes over: the same
    layout with each distinct edge's least value of that attribute column
    per slot, in float32."""
    eng = as_engine(g)
    M = eng.n_internal_vertices
    if M > DENSE_MAX_VERTICES:
        raise ValueError(
            f"dense plan disabled above {DENSE_MAX_VERTICES} internal "
            f"vertices (store has {M}): the indicator panel would dominate")

    def build():
        from ..kernels.frontier_expand import build_frontier_plan
        if weight is not None:
            chunks = list(eng.edge_chunks(weight)) or [_NO_EDGES]
            s, d, w = (np.concatenate(parts) for parts in
                       zip(*[(c.src, c.dst, c.column) for c in chunks]))
            w = _weights(w, weight)
        else:
            keys = _edge_keys_internal(eng)
            s, d, w = keys // M, keys % M, None
        if direction == "out":
            return build_frontier_plan(s, d, n_src=M, n_dst=M, weight=w)
        return build_frontier_plan(d, s, n_src=M, n_dst=M, weight=w)

    return _memoized(eng, _plan_name(direction, weight), build)


def _plan_cached(eng: StorageEngine, name) -> bool:
    token = eng.cache_token()
    return token is not None and (name, token) in eng.plan_cache()


def _device_plan(eng: StorageEngine, plan, name):
    """`plan` on the device: uploaded at the first kernel launch over it
    and memoized beside it under the device name `name`, under the same
    token (call under the `multihop.kernel.prep` span)."""
    def build():
        from ..kernels.frontier_expand import upload_plan
        dplan = upload_plan(plan)
        _M_H2D.inc(dplan.nbytes)
        _M_PLAN_UPLOADS.inc()
        return dplan
    return _memoized(eng, name, build)


def _stage(x):
    """Hand one launch's indicator panel to the device, counting the bytes
    (call under the `multihop.kernel.prep` span, with the panel's build)."""
    from ..kernels.frontier_expand import stage_frontier
    staged, nbytes = stage_frontier(x)
    _M_H2D.inc(nbytes)
    return staged


def _launch(dplan, staged) -> np.ndarray:
    """Run one staged launch and read its counts back."""
    from ..kernels.frontier_expand import expand_staged
    with telemetry.span("multihop.kernel.wait"):
        return expand_staged(dplan, staged)


def _expand_dense(eng: StorageEngine, frontier: np.ndarray,
                  direction: str) -> np.ndarray:
    """Kernel hop: scatter the frontier into a one-column indicator, run the
    frontier-expansion kernel, read back the touched destinations."""
    plan = dense_plan(eng, direction)
    iv = eng.intervals
    with telemetry.span("multihop.kernel.prep"):
        dplan = _device_plan(eng, plan, (_DEVICE_PLAN_KEY, direction))
        x = np.zeros((eng.n_internal_vertices, 1), np.float32)
        x[np.asarray(iv.to_internal(frontier), np.int64), 0] = 1.0
        staged = _stage(x)
    counts = _launch(dplan, staged)
    nxt = np.flatnonzero(counts[:, 0] > 0)
    return np.sort(np.asarray(iv.to_original(nxt), np.int64))


def _hop_mode(eng: StorageEngine, frontier_size: int, dense: str,
              threshold: float, predicate,
              plan=_plan_name("out")) -> str:
    """The density heuristic (§10.3). Predicates force the sparse path —
    pushdown only exists in the slab scan. Below `threshold · |V|` the
    frontier is sparse: per-slab searchsorted probes touch only adjacent
    edges. Above it, every edge is worth a look: use the device when the
    plan the round needs (`plan`, a `_plan_name`) is already memoized for
    this store content (repeated analytics amortized it) and the store is
    small enough to hold indicator panels; otherwise a one-shot bottom-up
    edge stream, which needs no prep."""
    if predicate is not None or dense == "never":
        return "sparse"
    supported = getattr(eng, "supported_hop_modes",
                        ("sparse", "stream", "kernel"))
    if dense in ("kernel", "stream"):
        # an engine that cannot serve the requested mode (the sharded
        # scatter/gather engine only probes — ISSUE 8) clamps to sparse
        # rather than erroring: mode is an execution hint, not semantics
        return dense if dense in supported else "sparse"
    if frontier_size <= threshold * eng.n_internal_vertices:
        return "sparse"
    if ("kernel" in supported and _plan_cached(eng, plan)
            and eng.n_internal_vertices <= DENSE_MAX_VERTICES):
        return "kernel"
    return "stream" if "stream" in supported else "sparse"


# ---------------------------------------------------------------------------
# k-hop expansion (BFS levels) with columnar visited-set management
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class KHopResult:
    """levels[d] = vertices first reached at depth d (sorted); levels[0] is
    the compacted seed set. visited = sorted union of all levels."""

    levels: list
    visited: np.ndarray

    def depth_of(self, v: int) -> Optional[int]:
        for d, lv in enumerate(self.levels):
            i = np.searchsorted(lv, v)
            if i < lv.shape[0] and lv[i] == v:
                return d
        return None


def khop(g: GraphLike, seeds, k: int, direction: str = "out",
         predicate: Optional[EdgePredicate] = None, dense: str = "auto",
         dense_threshold: float = _DENSE_THRESHOLD) -> KHopResult:
    """Whole-frontier k-hop expansion. Each hop expands the previous level
    in ONE engine call (or one kernel launch / edge stream, per the density
    heuristic), then subtracts the visited set and merges — all columnar.
    With `predicate`, only edges passing the pushed-down filter are
    traversed (attribute-filtered traversal)."""
    eng = as_engine(g)
    frontier = compact_frontier(seeds)
    visited = frontier
    levels = [frontier]
    for hop in range(k):
        if frontier.shape[0] == 0:
            break
        mode = _hop_mode(eng, frontier.shape[0], dense, dense_threshold,
                         predicate)
        with telemetry.span("multihop.hop", hop=hop, mode=mode,
                            frontier=int(frontier.shape[0])) as sp:
            if mode == "kernel":
                nxt = _expand_dense(eng, frontier, direction)
            elif mode == "stream":
                nxt = _expand_stream(eng, frontier, direction)
            else:
                with telemetry.span("multihop.probe"):
                    _, nb = eng.expand_frontier(frontier, direction,
                                                predicate)
                    nxt = np.unique(nb)
            with telemetry.span("multihop.merge"):
                fresh = _setdiff_sorted(nxt, visited)
                visited = _union_sorted(visited, fresh)
            sp.tag(fresh=int(fresh.shape[0]))
            _M_HOPS.inc(label=mode)
        if fresh.shape[0] == 0:
            break
        levels.append(fresh)
        frontier = fresh
    return KHopResult(levels, visited)


# ---------------------------------------------------------------------------
# Single-source shortest paths: frontier Bellman-Ford with min-plus rounds
# ---------------------------------------------------------------------------
def _weights(w: np.ndarray, name: str) -> np.ndarray:
    """An edge-weight column as the float32 the relax adds; a negative
    weight is refused (it could lower distances forever)."""
    w = np.asarray(w, np.float32)
    if w.size and bool((w < 0).any()):
        raise ValueError(f"edge column {name!r} holds a negative weight")
    return w


def _relax_sparse(eng: StorageEngine, dist: np.ndarray,
                  frontier: np.ndarray, weight: str,
                  direction: str) -> np.ndarray:
    """Host round: the frontier's adjacent edges and their weights off the
    slab probes, one float32 add each, the least per destination (under
    the `multihop.probe` span, as a sparse `khop` hop)."""
    iv = eng.intervals
    with telemetry.span("multihop.probe"):
        owner, nb, w = eng.expand_frontier_column(
            np.asarray(iv.to_original(frontier), np.int64), weight,
            direction)
        cand = np.full(dist.shape[0], np.inf, np.float32)
        np.minimum.at(cand, np.asarray(iv.to_internal(nb), np.int64),
                      dist[frontier][owner] + _weights(w, weight))
    return cand


def _relax_stream(eng: StorageEngine, dist: np.ndarray,
                  frontier: np.ndarray, weight: str,
                  direction: str) -> np.ndarray:
    """Bottom-up round: every live edge and its weight streamed once, the
    edges leaving the frontier relaxed."""
    x = np.full(dist.shape[0], np.inf, np.float32)
    x[frontier] = dist[frontier]
    cand = np.full(dist.shape[0], np.inf, np.float32)
    for chunk in eng.edge_chunks(weight):
        key, other = ((chunk.src, chunk.dst) if direction == "out"
                      else (chunk.dst, chunk.src))
        c = x[key] + _weights(chunk.column, weight)
        m = c < np.inf
        np.minimum.at(cand, other[m], c[m])
    return cand


def _relax_dense(eng: StorageEngine, dist: np.ndarray,
                 frontier: np.ndarray, weight: str,
                 direction: str) -> np.ndarray:
    """Device round: the frontier's distances (+inf elsewhere) as a
    one-column panel, one min-plus relax launch over the resident weighted
    plan, the candidates read back."""
    from ..kernels.frontier_expand import relax_staged
    plan = dense_plan(eng, direction, weight)
    with telemetry.span("multihop.kernel.prep"):
        dplan = _device_plan(eng, plan, (_DEVICE_WPLAN_KEY, weight,
                                         direction))
        x = np.full((dist.shape[0], 1), np.inf, np.float32)
        x[frontier, 0] = dist[frontier]
        staged = _stage(x)
    with telemetry.span("multihop.kernel.wait"):
        return relax_staged(dplan, staged)[:, 0]


_RELAX = {"kernel": _relax_dense, "stream": _relax_stream,
          "sparse": _relax_sparse}


def sssp(g: GraphLike, root: int, weight: str = "w",
         direction: str = "out", dense: str = "auto") -> np.ndarray:
    """Single-source shortest paths from `root` over the non-negative edge
    column `weight`: float32 distances indexed by original vertex id, +inf
    where a vertex is unreached (an edge without the column weighs 0).

    Frontier-driven Bellman-Ford: each round relaxes the edges leaving the
    vertices whose distance fell in the round before, with one float32
    add per edge and a min per destination, until no distance falls. The
    round's mode follows `khop`'s density rule (`_hop_mode`) against the
    weighted plan: sparse rounds probe the slabs with the column on the
    host, dense ones run the min-plus relax over the resident weighted
    plan on the device (or stream every edge where none is held). Since
    weights are non-negative and fl(a + w) is monotone in a and w, the
    answer is the least float32 fixed point, the least float32 left-to-
    right path sum, whatever the order of relaxation: every mode gives
    the same bits."""
    eng = as_engine(g)
    iv = eng.intervals
    M = eng.n_internal_vertices
    plan = _plan_name(direction, weight)
    dist = np.full(M, np.inf, np.float32)
    frontier = np.array([iv.to_internal_scalar(int(root))], np.int64)
    dist[frontier] = 0.0
    rnd = 0
    while frontier.shape[0]:
        mode = _hop_mode(eng, frontier.shape[0], dense, _DENSE_THRESHOLD,
                         None, plan)
        with telemetry.span("multihop.relax", round=rnd, mode=mode,
                            frontier=int(frontier.shape[0])) as sp:
            cand = _RELAX[mode](eng, dist, frontier, weight, direction)
            frontier = np.flatnonzero(cand < dist)
            dist[frontier] = cand[frontier]
            sp.tag(improved=int(frontier.shape[0]))
        _M_RELAX_ROUNDS.inc(label=mode)
        _M_RELAX_IMPROVED.inc(int(frontier.shape[0]))
        rnd += 1
    return dist[np.asarray(iv.to_internal(np.arange(M)), np.int64)]


# ---------------------------------------------------------------------------
# 2-hop intersection: friends-of-friends with counts
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class TwoHopResult:
    """CSR per seed: ids[offsets[i]:offsets[i+1]] are seed i's two-hop
    vertices (sorted), counts[...] the number of DISTINCT middle friends
    through which each is reachable — the paper's FoF answer (§8.4) plus
    the intersection cardinality."""

    seeds: np.ndarray
    offsets: np.ndarray
    ids: np.ndarray
    counts: np.ndarray

    def slice_of(self, i: int) -> slice:
        return slice(int(self.offsets[i]), int(self.offsets[i + 1]))


def _empty_two_hop(seeds: np.ndarray) -> TwoHopResult:
    return TwoHopResult(seeds, np.zeros(seeds.shape[0] + 1, np.int64),
                        np.empty(0, np.int64), np.empty(0, np.int64))


def two_hop_counts(g: GraphLike, seeds, direction: str = "out",
                   max_friends: Optional[int] = None, exclude: bool = True,
                   predicate: Optional[EdgePredicate] = None,
                   dense: str = "never") -> TwoHopResult:
    """Friends-of-friends with counts for a whole seed batch: expand twice,
    dedup (seed, friend) and (path, target) pairs on packed keys, aggregate
    distinct middles per (seed, target), and semijoin away the seeds' own
    friend sets (`exclude`, the paper's selectOut filter).

    `max_friends` truncates each seed's friend list to its first
    `max_friends` in sorted id order — bitwise the per-seed semantics of
    `query.friends_of_friends`. `dense="kernel"` routes both hops through
    the Pallas frontier-expansion plan (requires no predicate/truncation);
    results are bitwise-identical to the sparse path (§10.4)."""
    n_seeds = int(np.asarray(seeds).size)
    with telemetry.span("multihop.two_hop", seeds=n_seeds, dense=dense):
        return _two_hop_counts(g, seeds, direction, max_friends, exclude,
                               predicate, dense)


def _two_hop_counts(g, seeds, direction, max_friends, exclude, predicate,
                    dense) -> TwoHopResult:
    eng = as_engine(g)
    seeds = np.asarray(seeds, np.int64).ravel()
    S = seeds.shape[0]
    if S == 0:
        return _empty_two_hop(seeds)
    if dense == "kernel":
        if predicate is not None or max_friends is not None:
            raise ValueError("dense 2-hop supports neither predicates nor "
                             "max_friends truncation")
        return _two_hop_dense(eng, seeds, direction, exclude)
    M = np.int64(eng.n_internal_vertices)

    # hop 1 + aggregate: distinct (seed, friend), sorted by packed key
    owner, nb = eng.expand_frontier(seeds, direction, predicate)
    fk = np.unique(owner * M + nb)
    s_idx, fr = fk // M, fk % M
    if max_friends is not None:
        cnt = np.bincount(s_idx, minlength=S)
        starts = np.repeat(np.cumsum(cnt) - cnt, cnt)
        keep = np.arange(fk.shape[0]) - starts < max_friends
        fk, s_idx, fr = fk[keep], s_idx[keep], fr[keep]
    if fr.shape[0] == 0:
        return _empty_two_hop(seeds)

    # hop 2 on the UNIQUE friend set, joined back to (seed, friend) pairs
    uf = np.unique(fr)
    vals, offs = _expand_grouped(eng, uf, direction, predicate)
    fpos = np.searchsorted(uf, fr)
    pos, pair = _expand_ranges(offs[fpos], offs[fpos + 1],
                               np.arange(fr.shape[0], dtype=np.int64))
    # aggregate twice: distinct (path, target) collapses multi-edges, then
    # distinct-middle counts per (seed, target)
    pk = np.unique(pair * M + vals[pos])
    sk, counts = aggregate_counts(s_idx[pk // M] * M + pk % M)
    if exclude:
        selfk = np.arange(S, dtype=np.int64) * M + seeds
        keep = ~(semijoin(sk, fk) | semijoin(sk, selfk))
        sk, counts = sk[keep], counts[keep]
    return TwoHopResult(seeds, _csr_offsets(sk // M, S), sk % M,
                        counts.astype(np.int64))


def _two_hop_dense(eng: StorageEngine, seeds: np.ndarray, direction: str,
                   exclude: bool) -> TwoHopResult:
    """Kernel 2-hop: seeds become indicator columns; hop 1 is binarized to
    the distinct-friend panel, hop 2's accumulation IS the distinct-middle
    count (float32 counts are integer-exact far below 2**24). Seeds stream
    through in `_SEED_BLOCK`-column panels — one kernel feature tile."""
    plan = dense_plan(eng, direction)
    iv = eng.intervals
    M = np.int64(eng.n_internal_vertices)
    S = seeds.shape[0]
    si = np.asarray(iv.to_internal(seeds), np.int64)
    sk_parts, cnt_parts, fk_parts = [], [], []
    for c0 in range(0, S, _SEED_BLOCK):
        blk = si[c0:c0 + _SEED_BLOCK]
        with telemetry.span("multihop.kernel.prep"):
            dplan = _device_plan(eng, plan, (_DEVICE_PLAN_KEY, direction))
            x = np.zeros((int(M), blk.shape[0]), np.float32)
            x[blk, np.arange(blk.shape[0])] = 1.0
            staged = _stage(x)
        c1 = _launch(dplan, staged)                     # (M, B) 0/1: edges
        with telemetry.span("multihop.kernel.prep"):
            staged = _stage((c1 > 0).astype(np.float32))
        c2 = _launch(dplan, staged)
        w, j = np.nonzero(c2)
        cnt_parts.append(np.rint(c2[w, j]).astype(np.int64))
        wo = np.asarray(iv.to_original(w), np.int64)
        sk_parts.append((c0 + j) * M + wo)
        if exclude:
            fw, fj = np.nonzero(c1)
            fk_parts.append((c0 + fj) * M
                            + np.asarray(iv.to_original(fw), np.int64))
    if not sk_parts:
        return _empty_two_hop(seeds)
    sk = np.concatenate(sk_parts)
    counts = np.concatenate(cnt_parts)
    if exclude:
        fk = np.sort(np.concatenate(fk_parts)) if fk_parts \
            else np.empty(0, np.int64)
        selfk = np.arange(S, dtype=np.int64) * M + seeds
        keep = ~(semijoin(sk, fk) | semijoin(sk, selfk))
        sk, counts = sk[keep], counts[keep]
    order = np.argsort(sk)  # (seed, target-id) order, matching sparse
    sk, counts = sk[order], counts[order]
    return TwoHopResult(seeds, _csr_offsets(sk // M, S), sk % M, counts)


# ---------------------------------------------------------------------------
# Triangle counting: wedge cross-product + edge-set semijoin
# ---------------------------------------------------------------------------
def triangle_count(g: GraphLike, middles=None,
                   wedge_budget: int = 4_000_000) -> int:
    """Directed closed-wedge count: |{(u, v, w) : u→v, v→w, u→w}| over the
    DISTINCT edge set, summed per middle vertex v. Per chunk of middles the
    (distinct in-nbr × distinct out-nbr) wedge cross-product is built
    columnar and semijoined against the packed edge-key set; `wedge_budget`
    bounds resident wedges (chunks are sized by the degree product
    estimate, fetched via the no-gather degree batch)."""
    eng = as_engine(g)
    iv = eng.intervals
    M = np.int64(eng.n_internal_vertices)
    ekeys = _edge_keys_internal(eng)
    if ekeys.shape[0] == 0:
        return 0
    if middles is None:
        # only a vertex with both in- and out-edges closes a wedge
        mids_i = np.intersect1d(np.unique(ekeys // M), np.unique(ekeys % M),
                                assume_unique=True)
        mids = np.sort(np.asarray(iv.to_original(mids_i), np.int64))
    else:
        mids = compact_frontier(middles)
    if mids.shape[0] == 0:
        return 0
    est = eng.in_degree_batch(mids) * eng.out_degree_batch(mids)
    nz = est > 0
    mids, est = mids[nz], est[nz]
    total = 0
    cum = np.cumsum(est)
    start = 0
    while start < mids.shape[0]:
        limit = (cum[start - 1] if start else 0) + wedge_budget
        stop = max(int(np.searchsorted(cum, limit, side="right")), start + 1)
        total += _triangle_chunk(eng, mids[start:stop], ekeys, M)
        start = stop
    return int(total)


def _triangle_chunk(eng: StorageEngine, mids: np.ndarray, ekeys: np.ndarray,
                    M: np.int64) -> int:
    iv = eng.intervals
    o_in, u = eng.expand_frontier(mids, "in")
    if u.shape[0] == 0:
        return 0
    o_out, w = eng.expand_frontier(mids, "out")
    if w.shape[0] == 0:
        return 0
    # aggregate to distinct (middle, neighbor), internal ids for the probe
    ik = np.unique(o_in * M + np.asarray(iv.to_internal(u), np.int64))
    ok = np.unique(o_out * M + np.asarray(iv.to_internal(w), np.int64))
    io_, iu = ik // M, ik % M
    oo_, ow = ok // M, ok % M
    ooff = _csr_offsets(oo_, mids.shape[0])
    # expand: every in-entry against its middle's whole out-range
    pos, ie = _expand_ranges(ooff[io_], ooff[io_ + 1],
                             np.arange(io_.shape[0], dtype=np.int64))
    if pos.shape[0] == 0:
        return 0
    # semijoin the wedges against the edge-set closure table
    return int(semijoin(iu[ie] * M + ow[pos], ekeys).sum())
