"""The analytics the benchmark cells run, on every store that serves them.

One weighted Graph500 Kronecker graph (the SSSP cell's generator and
weights at scale 11, stored both ways) is held by six stores: a bulk
`GraphPAL`; a live `LSMTree` with merged levels, live buffers, deletes and
a column update; such a tree's pinned `read_view()`, outlived by further
writes and merges; a `GraphDB` closed and reopened; a durable `ServiceDB`
with the cells' store layout cut by the graph's 2^9 (bench/data.py's
load, checkpoint, then `read_view()`, as the cells read); and
`Snapshot.open` of a session pinned before further writes. On each,
`khop`, `sssp`, `two_hop_counts`, `triangle_count`, `friends_of_friends`
and `pagerank_device` run in every mode that serves them, and each answer
is checked against a plain numpy reference built from the edges the store
holds: bit for bit, except PageRank, which is held to the per-vertex
float32 bound of a float64 Jacobi iteration.
"""
import os
import sys

import numpy as np
import pytest
import scipy.sparse as sp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from bench.gen import kronecker_edges  # noqa: E402
from bench.gen.weights import edge_weights  # noqa: E402
from repro.core import (GraphDB, GraphPAL, IntervalMap, LSMTree,  # noqa: E402
                        ServiceDB, Snapshot, build_device_graph,
                        friends_of_friends, khop, pagerank_device, sssp,
                        triangle_count, two_hop_counts)

SCALE = 11
N = 1 << SCALE
# graph500w-s20's store and load batch, cut by 2^(20 - SCALE) as its graph
STORE = dict(n_partitions=16, n_levels=2, branching=4, buffer_cap=2048,
             max_partition_edges=16384, column_dtypes={"w": np.float32})
LOAD_BATCH = 8192
PAGERANK_ITERS = 5

STORES = ["pal", "lsm", "lsm_view", "graphdb_reopened", "service_view",
          "snapshot"]


class Store:
    """A store under test and the (src, dst, w) edges it holds."""

    def __init__(self, graph, src, dst, w, device_graph):
        self.graph = graph
        self.src, self.dst, self.w = src, dst, w
        self.device_graph = device_graph
        self.root = int(np.argmax(np.bincount(src, minlength=N) > 2))
        rng = np.random.default_rng(SCALE)
        has_out = np.flatnonzero(np.bincount(src, minlength=N))
        self.seeds = rng.choice(has_out, 128, replace=False)

    def adjacency(self):
        """Distinct-edge adjacency over original ids, int64 CSR."""
        a = sp.csr_matrix((np.ones(self.src.shape[0], np.int64),
                           (self.src, self.dst)), shape=(N, N))
        a.data[:] = 1  # parallel edges count once
        return a


class Truth:
    """The edge list a store should hold, mutated beside it."""

    def __init__(self, seed):
        u, v = kronecker_edges(SCALE, 16, structure_seed=500, seed=seed)
        w = edge_weights(seed, u.shape[0])
        self.src = np.concatenate([u, v])
        self.dst = np.concatenate([v, u])
        self.w = np.concatenate([w, w])

    def batches(self, lo=0, hi=None):
        hi = self.src.shape[0] if hi is None else hi
        for i in range(lo, hi, LOAD_BATCH):
            j = min(i + LOAD_BATCH, hi)
            yield self.src[i:j], self.dst[i:j], {"w": self.w[i:j]}

    def delete(self, store, n, rng):
        """Delete `n` distinct present edges (every parallel copy)."""
        picks = rng.choice(self.src.shape[0], n, replace=False)
        for s, d in {(int(self.src[i]), int(self.dst[i])) for i in picks}:
            assert store.delete_edge(s, d)
            keep = ~((self.src == s) & (self.dst == d))
            self.src, self.dst, self.w = (self.src[keep], self.dst[keep],
                                          self.w[keep])

    def update(self, store, value):
        """Set column w of one edge that has no parallel copy."""
        _, first, count = np.unique(self.src * N + self.dst,
                                    return_index=True, return_counts=True)
        single = first[count == 1]
        i = int(single[single.shape[0] // 3])
        assert store.update_edge_column(int(self.src[i]), int(self.dst[i]),
                                        "w", value)
        self.w = self.w.copy()
        self.w[i] = value

    def mutate(self, store, rng, value=0.0):
        self.delete(store, 40, rng)
        self.update(store, np.float32(value))

    def insert(self, store, lo=0, hi=None):
        for s, d, c in self.batches(lo, hi):
            store.insert_edges(s, d, columns=c)

    def insert_again(self, store, n):
        """Insert the first `n` edges once more, as parallel edges."""
        self.insert(store, 0, n)
        self.src, self.dst, self.w = (np.concatenate([a, a[:n]]) for a in
                                      (self.src, self.dst, self.w))

    def frozen(self):
        return self.src.copy(), self.dst.copy(), self.w.copy()


def _live_tree(truth, rng):
    """An LSMTree loaded in batches (flushes and merges), its last batch
    left in the buffers, then deletes and a column update."""
    t = LSMTree(IntervalMap.for_capacity(N - 1, STORE["n_partitions"]),
                **{k: v for k, v in STORE.items() if k != "n_partitions"})
    tail = truth.src.shape[0] - STORE["buffer_cap"] // 2
    truth.insert(t, 0, tail)
    truth.insert(t, tail)
    assert t.total_buffered() > 0
    truth.mutate(t, rng)
    return t


@pytest.fixture(scope="module")
def pal():
    truth = Truth(seed=1)
    g = GraphPAL.from_edges(truth.src, truth.dst, n_partitions=16,
                            max_id=N - 1, columns={"w": truth.w})
    return Store(g, *truth.frozen(), lambda: build_device_graph(g))


@pytest.fixture(scope="module")
def lsm():
    truth = Truth(seed=2)
    t = _live_tree(truth, np.random.default_rng(2))
    return Store(t, *truth.frozen(), t.snapshot)


@pytest.fixture(scope="module")
def lsm_view():
    truth = Truth(seed=3)
    rng = np.random.default_rng(3)
    t = _live_tree(truth, rng)
    view = t.read_view()
    pinned = truth.frozen()
    # outlive the pin: more writes, deletes, an update and merges
    truth.insert_again(t, LOAD_BATCH)
    truth.mutate(t, rng, 3.5)
    t.flush_all()
    assert t.n_edges == truth.src.shape[0] != pinned[0].shape[0]
    yield Store(view, *pinned, view.snapshot)
    view.release()


@pytest.fixture(scope="module")
def graphdb_reopened(tmp_path_factory):
    truth = Truth(seed=4)
    d = str(tmp_path_factory.mktemp("graphdb") / "db")
    db = GraphDB.create(d, max_id=N - 1, **STORE)
    truth.insert(db)
    truth.mutate(db, np.random.default_rng(4))
    db.close()
    db = GraphDB.open(d)
    yield Store(db, *truth.frozen(), db.snapshot)
    db.close()


@pytest.fixture(scope="module")
def service_view(tmp_path_factory):
    truth = Truth(seed=5)
    d = str(tmp_path_factory.mktemp("service") / "store")
    svc = ServiceDB.create(d, max_id=N - 1, **STORE)
    truth.insert(svc)
    svc.checkpoint()
    view = svc.read_view()
    yield Store(view, *truth.frozen(), view.snapshot)
    view.release()
    svc.close()


@pytest.fixture(scope="module")
def snapshot(tmp_path_factory):
    truth = Truth(seed=6)
    rng = np.random.default_rng(6)
    d = str(tmp_path_factory.mktemp("session") / "store")
    svc = ServiceDB.create(d, max_id=N - 1, **STORE)
    truth.insert(svc)
    svc.checkpoint()
    truth.mutate(svc, rng)
    session = svc.begin_snapshot().dir
    pinned = truth.frozen()
    truth.insert_again(svc, LOAD_BATCH)
    truth.mutate(svc, rng, 3.5)
    snap = Snapshot.open(session)
    yield Store(snap, *pinned, snap.snapshot)
    snap.close()
    svc.close()


# -- plain references -------------------------------------------------------
def ref_khop_levels(st: Store, root: int):
    a = st.adjacency()
    seen = np.zeros(N, bool)
    seen[root] = True
    levels = [np.array([root], np.int64)]
    while True:
        reached = np.flatnonzero(a[levels[-1]].sum(axis=0).A1 > 0)
        fresh = reached[~seen[reached]]
        if fresh.shape[0] == 0:
            return levels
        seen[fresh] = True
        levels.append(fresh)


def ref_sssp(st: Store, root: int, m: int):
    """Float32 Bellman-Ford to its fixed point: the least float32 path
    sums, whatever the order of relaxation."""
    dist = np.full(m, np.inf, np.float32)
    dist[root] = 0
    while True:
        cand = dist.copy()
        np.minimum.at(cand, st.dst, dist[st.src] + st.w)
        if np.array_equal(cand, dist):
            return dist
        dist = cand


def ref_two_hop(st: Store, seeds):
    """Per seed: each vertex two hops out, but neither the seed nor one of
    its friends, with its count of distinct middle friends."""
    a = st.adjacency()
    f = a[seeds]
    c = (f @ a).toarray()
    c[f.toarray() > 0] = 0
    c[np.arange(seeds.shape[0]), seeds] = 0
    rows, ids = np.nonzero(c)
    offsets = np.searchsorted(rows, np.arange(seeds.shape[0] + 1))
    return offsets, ids, c[rows, ids]


# -- the operators on every store ---------------------------------------------
@pytest.mark.parametrize("dense", ["never", "stream", "kernel"])
@pytest.mark.parametrize("name", STORES)
def test_khop_depths(request, name, dense):
    st = request.getfixturevalue(name)
    res = khop(st.graph, [st.root], k=64, dense=dense)
    want = ref_khop_levels(st, st.root)
    assert len(want) > 3
    assert len(res.levels) == len(want)
    for got, ref in zip(res.levels, want):
        assert np.array_equal(got, ref)


@pytest.mark.parametrize("dense", ["never", "stream", "kernel"])
@pytest.mark.parametrize("name", STORES)
def test_sssp_distances(request, name, dense):
    st = request.getfixturevalue(name)
    got = sssp(st.graph, st.root, weight="w", dense=dense)
    assert got.dtype == np.float32
    want = ref_sssp(st, st.root, got.shape[0])
    assert np.isfinite(want).sum() > N // 2
    assert np.array_equal(got, want)


@pytest.mark.parametrize("dense", ["never", "kernel"])
@pytest.mark.parametrize("name", STORES)
def test_two_hop_counts_seed_block(request, name, dense):
    st = request.getfixturevalue(name)
    res = two_hop_counts(st.graph, st.seeds, dense=dense)
    offsets, ids, counts = ref_two_hop(st, st.seeds)
    assert ids.shape[0] > 0
    assert np.array_equal(res.seeds, st.seeds)
    assert np.array_equal(res.offsets, offsets)
    assert np.array_equal(res.ids, ids)
    assert np.array_equal(res.counts, counts)


@pytest.mark.parametrize("name", STORES)
def test_triangle_count(request, name):
    st = request.getfixturevalue(name)
    a = st.adjacency()
    want = int((a @ a).multiply(a).sum())
    assert want > 0
    assert triangle_count(st.graph) == want


@pytest.mark.parametrize("name", STORES)
def test_friends_of_friends(request, name):
    st = request.getfixturevalue(name)
    offsets, ids, _ = ref_two_hop(st, st.seeds[:16])
    for i, v in enumerate(st.seeds[:16].tolist()):
        got = friends_of_friends(st.graph, v)
        assert np.array_equal(got, ids[offsets[i]:offsets[i + 1]])


@pytest.mark.parametrize("mode", ["psw_windows", "dense_gather"])
@pytest.mark.parametrize("name", STORES)
def test_pagerank_device_within_bound(request, name, mode):
    st = request.getfixturevalue(name)
    dg = st.device_graph()
    iv = st.graph.intervals
    ranks = np.asarray(pagerank_device(dg, n_iters=PAGERANK_ITERS,
                                       mode=mode))
    assert ranks.shape == (dg.n_partitions, dg.interval_len)
    ref, bound = chip_smoke.jacobi_pagerank(
        np.asarray(iv.to_internal(st.src), np.int64),
        np.asarray(iv.to_internal(st.dst), np.int64), ranks.size,
        PAGERANK_ITERS)
    assert np.all(np.abs(ranks.reshape(-1) - ref) <= bound)
