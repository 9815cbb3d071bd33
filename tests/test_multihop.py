"""Multi-hop operator tests (ISSUE 6): columnar 2-hop / triangle /
filtered-traversal operators vs naive per-hop references, on messy live LSM
state (buffers + tombstones), lock-free ManifestView epoch snapshots, the
dense Pallas plan path, and a reopened on-disk GraphDB; weighted `sssp`
against float64 and float32 references in every round mode."""
import os

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core import (
    EdgePredicate,
    GraphDB,
    GraphPAL,
    IntervalMap,
    LSMTree,
    as_engine,
    bfs,
    bfs_perhop,
    dedup_frontier,
    friends_of_friends,
    friends_of_friends_perhop,
    khop,
    shortest_path,
    sssp,
    triangle_count,
    two_hop_counts,
)
from repro.core import multihop as mh
from repro.core import telemetry


# ---------------------------------------------------------------------------
# Naive per-hop references (pure-python adjacency sets)
# ---------------------------------------------------------------------------
def adjacency(g):
    """Live adjacency sets in original ids, straight from to_coo()."""
    so, do = as_engine(g).to_coo()
    out_adj, in_adj, eset = {}, {}, set()
    for a, b in zip(np.asarray(so).tolist(), np.asarray(do).tolist()):
        out_adj.setdefault(a, set()).add(b)
        in_adj.setdefault(b, set()).add(a)
        eset.add((a, b))
    return out_adj, in_adj, eset


def naive_two_hop(out_adj, v, max_friends=None):
    """(ids, counts) per the per-hop FoF semantics: distinct middles,
    sorted-first-max_friends truncation, seed+friends excluded."""
    friends = sorted(out_adj.get(v, ()))
    if max_friends is not None:
        friends = friends[:max_friends]
    cnt = {}
    for u in friends:
        for w in out_adj.get(u, ()):
            cnt[w] = cnt.get(w, 0) + 1
    # only the (possibly truncated) friend set is excluded — exactly the
    # per-hop `setdiff1d(fof, [friends..., v])` semantics
    for w in list(cnt):
        if w == v or w in set(friends):
            del cnt[w]
    ids = sorted(cnt)
    return (np.asarray(ids, np.int64),
            np.asarray([cnt[w] for w in ids], np.int64))


def naive_triangles(out_adj, in_adj, eset):
    return sum(1 for v in set(in_adj) & set(out_adj)
               for u in in_adj[v] for w in out_adj[v] if (u, w) in eset)


def naive_filtered_khop(fadj, seeds, k):
    vis = set(seeds)
    lev = set(seeds)
    levels = [sorted(lev)]
    for _ in range(k):
        nxt = set()
        for u in lev:
            nxt |= fadj.get(u, set())
        fresh = nxt - vis
        if not fresh:
            break
        vis |= fresh
        levels.append(sorted(fresh))
        lev = fresh
    return levels, sorted(vis)


def build_messy_lsm(n, e, seed, n_deletes=0, columns=None, etype=None):
    """Live LSM with flushed levels, tombstones, and a still-buffered tail."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    iv = IntervalMap.for_capacity(n - 1, 16)
    dtypes = {k: v.dtype for k, v in (columns or {}).items()} or None
    t = LSMTree(iv, n_levels=3, branching=4, buffer_cap=max(60, e // 8),
                max_partition_edges=max(100, e // 4), column_dtypes=dtypes)
    k = e - max(1, e // 10)

    def sl(a, b):
        cols = {key: v[a:b] for key, v in (columns or {}).items()}
        et = None if etype is None else etype[a:b]
        return cols, et

    cols, et = sl(0, k)
    t.insert_edges(src[:k], dst[:k], etype=et, columns=cols)
    cols, et = sl(k, e)
    t.insert_edges(src[k:], dst[k:], etype=et, columns=cols)
    for i in rng.choice(k, size=min(n_deletes, k), replace=False):
        t.delete_edge(int(src[i]), int(dst[i]))
    return t


def assert_two_hop_equal(res, seeds, out_adj, max_friends=None):
    for i, v in enumerate(np.asarray(seeds).tolist()):
        ids, counts = naive_two_hop(out_adj, v, max_friends)
        sl = res.slice_of(i)
        assert np.array_equal(res.ids[sl], ids), v
        assert np.array_equal(res.counts[sl], counts), v


# ---------------------------------------------------------------------------
# Property tests: random live stores vs the naive reference
# ---------------------------------------------------------------------------
class TestPropertyVsNaive:
    @given(st.integers(0, 10_000), st.integers(20, 400), st.integers(0, 40),
           st.booleans())
    @settings(max_examples=25, deadline=None)
    def test_two_hop_counts_matches_naive(self, seed, e, n_deletes, trunc):
        n = 120
        t = build_messy_lsm(n, e, seed, n_deletes)
        out_adj, _, _ = adjacency(t)
        rng = np.random.default_rng(seed)
        seeds = rng.integers(0, n, 17).astype(np.int64)  # dups allowed
        mf = 3 if trunc else None
        res = two_hop_counts(t, seeds, max_friends=mf)
        assert_two_hop_equal(res, seeds, out_adj, mf)

    @given(st.integers(0, 10_000), st.integers(20, 400), st.integers(0, 40))
    @settings(max_examples=25, deadline=None)
    def test_triangle_count_matches_naive(self, seed, e, n_deletes):
        n = 100
        t = build_messy_lsm(n, e, seed, n_deletes)
        out_adj, in_adj, eset = adjacency(t)
        want = naive_triangles(out_adj, in_adj, eset)
        assert triangle_count(t) == want
        # chunked wedge budget must not change the count
        assert triangle_count(t, wedge_budget=7) == want

    @given(st.integers(0, 10_000), st.integers(20, 300))
    @settings(max_examples=20, deadline=None)
    def test_filtered_traversal_matches_naive(self, seed, e):
        n = 90
        rng = np.random.default_rng(seed)
        w = rng.integers(0, 10, e).astype(np.float32)
        et = rng.integers(0, 3, e).astype(np.int8)
        t = build_messy_lsm(n, e, seed, columns={"w": w}, etype=et)
        pred = EdgePredicate(etype=1, column="w", op="<=", value=5.0)
        batch = as_engine(t).edge_columns_batch(np.arange(n), names=["w"])
        fadj = {}
        for s, d, ww, ee in zip(batch.src.tolist(), batch.dst.tolist(),
                                batch.columns["w"].tolist(),
                                batch.etype.tolist()):
            if ee == 1 and ww <= 5.0:
                fadj.setdefault(s, set()).add(d)
        seeds = [int(rng.integers(0, n))]
        res = khop(t, seeds, 3, predicate=pred)
        levels, visited = naive_filtered_khop(fadj, seeds, 3)
        assert len(res.levels) == len(levels)
        for got, want in zip(res.levels, levels):
            assert got.tolist() == want
        assert res.visited.tolist() == visited

    @given(st.integers(0, 10_000), st.integers(20, 300), st.integers(0, 30))
    @settings(max_examples=20, deadline=None)
    def test_dense_paths_bitwise_equal_sparse(self, seed, e, n_deletes):
        n = 110
        t = build_messy_lsm(n, e, seed, n_deletes)
        rng = np.random.default_rng(seed)
        seeds = np.unique(rng.integers(0, n, 9))
        sparse = two_hop_counts(t, seeds)
        dense = two_hop_counts(t, seeds, dense="kernel")
        assert np.array_equal(sparse.offsets, dense.offsets)
        assert np.array_equal(sparse.ids, dense.ids)
        assert np.array_equal(sparse.counts, dense.counts)
        s0 = [int(seeds[0])]
        base = khop(t, s0, 3, dense="never")
        for mode in ("kernel", "stream"):
            other = khop(t, s0, 3, dense=mode)
            assert len(base.levels) == len(other.levels)
            for a, b in zip(base.levels, other.levels):
                assert np.array_equal(a, b)
            assert np.array_equal(base.visited, other.visited)

    @given(st.integers(0, 10_000), st.integers(20, 300))
    @settings(max_examples=20, deadline=None)
    def test_query_facades_match_perhop(self, seed, e):
        n = 100
        t = build_messy_lsm(n, e, seed, n_deletes=10)
        rng = np.random.default_rng(seed)
        for v in rng.integers(0, n, 4).tolist():
            assert np.array_equal(friends_of_friends(t, v),
                                  friends_of_friends_perhop(t, v))
            assert np.array_equal(friends_of_friends(t, v, max_friends=2),
                                  friends_of_friends_perhop(t, v, max_friends=2))
            assert bfs(t, v, max_depth=4) == bfs_perhop(t, v, max_depth=4)
        s, d = int(rng.integers(0, n)), int(rng.integers(0, n))
        # the columnar two-sided meet takes the true minimum: oracle is
        # one-sided BFS, not the first-meet per-hop baseline
        want = bfs_perhop(t, s, max_depth=4).get(d)
        assert shortest_path(t, s, d, max_depth=4) == want


# ---------------------------------------------------------------------------
# Store-generality: epoch views and a reopened on-disk GraphDB
# ---------------------------------------------------------------------------
class TestAcrossStores:
    def test_manifest_view_identical_to_live(self):
        t = build_messy_lsm(300, 2000, seed=3, n_deletes=60)
        seeds = np.unique(np.random.default_rng(3).integers(0, 300, 40))
        live = two_hop_counts(t, seeds)
        with t.read_view() as view:
            pinned = two_hop_counts(view, seeds)
            assert np.array_equal(live.offsets, pinned.offsets)
            assert np.array_equal(live.ids, pinned.ids)
            assert np.array_equal(live.counts, pinned.counts)
            assert triangle_count(view) == triangle_count(t)
            # mutate the live store: the pinned view must not move
            t.insert_edges(np.arange(50), np.arange(1, 51))
            again = two_hop_counts(view, seeds)
            assert np.array_equal(pinned.ids, again.ids)
            assert np.array_equal(pinned.counts, again.counts)
        # the LIVE store sees the mutation (fresh cache token -> no stale
        # plan reuse)
        after_sparse = two_hop_counts(t, seeds)
        after_dense = two_hop_counts(t, seeds, dense="kernel")
        assert np.array_equal(after_sparse.ids, after_dense.ids)
        assert np.array_equal(after_sparse.counts, after_dense.counts)

    def test_reopened_graphdb_matches_prior_answers(self, tmp_path):
        rng = np.random.default_rng(11)
        n, e = 400, 3000
        src = rng.integers(0, n, e)
        dst = rng.integers(0, n, e)
        d = os.path.join(str(tmp_path), "db")
        db = GraphDB.create(d, max_id=n - 1, n_partitions=8, n_levels=2,
                            branching=4, buffer_cap=800,
                            max_partition_edges=1500, persist_min_edges=64)
        db.insert_edges(src[:e - 200], dst[:e - 200])
        db.checkpoint()
        db.insert_edges(src[e - 200:], dst[e - 200:])  # WAL-tail edges
        seeds = np.unique(rng.integers(0, n, 64))
        live = two_hop_counts(db, seeds)
        tri = triangle_count(db)
        out_adj, in_adj, eset = adjacency(db)
        assert tri == naive_triangles(out_adj, in_adj, eset)
        assert_two_hop_equal(live, seeds, out_adj)
        db.close()

        re_db = GraphDB.open(d)
        res = two_hop_counts(re_db, seeds)
        assert np.array_equal(res.offsets, live.offsets)
        assert np.array_equal(res.ids, live.ids)
        assert np.array_equal(res.counts, live.counts)
        assert triangle_count(re_db) == tri
        dense = two_hop_counts(re_db, seeds, dense="kernel")
        assert np.array_equal(dense.ids, live.ids)
        assert np.array_equal(dense.counts, live.counts)
        re_db.close()


# ---------------------------------------------------------------------------
# Engine primitives behind the operators
# ---------------------------------------------------------------------------
class TestEnginePrimitives:
    def test_expand_frontier_matches_grouped_batch(self):
        t = build_messy_lsm(200, 1200, seed=5, n_deletes=30)
        eng = as_engine(t)
        vs = np.unique(np.random.default_rng(5).integers(0, 200, 60))
        for direction in ("out", "in"):
            owner, nb = eng.expand_frontier(vs, direction)
            vals, offsets = (eng.out_neighbors_batch(vs) if direction == "out"
                             else eng.in_neighbors_batch(vs))
            M = np.int64(eng.n_internal_vertices)
            got = np.sort(owner * M + nb)
            want = np.sort(np.repeat(np.arange(vs.shape[0], dtype=np.int64),
                                     np.diff(offsets)) * M + vals)
            assert np.array_equal(got, want), direction

    def test_predicate_pushdown_prunes_before_gather(self):
        rng = np.random.default_rng(6)
        n, e = 150, 900
        src = rng.integers(0, n, e)
        dst = rng.integers(0, n, e)
        w = rng.normal(size=e)
        et = rng.integers(0, 2, e).astype(np.int8)
        g = GraphPAL.from_edges(src, dst, n_partitions=4, max_id=n - 1,
                                columns={"w": w}, etype=et)
        eng = as_engine(g)
        pred = EdgePredicate(etype=1, column="w", op=">", value=0.0)
        vs = np.arange(0, n, 2, dtype=np.int64)
        owner, nb = eng.expand_frontier(vs, "out", pred)
        keep = (et == 1) & (w > 0.0)
        want = sorted(zip(src[keep].tolist(), dst[keep].tolist()))
        got = sorted(zip(vs[owner].tolist(), nb.tolist()))
        want = [p for p in want if p[0] % 2 == 0]
        assert got == want

    def test_degree_batch_counts_live_multi_edges(self):
        t = build_messy_lsm(120, 700, seed=7, n_deletes=25)
        eng = as_engine(t)
        so, do = t.to_coo()
        vs = np.arange(120, dtype=np.int64)
        out_want = np.bincount(np.asarray(so), minlength=120)
        in_want = np.bincount(np.asarray(do), minlength=120)
        assert np.array_equal(eng.out_degree_batch(vs), out_want)
        assert np.array_equal(eng.in_degree_batch(vs), in_want)

    def test_dedup_frontier_degree_order(self):
        t = build_messy_lsm(100, 600, seed=8)
        eng = as_engine(t)
        ids = np.array([5, 5, 9, 3, 9, 40, 3], np.int64)
        out = dedup_frontier(eng, ids)
        assert np.array_equal(out, [3, 5, 9, 40])
        out = dedup_frontier(eng, ids, visited=np.array([9, 40]))
        assert np.array_equal(out, [3, 5])
        ordered = dedup_frontier(eng, ids, degree_order=True)
        deg = eng.out_degree_batch(ordered)
        assert np.all(np.diff(deg) <= 0)  # descending
        assert set(ordered.tolist()) == {3, 5, 9, 40}

    def test_semijoin_and_aggregate(self):
        table = np.array([2, 5, 9], np.int64)
        keys = np.array([9, 1, 5, 10, 2, 2], np.int64)
        assert mh.semijoin(keys, table).tolist() == \
            [True, False, True, False, True, True]
        assert mh.semijoin(keys, np.empty(0, np.int64)).tolist() == [False] * 6
        u, c = mh.aggregate_counts(np.array([3, 1, 3, 3, 1], np.int64))
        assert u.tolist() == [1, 3] and c.tolist() == [2, 3]


# ---------------------------------------------------------------------------
# The frontier-expansion kernel plan
# ---------------------------------------------------------------------------
class TestFrontierPlan:
    def test_virtual_rows_linear_in_edges(self):
        from repro.kernels.frontier_expand import build_frontier_plan
        rng = np.random.default_rng(9)
        # one hub: padding every vertex to degree 5000 would take n*5000 slots
        src = np.concatenate([rng.integers(0, 1000, 5000),
                              rng.integers(0, 1000, 2000)])
        dst = np.concatenate([np.zeros(5000, np.int64),
                              rng.integers(0, 1000, 2000)])
        plan = build_frontier_plan(src, dst, 1000, 1000, k_slots=32)
        assert plan.idx.shape[0] <= ((plan.n_edges // 32 + 1000 + 1) // 128
                                     + 1) * 128
        assert plan.mask.sum() == plan.n_edges  # exact, no truncation

    def test_counts_match_dedup_matmul(self):
        from repro.kernels.frontier_expand import (build_frontier_plan,
                                                   frontier_expand_counts)
        rng = np.random.default_rng(10)
        n, e, B = 300, 2500, 5
        src = rng.integers(0, n, e)
        dst = rng.integers(0, n, e)
        plan = build_frontier_plan(src, dst, n, n, k_slots=8)
        x = (rng.random((n, B)) < 0.2).astype(np.float32)
        A = np.zeros((n, n), np.float32)
        A[dst, src] = 1.0  # dedup adjacency
        want = A @ x
        for use_kernel in (False, True):
            got = frontier_expand_counts(plan, x, use_kernel=use_kernel)
            assert np.array_equal(got, want), use_kernel
        from repro.kernels.frontier_expand import frontier_expand_np
        rows = frontier_expand_np(plan.idx, plan.mask, x)
        out = np.zeros((n + 1, B), np.float32)
        np.add.at(out, plan.row_dst, rows)
        assert np.array_equal(out[:n], want)

    def test_empty_plan(self):
        from repro.kernels.frontier_expand import (build_frontier_plan,
                                                   frontier_expand_counts)
        plan = build_frontier_plan(np.empty(0), np.empty(0), 10, 10)
        out = frontier_expand_counts(plan, np.ones((10, 2), np.float32))
        assert out.shape == (10, 2) and not out.any()


def _counter_delta(before, after, name):
    return after.get(name, 0) - before.get(name, 0)


class TestKernelUploadCounter:
    @pytest.mark.parametrize("query", ["khop", "two_hop"])
    def test_h2d_bytes_are_the_staged_arrays(self, query):
        """`multihop.kernel.h2d_bytes` adds the plan's folded slots and
        `row_dst` once, at the first launch over the plan, and each
        launch's (M, B) float32 indicator panel unpadded;
        `multihop.kernel.plan_uploads` rises by exactly one."""
        t = build_messy_lsm(110, 400, 3)
        plan = mh.dense_plan(t, "out")
        M = as_engine(t).n_internal_vertices
        # the slots are the (R, K) int32 idx with the mask folded in
        once = plan.idx.nbytes + plan.row_dst.nbytes
        before = telemetry.snapshot()["counters"]
        if query == "khop":
            khop(t, [5], 4, dense="kernel")
        else:
            two_hop_counts(t, np.arange(10), dense="kernel")
        after = telemetry.snapshot()["counters"]
        if query == "khop":
            launches = (after["multihop.hops"].get("kernel", 0)
                        - before.get("multihop.hops", {}).get("kernel", 0))
            panels = launches * M * 1 * 4
        else:
            launches = 2                  # both hops of one 10-seed block
            panels = launches * M * 10 * 4
        assert launches > 0
        assert _counter_delta(before, after,
                              "multihop.kernel.h2d_bytes") == once + panels
        assert _counter_delta(before, after,
                              "multihop.kernel.plan_uploads") == 1

    def test_pinned_view_uploads_its_plan_once(self):
        """Two kernel traversals over one pinned view send the plan once;
        the second sends nothing but its indicator panels."""
        t = build_messy_lsm(110, 400, 4)
        with t.read_view() as view:
            M = as_engine(view).n_internal_vertices
            first = khop(view, [5], 4, dense="kernel")
            before = telemetry.snapshot()["counters"]
            second = khop(view, [5], 4, dense="kernel")
            after = telemetry.snapshot()["counters"]
        launches = (after["multihop.hops"].get("kernel", 0)
                    - before.get("multihop.hops", {}).get("kernel", 0))
        assert launches > 0
        assert _counter_delta(before, after,
                              "multihop.kernel.plan_uploads") == 0
        assert _counter_delta(before, after,
                              "multihop.kernel.h2d_bytes") == launches * M * 4
        for a, b in zip(first.levels, second.levels):
            assert np.array_equal(a, b)

    def test_new_epoch_uploads_a_new_plan(self):
        """After `insert_edges`, a new read view's first kernel hop uploads
        a fresh plan, and its answer matches the sparse path bitwise on the
        new epoch, the new edge included."""
        n = 110
        t = build_messy_lsm(n, 400, 5)
        with t.read_view() as old:
            khop(old, [5], 4, dense="kernel")
        live = set(as_engine(t).out_neighbors_batch([5])[0].tolist())
        fresh_dst = next(v for v in range(n) if v != 5 and v not in live)
        t.insert_edges(np.array([5]), np.array([fresh_dst]))
        with t.read_view() as view:
            before = telemetry.snapshot()["counters"]
            dense = khop(view, [5], 4, dense="kernel")
            after = telemetry.snapshot()["counters"]
            sparse = khop(view, [5], 4, dense="never")
        assert _counter_delta(before, after,
                              "multihop.kernel.plan_uploads") == 1
        assert len(dense.levels) == len(sparse.levels)
        for a, b in zip(dense.levels, sparse.levels):
            assert np.array_equal(a, b)
        assert np.array_equal(dense.visited, sparse.visited)
        assert fresh_dst in dense.levels[1].tolist()

    def test_live_store_keeps_one_device_plan(self):
        """On a live store a mutation moves the cache token: the next
        kernel hop replaces the older token's device plan, so the cache
        holds one device plan per direction, not one per epoch."""
        t = build_messy_lsm(110, 400, 6)
        eng = as_engine(t)
        khop(t, [5], 4, dense="kernel")
        t.insert_edges(np.array([5]), np.array([7]))
        khop(t, [5], 4, dense="kernel")
        held = [k for k in eng.plan_cache()
                if k[0] == (mh._DEVICE_PLAN_KEY, "out")]
        assert held == [((mh._DEVICE_PLAN_KEY, "out"), eng.cache_token())]

    def test_launches_are_counted_by_path(self):
        """`multihop.kernel.launches` counts a single-root BFS's kernel
        hops, one each, under `gather`; a 128-seed dense 2-hop's two
        launches are wide panels and count none there (`mosaic` on the
        chip, `ref` off it), and its answer is the sparse path's."""
        t = build_messy_lsm(200, 900, 7)

        def labels(snap, name="multihop.kernel.launches"):
            c = snap.get(name, {})
            return c if isinstance(c, dict) else {"": c}

        before = telemetry.snapshot()["counters"]
        khop(t, [5], 6, dense="kernel")
        mid = telemetry.snapshot()["counters"]
        hops = (labels(mid, "multihop.hops").get("kernel", 0)
                - labels(before, "multihop.hops").get("kernel", 0))
        assert hops > 0
        assert (labels(mid).get("gather", 0)
                - labels(before).get("gather", 0)) == hops
        seeds = np.arange(128)
        dense = two_hop_counts(t, seeds, dense="kernel")
        after = telemetry.snapshot()["counters"]
        wide = {k: v - labels(mid).get(k, 0)
                for k, v in labels(after).items()}
        assert wide.get("gather", 0) == 0
        assert wide.get("mosaic", 0) + wide.get("ref", 0) == 2
        sparse = two_hop_counts(t, seeds, dense="never")
        for a, b in ((dense.ids, sparse.ids), (dense.counts, sparse.counts),
                     (dense.offsets, sparse.offsets)):
            assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# Weighted traversal: sssp over an edge-weight column
# ---------------------------------------------------------------------------
def weighted_messy_lsm(n, e, seed, n_deletes=0):
    """A messy live LSM whose edges carry float32 weights in column "w":
    parallel edges with other weights, zero weights, and vertices no edge
    reaches (the last tenth of the ids only ever sends)."""
    rng = np.random.default_rng(seed + 1)
    w = rng.random(e, dtype=np.float32)
    w[rng.random(e) < 0.05] = 0
    t = build_messy_lsm(n, e, seed, n_deletes, columns={"w": w})
    # parallel copies of some edges, lighter or heavier, and no edge into
    # the last tenth of the ids
    live = as_engine(t).edge_columns_batch(np.arange(n), names=["w"])
    k = min(40, live.src.shape[0])
    scale = np.where(np.arange(k) % 2 == 0, 0.5, 3.0).astype(np.float32)
    t.insert_edges(live.src[:k], live.dst[:k],
                   columns={"w": live.columns["w"][:k] * scale})
    cut = n - n // 10
    for s_, d_ in zip(live.src.tolist(), live.dst.tolist()):
        if d_ >= cut:
            t.delete_edge(s_, d_)
    return t


def live_weighted_edges(g, n):
    b = as_engine(g).edge_columns_batch(np.arange(n), names=["w"])
    return b.src, b.dst, b.columns["w"].astype(np.float32)


def bellman_ford32(src, dst, w, n, root):
    """Plain float32 Bellman-Ford over every edge until nothing falls."""
    d = np.full(n, np.inf, np.float32)
    d[root] = 0
    while True:
        c = np.full(n, np.inf, np.float32)
        np.minimum.at(c, dst, d[src] + w)
        new = np.minimum(d, c)
        if np.array_equal(new, d):
            return d
        d = new


def bellman_ford64(src, dst, w, n, root):
    d = np.full(n, np.inf)
    d[root] = 0
    w = w.astype(np.float64)
    while True:
        c = np.full(n, np.inf)
        np.minimum.at(c, dst, d[src] + w)
        new = np.minimum(d, c)
        if np.array_equal(new, d):
            return d
        d = new


class TestSSSP:
    @pytest.mark.parametrize("seed,e,n_deletes", [(0, 300, 0), (1, 900, 25),
                                                  (2, 2500, 60)])
    def test_matches_float64_and_bitwise_float32(self, seed, e, n_deletes):
        """Against a float64 Bellman-Ford within float32 rounding, and bit
        for bit against a float32 one, on a live store with buffers,
        deletes, parallel edges of other weights, zero weights and
        unreached vertices."""
        n = 120
        t = weighted_messy_lsm(n, e, seed, n_deletes)
        src, dst, w = live_weighted_edges(t, n)
        assert (w == 0).any() and np.unique(src * n + dst).size < src.size
        for root in (0, 7, int(src[3])):
            got = sssp(t, root, dense="never")
            assert got.dtype == np.float32 and got.shape[0] >= n
            assert np.isinf(got[n:]).all()
            want32 = bellman_ford32(src, dst, w, n, root)
            assert np.array_equal(got[:n], want32)
            want64 = bellman_ford64(src, dst, w, n, root)
            reached = np.isfinite(want64)
            assert np.array_equal(np.isfinite(got[:n]), reached)
            assert not reached[n - n // 10:].any()
            # a path of h <= n adds is within h·2^-24 of its float64 sum
            np.testing.assert_allclose(got[:n][reached], want64[reached],
                                       rtol=n * 2.0 ** -24, atol=0)

    @pytest.mark.parametrize("seed", [3, 4])
    def test_every_round_mode_gives_the_same_bits(self, seed):
        """`dense="kernel"` (the min-plus relax on the device), "stream"
        and "auto" over a held weighted plan all equal `dense="never"`
        bitwise, on the live store and on a pinned view."""
        n = 150
        t = weighted_messy_lsm(n, 1200, seed, 20)
        for g in (t, t.read_view()):
            mh.dense_plan(g, "out", weight="w")
            for root in (1, 9):
                base = sssp(g, root, dense="never")
                for mode in ("kernel", "stream", "auto"):
                    assert np.array_equal(sssp(g, root, dense=mode), base)
            base_in = sssp(g, 4, direction="in", dense="never")
            assert np.array_equal(sssp(g, 4, direction="in",
                                       dense="kernel"), base_in)

    def test_rounds_run_on_the_device_and_are_counted(self):
        """With the weighted plan held, dense rounds take the kernel: the
        relax counters and spans say how many rounds ran in each mode and
        how many distances fell."""
        n = 150
        t = weighted_messy_lsm(n, 1500, 5)
        with t.read_view() as view:
            mh.dense_plan(view, "out", weight="w")
            telemetry.trace_events(clear=True)
            before = telemetry.snapshot()["counters"]
            dist = sssp(view, 2)
            after = telemetry.snapshot()["counters"]
        rounds = [e for e in telemetry.trace_events(clear=True)
                  if e["name"] == "multihop.relax"]
        modes = [e["args"]["mode"] for e in rounds]
        assert "kernel" in modes and "sparse" in modes
        ran = {m: after["multihop.relax.rounds"].get(m, 0)
               - before.get("multihop.relax.rounds", {}).get(m, 0)
               for m in set(modes)}
        assert ran == {m: modes.count(m) for m in set(modes)}
        fell = sum(e["args"]["improved"] for e in rounds)
        assert _counter_delta(before, after, "multihop.relax.improved") \
            == fell >= int(np.isfinite(dist).sum()) - 1
        assert rounds[-1]["args"]["improved"] == 0

    def test_weighted_plan_is_memoized_beside_the_unweighted(self):
        """The weighted plan and its device copy sit under their own names
        in the same cache under the same token; holding them changes no
        BFS hop mode."""
        n = 150
        t = weighted_messy_lsm(n, 1500, 6)
        with t.read_view() as view:
            eng = as_engine(view)
            plan = mh.dense_plan(view, "out", weight="w")
            assert plan.weight is not None
            bfs_before = khop(view, [2], 5)
            sssp(view, 2, dense="kernel")
            token = eng.cache_token()
            held = {k[0] for k in eng.plan_cache()
                    if isinstance(k, tuple) and k[1] == token}
            assert {(mh._WPLAN_KEY, "w", "out"),
                    (mh._DEVICE_WPLAN_KEY, "w", "out")} <= held
            assert (mh._PLAN_KEY, "out") not in held
            # BFS sees no plan of its own: a dense hop streams, as before
            assert mh._hop_mode(eng, n, "auto", 0.05, None) == "stream"
            assert mh._hop_mode(eng, n, "auto", 0.05, None,
                                mh._plan_name("out", "w")) == "kernel"
            after = khop(view, [2], 5)
            for a, b in zip(bfs_before.levels, after.levels):
                assert np.array_equal(a, b)

    def test_pinned_view_is_unchanged_by_mutations(self):
        n = 120
        t = weighted_messy_lsm(n, 900, 7, 10)
        view = t.read_view()
        mh.dense_plan(view, "out", weight="w")
        pinned = sssp(view, 0, dense="kernel")
        live = as_engine(t).edge_columns_batch([0], names=["w"])
        t.insert_edges(np.arange(1, 30), np.arange(2, 31),
                       columns={"w": np.zeros(29, np.float32)})
        for d_ in live.dst[:5].tolist():
            t.delete_edge(0, int(d_))
        assert np.array_equal(sssp(view, 0, dense="kernel"), pinned)
        assert np.array_equal(sssp(view, 0, dense="never"), pinned)
        view.release()
        src, dst, w = live_weighted_edges(t, n)
        with t.read_view() as fresh:
            assert np.array_equal(sssp(fresh, 0, dense="kernel")[:n],
                                  bellman_ford32(src, dst, w, n, 0))

    def test_new_epoch_uploads_a_new_weighted_plan(self):
        """After `insert_edges` of a cheaper edge, a new view's first
        kernel round uploads a new weighted plan and the distance falls."""
        n = 120
        t = weighted_messy_lsm(n, 900, 8)
        with t.read_view() as old:
            mh.dense_plan(old, "out", weight="w")
            before_d = sssp(old, 0, dense="kernel")
        target = int(np.argmax(np.where(np.isfinite(before_d[:n]),
                                        before_d[:n], -1)))
        assert before_d[target] > 0
        t.insert_edges(np.array([0]), np.array([target]),
                       columns={"w": np.array([0.0], np.float32)})
        with t.read_view() as view:
            before = telemetry.snapshot()["counters"]
            after_d = sssp(view, 0, dense="kernel")
            after = telemetry.snapshot()["counters"]
            assert np.array_equal(after_d, sssp(view, 0, dense="never"))
        assert _counter_delta(before, after,
                              "multihop.kernel.plan_uploads") == 1
        assert after_d[target] == 0 < before_d[target]
        assert (after_d <= before_d).all()

    def test_durable_store_replays_its_weights(self, tmp_path):
        """Weights go through the WAL with the edges: a ServiceDB closed
        with a WAL tail and reopened gives the same distances."""
        from repro.core import ServiceDB
        rng = np.random.default_rng(12)
        n, e = 300, 3000
        src, dst = rng.integers(0, n, e), rng.integers(0, n, e)
        w = rng.random(e, dtype=np.float32)
        d = os.path.join(str(tmp_path), "svc")
        svc = ServiceDB.create(d, max_id=n - 1, n_partitions=4, n_levels=2,
                               branching=4, buffer_cap=800,
                               max_partition_edges=1500,
                               column_dtypes={"w": "float32"})
        svc.insert_edges(src[:2500], dst[:2500], columns={"w": w[:2500]})
        svc.checkpoint()
        svc.insert_edges(src[2500:], dst[2500:], columns={"w": w[2500:]})
        with svc.read_view() as view:
            mh.dense_plan(view, "out", weight="w")
            want = sssp(view, 5)
        svc.close()
        assert np.array_equal(want[:n], bellman_ford32(src, dst, w, n, 5))
        svc = ServiceDB.open(d)
        with svc.read_view() as view:
            assert np.array_equal(sssp(view, 5, dense="kernel"), want)
        svc.close()

    def test_negative_weight_is_refused(self):
        n = 60
        t = build_messy_lsm(n, 200, 9,
                            columns={"w": np.full(200, -1, np.float32)})
        with pytest.raises(ValueError, match="negative"):
            sssp(t, 0, dense="never")
        with pytest.raises(ValueError, match="negative"):
            mh.dense_plan(t, "out", weight="w")
