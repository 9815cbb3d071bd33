"""StorageEngine tests: batched primitives vs naive per-vertex queries
(across LSM levels + buffers + tombstones), engine-generic traversal, and
LSMTree.snapshot() analytics on the live store (ISSUE 1)."""
import numpy as np
import pytest

from repro.core import (
    GraphPAL,
    IntervalMap,
    LSMEngine,
    LSMTree,
    PALEngine,
    StorageEngine,
    as_engine,
    bfs,
    build_device_graph,
    friends_of_friends,
    pagerank_device,
    shortest_path,
)


def build_live_lsm(n=10_000, e=4000, seed=0, n_deletes=150,
                   column_dtypes=None, columns=None):
    """An LSM store in a deliberately messy live state: multiple flushes,
    push-down merges, tombstones, and a final batch still sitting in the
    in-memory buffers."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    iv = IntervalMap.for_capacity(n - 1, 16)
    t = LSMTree(iv, n_levels=3, branching=4, buffer_cap=600,
                max_partition_edges=900, column_dtypes=column_dtypes)
    k = e - min(400, max(1, e // 8))
    cols = columns or {}

    def sl(a, b):
        return {key: v[a:b] for key, v in cols.items()}

    t.insert_edges(src[:k], dst[:k], columns=sl(0, k))
    # final batch smaller than buffer_cap so it STAYS in the buffers
    t.insert_edges(src[k:], dst[k:], columns=sl(k, e))
    assert t.total_buffered() > 0
    # deletes last, targeting flushed edges, so tombstones are live at query
    # time (earlier deletes would be purged by the later merges)
    deleted = []
    if n_deletes:
        for i in rng.choice(k, size=n_deletes, replace=False):
            if t.delete_edge(int(src[i]), int(dst[i])):
                deleted.append((int(src[i]), int(dst[i])))
    return t, src, dst, deleted


@pytest.fixture(scope="module")
def live_lsm():
    return build_live_lsm()


class TestEngineDispatch:
    def test_as_engine_types(self, live_lsm):
        t, *_ = live_lsm
        eng = as_engine(t)
        assert isinstance(eng, LSMEngine)
        assert as_engine(eng) is eng  # idempotent
        assert t.storage_engine() is eng  # cached
        g = GraphPAL.from_edges([0, 1], [1, 2], n_partitions=2, max_id=9)
        assert isinstance(as_engine(g), PALEngine)

    def test_as_engine_rejects_unknown(self):
        with pytest.raises(TypeError):
            as_engine(object())

    def test_no_storage_class_branching_in_query_layer(self):
        import inspect

        import repro.core.query as query

        source = inspect.getsource(query)
        assert "isinstance" not in source  # acceptance: zero class branching


class TestBatchedEquivalence:
    """Acceptance: batched LSM out/in_neighbors_batch must match the naive
    per-vertex results across levels + buffers + tombstones."""

    def test_live_state_is_messy(self, live_lsm):
        t, *_ = live_lsm
        assert t.total_buffered() > 0, "want edges still in buffers"
        assert t.stats.pushdown_merges > 0, "want multiple populated levels"
        assert any(p.dead is not None and p.dead.any()
                   for p in t.all_partitions()), "want live tombstones"

    @pytest.mark.parametrize("direction", ["out", "in"])
    def test_lsm_batch_matches_per_vertex(self, live_lsm, direction):
        t, src, dst, _ = live_lsm
        eng = t.storage_engine()
        rng = np.random.default_rng(1)
        vs = np.unique(rng.integers(0, 10_000, 400))  # hits + misses
        if direction == "out":
            vals, offsets = eng.out_neighbors_batch(vs)
            naive = [t.out_neighbors(int(v)) for v in vs]
        else:
            vals, offsets = eng.in_neighbors_batch(vs)
            naive = [t.in_neighbors(int(v)) for v in vs]
        assert offsets.shape == (vs.shape[0] + 1,)
        assert int(offsets[-1]) == vals.shape[0]
        for i, v in enumerate(vs):
            got = np.sort(vals[offsets[i]:offsets[i + 1]])
            assert np.array_equal(got, np.sort(naive[i])), int(v)

    def test_pal_batch_matches_per_vertex(self):
        rng = np.random.default_rng(2)
        n, e = 500, 4000
        src = rng.integers(0, n, e)
        dst = rng.integers(0, n, e)
        g = GraphPAL.from_edges(src, dst, n_partitions=8, max_id=n - 1)
        eng = g.storage_engine()
        vs = np.arange(0, n, 3)
        vals, offsets = eng.out_neighbors_batch(vs)
        for i, v in enumerate(vs):
            got = np.sort(vals[offsets[i]:offsets[i + 1]])
            assert np.array_equal(got, np.sort(dst[src == v])), int(v)
        vals, offsets = eng.in_neighbors_batch(vs)
        for i, v in enumerate(vs):
            got = np.sort(vals[offsets[i]:offsets[i + 1]])
            assert np.array_equal(got, np.sort(src[dst == v])), int(v)

    def test_empty_frontier_and_missing_vertices(self, live_lsm):
        t, *_ = live_lsm
        eng = t.storage_engine()
        vals, offsets = eng.out_neighbors_batch(np.empty(0, np.int64))
        assert vals.size == 0 and np.array_equal(offsets, [0])
        # vertices with no in-edges at all
        _, _, dst, _ = live_lsm
        missing = np.setdiff1d(np.arange(10_000), dst)[:2]
        assert missing.size == 2
        vals, offsets = eng.in_neighbors_batch(missing)
        assert vals.size == 0 and np.array_equal(offsets, [0, 0, 0])


class TestEdgeColumnsBatch:
    def test_columns_follow_edges_across_levels_and_buffers(self):
        rng = np.random.default_rng(3)
        n, e = 10_000, 3000
        src = rng.integers(0, n, e)
        dst = rng.integers(0, n, e)
        w = (src * 7 + dst).astype(np.float32)
        t, *_ = build_live_lsm(n=n, e=e, seed=3, n_deletes=0,
                               column_dtypes={"w": np.float32},
                               columns={"w": w})
        # rebuild with the exact arrays used above
        eng = t.storage_engine()
        vs = np.unique(rng.integers(0, n, 200))
        batch = eng.edge_columns_batch(vs, names=["w"], direction="out")
        assert batch.src.shape == batch.dst.shape == batch.columns["w"].shape
        total = 0
        for i, v in enumerate(vs):
            sl = batch.slice_of(i)
            assert np.all(batch.src[sl] == v)
            total += sl.stop - sl.start
        assert total == batch.src.shape[0]
        np.testing.assert_allclose(
            batch.columns["w"],
            (batch.src * 7 + batch.dst).astype(np.float32))

    def test_in_direction_groups_by_destination(self, live_lsm):
        t, *_ = live_lsm
        eng = t.storage_engine()
        vs = np.asarray([5, 77, 4242])
        batch = eng.edge_columns_batch(vs, direction="in")
        for i, v in enumerate(vs):
            assert np.all(batch.dst[batch.slice_of(i)] == v)

    def test_pal_default_names_discovers_columns(self):
        """GraphPAL declares no column_dtypes; names=None must still
        surface the columns its partitions carry."""
        rng = np.random.default_rng(4)
        n, e = 100, 500
        src = rng.integers(0, n, e)
        dst = rng.integers(0, n, e)
        w = (src * 3 + dst).astype(np.float32)
        g = GraphPAL.from_edges(src, dst, n_partitions=4, max_id=n - 1,
                                columns={"w": w})
        batch = g.storage_engine().edge_columns_batch(np.arange(0, n, 5))
        assert "w" in batch.columns
        assert batch.columns["w"].dtype == np.float32
        np.testing.assert_allclose(
            batch.columns["w"],
            (batch.src * 3 + batch.dst).astype(np.float32))


class TestEngineGenericQueries:
    """FoF / BFS / shortest-path produce identical answers through the
    engine on both backends."""

    def test_fof_backends_agree(self, live_lsm):
        t, src, dst, deleted = live_lsm
        s, d = t.to_coo()
        g = GraphPAL.from_edges(s, d, n_partitions=16, max_id=10_000 - 1)
        for v in [0, 7, 1234]:
            a = friends_of_friends(t, v)
            b = friends_of_friends(g, v)
            assert np.array_equal(np.sort(a), np.sort(b)), v

    def test_bfs_backends_agree(self, live_lsm):
        t, *_ = live_lsm
        s, d = t.to_coo()
        g = GraphPAL.from_edges(s, d, n_partitions=16, max_id=10_000 - 1)
        v = int(s[0])
        assert bfs(t, v, max_depth=3) == bfs(g, v, max_depth=3)

    def test_shortest_path_on_engine(self):
        g = GraphPAL.from_edges([0, 1, 2, 3, 0], [1, 2, 3, 4, 9],
                                n_partitions=2, max_id=9)
        eng = as_engine(g)
        assert shortest_path(eng, 0, 4, max_depth=5) == 4
        assert shortest_path(eng, 0, 9, max_depth=5) == 1
        assert shortest_path(eng, 4, 0, max_depth=5) is None


class TestSnapshot:
    """Acceptance: LSMTree.snapshot() feeds PSW sweeps with results
    identical to the GraphPAL-built DeviceGraph, including edges still
    sitting in buffers."""

    def test_snapshot_bit_identical_to_pal(self):
        t, src, dst, _ = build_live_lsm(n_deletes=0, seed=7)
        assert t.total_buffered() > 0
        g = GraphPAL.from_edges(src, dst, n_partitions=16, max_id=10_000 - 1)
        dg_lsm = t.snapshot()
        dg_pal = build_device_graph(g)
        assert dg_lsm.n_edges == dg_pal.n_edges == src.shape[0]
        for name in ["src", "dst_local", "mask", "outdeg",
                     "send_idx", "edge_owner", "edge_slot"]:
            a = np.asarray(getattr(dg_lsm, name))
            b = np.asarray(getattr(dg_pal, name))
            assert np.array_equal(a, b), name

    def test_snapshot_pagerank_bit_for_bit(self):
        t, src, dst, _ = build_live_lsm(n_deletes=0, seed=8)
        g = GraphPAL.from_edges(src, dst, n_partitions=16, max_id=10_000 - 1)
        r_lsm = np.asarray(pagerank_device(t.snapshot(), n_iters=5))
        r_pal = np.asarray(pagerank_device(build_device_graph(g), n_iters=5))
        assert np.array_equal(r_lsm, r_pal)  # bit-for-bit

    def test_snapshot_respects_tombstones_and_buffers(self):
        t, src, dst, deleted = build_live_lsm(seed=9)
        dg = t.snapshot(with_window_plan=False)
        assert dg.n_edges == t.n_edges  # live edges only, buffers included
        assert t.total_buffered() > 0
        # snapshot is read-only: the store is untouched
        assert t.total_buffered() > 0 and dg.n_edges == t.n_edges

    def test_snapshot_spmm_on_live_store(self):
        """FoF-as-SpMM: one edge-centric sweep of the live store's
        snapshot, in both gather modes, is the dense SpMV of its COO."""
        from repro.core.psw import edge_centric_sweep
        import jax.numpy as jnp

        rng = np.random.default_rng(10)
        n, e = 512, 3000
        src = rng.integers(0, n, e)
        dst = rng.integers(0, n, e)
        iv = IntervalMap.for_capacity(n - 1, 16)
        t = LSMTree(iv, n_levels=2, branching=4, buffer_cap=500,
                    max_partition_edges=1200)
        t.insert_edges(src[:2700], dst[:2700])
        t.insert_edges(src[2700:], dst[2700:])  # < cap: stays buffered
        assert t.total_buffered() > 0
        s, d = t.to_coo()
        assert sorted(zip(s.tolist(), d.tolist())) == \
            sorted(zip(src.tolist(), dst.tolist()))
        dg = t.snapshot()
        x = rng.normal(size=(n, 16)).astype(np.float32)
        # vertex state in the device layout: row = internal id
        rows = np.asarray(iv.to_internal(np.arange(n)), np.int64)
        xi = np.zeros((iv.max_vertices, 16), np.float32)
        xi[rows] = x
        ref = np.zeros((n, 16), np.float64)
        np.add.at(ref, d, x[s].astype(np.float64))
        for mode in ("psw_windows", "dense_gather"):
            out = edge_centric_sweep(
                dg, jnp.asarray(xi.reshape(dg.n_partitions,
                                           dg.interval_len, 16)),
                lambda v: v, mode)
            out = np.asarray(out).reshape(-1, 16)[rows]
            np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)
