"""Service-tier tests (ISSUE 4): snapshot isolation vs a live writer,
background maintenance, crash recovery of every mutation type, WAL
segment rotation/compaction, backpressure."""
import os
import shutil
import threading

import numpy as np
import pytest

from repro.core import (
    GraphDB,
    IntervalMap,
    LSMTree,
    ServiceDB,
    Snapshot,
)
from repro.core.query import bfs, friends_of_friends


def make_service(tmp_path, name="db", **kw):
    opts = dict(max_id=9999, n_partitions=16, n_levels=3, branching=4,
                buffer_cap=2000, max_partition_edges=8000,
                persist_min_edges=512, wal_segment_bytes=64 << 10,
                checkpoint_interval_ops=10 ** 9)
    opts.update(kw)
    return ServiceDB.create(str(tmp_path / name), **opts)


def ref_tree(column_dtypes=None):
    iv = IntervalMap.for_capacity(9999, 16)
    return LSMTree(iv, n_levels=3, branching=4, buffer_cap=2000,
                   max_partition_edges=8000, column_dtypes=column_dtypes or {})


def coo_sorted(g):
    return sorted(zip(*map(list, g.to_coo())))


def apply_ops(tree, ops):
    """Serial replay of a recorded op list into a plain RAM tree."""
    for op in ops:
        if op[0] == "insert":
            tree.insert_edges(op[1], op[2], columns=op[3])
        elif op[0] == "delete":
            tree.delete_edge(op[1], op[2])
        else:
            tree.update_edge_column(op[1], op[2], op[3], op[4])


class TestSnapshotIsolation:
    def test_snapshot_pinned_through_delete_compaction_gc(self, tmp_path):
        """The acceptance scenario: a snapshot opened BEFORE a
        delete + compaction + checkpoint-GC + WAL-rotation cycle still
        answers every query identically to a serial replay of its pinned
        prefix, while the store's on-disk WAL bytes shrink."""
        svc = make_service(tmp_path, column_dtypes={"w": np.float32},
                           wal_segment_bytes=8 << 10)
        rng = np.random.default_rng(0)
        src = rng.integers(0, 10000, 30000)
        dst = rng.integers(0, 10000, 30000)
        w = rng.random(30000).astype(np.float32)
        svc.insert_edges(src, dst, columns={"w": w})
        wal_peak = svc.tree.wal.on_disk_bytes()
        snap = svc.begin_snapshot()

        # writer churns: more inserts, deletes, column writes, checkpoints
        s2 = rng.integers(0, 10000, 20000)
        d2 = rng.integers(0, 10000, 20000)
        svc.insert_edges(s2, d2, columns={"w": np.ones(20000, np.float32)})
        for i in range(100):
            svc.delete_edge(int(src[i]), int(dst[i]))
        svc.update_edge_column(int(src[200]), int(dst[200]), "w", -1.0)
        svc.checkpoint()  # persists, GCs store files, compacts WAL segments
        svc.checkpoint()
        assert svc.tree.wal.on_disk_bytes() < wal_peak, \
            "WAL compaction never reclaimed bytes"

        # serial replay reference: exactly the ops before the pin
        ref = ref_tree({"w": np.float32})
        ref.insert_edges(src, dst, columns={"w": w})
        assert coo_sorted(snap) == coo_sorted(ref)
        eng, reng = snap.storage_engine(), ref.storage_engine()
        vs = [int(v) for v in np.unique(src)[:40]]
        a = eng.edge_columns_batch(vs, names=["w"])
        b = reng.edge_columns_batch(vs, names=["w"])
        for i in range(len(vs)):
            sa, sb = a.slice_of(i), b.slice_of(i)
            assert sorted(zip(a.dst[sa].tolist(),
                              a.columns["w"][sa].tolist())) == \
                sorted(zip(b.dst[sb].tolist(), b.columns["w"][sb].tolist()))
        for v in vs[:10]:
            assert np.array_equal(np.sort(snap.out_neighbors(v)),
                                  np.sort(ref.out_neighbors(v)))
        snap.release()
        assert not os.path.exists(snap.dir)
        svc.close()

    def test_snapshot_sees_unflushed_buffers_deletes_and_columns(self, tmp_path):
        """The pin covers state that exists ONLY in buffers/WAL (nothing
        checkpointed yet): inserts with columns, a delete, a column
        write."""
        svc = make_service(tmp_path, column_dtypes={"w": np.float32},
                           maintenance=False, buffer_cap=10 ** 9)
        svc.insert_edges([1, 2, 3], [4, 5, 6],
                         columns={"w": np.asarray([1., 2., 3.], np.float32)})
        svc.delete_edge(2, 5)
        svc.update_edge_column(3, 6, "w", 7.5)
        snap = svc.begin_snapshot()
        assert coo_sorted(snap) == sorted([(1, 4), (3, 6)])
        batch = snap.storage_engine().edge_columns_batch([3], names=["w"])
        assert batch.columns["w"].tolist() == [7.5]
        svc.close()

    def test_snapshot_reopen_across_sessions(self, tmp_path):
        svc = make_service(tmp_path)
        rng = np.random.default_rng(1)
        svc.insert_edges(rng.integers(0, 10000, 5000),
                         rng.integers(0, 10000, 5000))
        snap = svc.begin_snapshot()
        ref = coo_sorted(snap)
        path = snap.dir
        snap.close()
        # a second opener (another thread/process would do the same)
        again = Snapshot.open(path)
        assert coo_sorted(again) == ref
        svc.close()

    def test_snapshot_ids_survive_service_reopen(self, tmp_path):
        """Regression: the session counter restarts per instance, so a
        reopened ServiceDB used to collide with a still-live session dir
        from the previous instance (FileExistsError)."""
        svc = make_service(tmp_path)
        svc.insert_edges([1, 2], [3, 4])
        snap = svc.begin_snapshot()  # NOT released: the dir stays
        svc.close()
        svc2 = ServiceDB.open(str(tmp_path / "db"))
        snap2 = svc2.begin_snapshot()
        assert snap2.dir != snap.dir
        assert coo_sorted(snap2) == coo_sorted(snap)
        svc2.close()

    def test_snapshot_requires_durability(self, tmp_path):
        db = GraphDB.create(str(tmp_path / "nd"), max_id=999, durable=False)
        with pytest.raises(ValueError):
            ServiceDB(db)


class TestConcurrentStress:
    def test_writers_vs_snapshot_readers(self, tmp_path):
        """Writer thread interleaves inserts and deletes while the main
        thread pins snapshots at arbitrary moments and runs FoF/BFS on
        them. Every snapshot must equal the serial replay of exactly the
        ops applied before its pin (the op log and the WAL are appended
        under the same lock, so the log prefix at pin time IS the pinned
        prefix; backpressure is disabled because its condition-wait
        releases the outer lock mid-append, which would unlink them)."""
        svc = make_service(tmp_path, buffer_cap=1000,
                           backpressure_edges=10 ** 9)
        rng = np.random.default_rng(2)
        n_rounds = 60
        batches = [
            (rng.integers(0, 10000, 200), rng.integers(0, 10000, 200))
            for _ in range(n_rounds)
        ]
        log = []
        stop = threading.Event()

        def writer():
            for bi, (s, d) in enumerate(batches):
                with svc._lock:
                    svc.insert_edges(s, d)
                    log.append(("insert", s, d, None))
                if bi % 3 == 2:  # delete something known to exist
                    s0, d0 = int(s[0]), int(d[0])
                    # lock ORDER: the delete's merge slot before the
                    # service lock (matching ServiceDB.delete_edge, which
                    # re-acquires both reentrantly)
                    with svc._merge_slot_of(d0), svc._lock:
                        svc.delete_edge(s0, d0)
                        log.append(("delete", s0, d0))
            stop.set()

        t = threading.Thread(target=writer)
        t.start()
        checked = 0
        try:
            # keep pinning until the writer is done AND we verified at
            # least a few mid-stream snapshots (post-stop pins are still
            # meaningful: they cover the full log)
            while not stop.is_set() or checked < 4:
                with svc._lock:
                    snap = svc.begin_snapshot()
                    prefix = list(log)
                ref = ref_tree()
                apply_ops(ref, prefix)
                assert coo_sorted(snap) == coo_sorted(ref)
                if prefix:
                    v = int(prefix[0][1][0])
                    assert np.array_equal(
                        np.sort(friends_of_friends(snap.storage_engine(), v)),
                        np.sort(friends_of_friends(ref.storage_engine(), v)))
                    assert bfs(snap.storage_engine(), v, max_depth=2) == \
                        bfs(ref.storage_engine(), v, max_depth=2)
                snap.release()
                checked += 1
        finally:
            t.join()
            svc.close()
        assert checked >= 4
        assert svc.stats.flushes > 0, "maintenance thread never drained"

    def test_maintenance_death_surfaces_to_writers(self, tmp_path):
        """If the maintenance thread dies (e.g. disk full mid-persist),
        writers must get the error instead of hanging forever in the
        backpressure wait."""
        svc = make_service(tmp_path, buffer_cap=100, backpressure_edges=300)

        def boom(j):
            raise OSError("simulated ENOSPC")

        # drain_buffer is the first step of BOTH the serial flush and the
        # pipelined flush job — patching it kills either maintenance mode
        svc.tree.drain_buffer = boom
        rng = np.random.default_rng(9)
        with pytest.raises((RuntimeError, OSError)):
            for _ in range(50):  # cross the cap, then observe the death
                svc.insert_edges(rng.integers(0, 10000, 100),
                                 rng.integers(0, 10000, 100))
        assert svc.maintenance_error is not None
        del svc.tree.drain_buffer
        svc.maintenance_error = None  # cleared: allow the closing checkpoint
        svc.close()

    def test_backpressure_bounds_dirty_set(self, tmp_path):
        svc = make_service(tmp_path, buffer_cap=500, backpressure_edges=2000)
        rng = np.random.default_rng(3)
        peak = 0
        for _ in range(40):
            svc.insert_edges(rng.integers(0, 10000, 400),
                             rng.integers(0, 10000, 400))
            peak = max(peak, svc.tree.total_buffered())
        # one in-flight batch may overshoot the bound before the wait
        assert peak <= 2000 + 400
        assert svc.stats.flushes > 0
        n = svc.n_edges
        svc.close()
        assert GraphDB.open(svc.db.dir).n_edges == n == 16000


class TestCrashRecovery:
    def test_crash_during_background_compaction(self, tmp_path):
        """Freeze the store mid-maintenance (lock held = the only instant a
        copy is consistent the way a kill is) with a half-written manifest
        lying around; recovery must reproduce the exact live state."""
        svc = make_service(tmp_path, buffer_cap=800,
                           checkpoint_interval_ops=3000)
        rng = np.random.default_rng(4)
        for _ in range(15):  # keep maintenance busy: flushes + checkpoints
            svc.insert_edges(rng.integers(0, 10000, 1000),
                             rng.integers(0, 10000, 1000))
        for i in range(20):
            svc.delete_edge(int(rng.integers(0, 10000)),
                            int(rng.integers(0, 10000)))
        with svc._lock:  # simulated kill: snapshot the dir at a WAL boundary
            svc.tree.wal_flush(fsync=False)
            live = coo_sorted(svc.tree)
            with open(str(tmp_path / "db" / (GraphDB.MANIFEST + ".tmp")),
                      "w") as f:
                f.write('{"config": TRUNCATED')  # torn manifest next to real
            crash = str(tmp_path / "crash")
            shutil.copytree(str(tmp_path / "db"), crash)
        svc.close()
        db2 = GraphDB.open(crash)
        assert coo_sorted(db2) == live
        assert svc.stats.flushes > 0 or svc.stats.checkpoints > 0

    def test_buffered_columns_survive_crash(self, tmp_path):
        """Regression (ROADMAP "Columns in the WAL"): attribute columns
        buffered since the last checkpoint — plus deletes and in-place
        column writes — must replay from the WAL. The old WAL dropped all
        of them (it only recorded src/dst/etype)."""
        svc = make_service(tmp_path, column_dtypes={"w": np.float32},
                           maintenance=False, buffer_cap=10 ** 9)
        rng = np.random.default_rng(5)
        src = rng.integers(0, 10000, 3000)
        dst = rng.integers(0, 10000, 3000)
        w1 = rng.random(3000).astype(np.float32)
        svc.insert_edges(src, dst, columns={"w": w1})
        svc.checkpoint()
        # post-checkpoint, pre-flush: lives only in buffers + WAL
        s2 = rng.integers(0, 10000, 2000)
        d2 = rng.integers(0, 10000, 2000)
        w2 = (rng.random(2000) + 5).astype(np.float32)
        svc.insert_edges(s2, d2, columns={"w": w2})
        svc.delete_edge(int(src[0]), int(dst[0]))
        svc.update_edge_column(int(src[1]), int(dst[1]), "w", 99.5)
        svc.tree.wal_flush(fsync=False)
        crash = str(tmp_path / "crash")
        shutil.copytree(str(tmp_path / "db"), crash)  # kill before any flush

        db2 = GraphDB.open(crash)
        ref = ref_tree({"w": np.float32})
        ref.insert_edges(src, dst, columns={"w": w1})
        ref.insert_edges(s2, d2, columns={"w": w2})
        ref.delete_edge(int(src[0]), int(dst[0]))
        ref.update_edge_column(int(src[1]), int(dst[1]), "w", 99.5)
        assert coo_sorted(db2) == coo_sorted(ref)
        eng, reng = db2.storage_engine(), ref.storage_engine()
        vs = [int(v) for v in np.unique(np.concatenate([src[:30], s2[:30]]))]
        a = eng.edge_columns_batch(vs, names=["w"])
        b = reng.edge_columns_batch(vs, names=["w"])
        for i in range(len(vs)):
            sa, sb = a.slice_of(i), b.slice_of(i)
            assert sorted(zip(a.dst[sa].tolist(),
                              a.columns["w"][sa].tolist())) == \
                sorted(zip(b.dst[sb].tolist(), b.columns["w"][sb].tolist()))
        svc.close()


class TestSaveLsm:
    def test_save_lsm_captures_live_buffers(self, tmp_path):
        """checkpoint/manager satellite: save_lsm on a store with unflushed
        buffers restores them, columns included (the old checkpoints
        silently dropped everything after the last flush)."""
        from repro.checkpoint.manager import restore_lsm, save_lsm
        svc = make_service(tmp_path, column_dtypes={"w": np.float32},
                           maintenance=False, buffer_cap=10 ** 9)
        rng = np.random.default_rng(6)
        src = rng.integers(0, 10000, 20000)
        dst = rng.integers(0, 10000, 20000)
        w = rng.random(20000).astype(np.float32)
        svc.insert_edges(src[:15000], dst[:15000], columns={"w": w[:15000]})
        svc.checkpoint()
        svc.insert_edges(src[15000:], dst[15000:], columns={"w": w[15000:]})
        assert svc.tree.total_buffered() > 0
        ck = str(tmp_path / "ckpt")
        m = save_lsm(svc.db, ck)
        assert m.get("buffers") == "buffers.npz"
        t2 = restore_lsm(ck)
        assert coo_sorted(t2) == coo_sorted(svc.tree)
        # buffered columns came back, not zeros
        eng, reng = t2.storage_engine(), svc.db.storage_engine()
        vs = [int(v) for v in np.unique(src[15000:])[:20]]
        a = eng.edge_columns_batch(vs, names=["w"])
        b = reng.edge_columns_batch(vs, names=["w"])
        for i in range(len(vs)):
            sa, sb = a.slice_of(i), b.slice_of(i)
            assert sorted(zip(a.dst[sa].tolist(),
                              a.columns["w"][sa].tolist())) == \
                sorted(zip(b.dst[sb].tolist(), b.columns["w"][sb].tolist()))
        svc.close()
