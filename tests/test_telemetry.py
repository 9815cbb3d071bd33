"""Unified telemetry tests (ISSUE 9): registry primitives (per-thread
cells, labels, catalog enforcement), exact histogram merging, span
context propagation (nesting, attach, Chrome-trace export), the
ServiceDB integration (instrumented WAL/manifest/service paths, legacy
stats shims, metric-derived health), and a thread-safety regression for
snapshot-vs-writer races.

The registry is process-global, so every assertion on counters is a
DELTA between two snapshots — other tests in the same process may have
instrumented work of their own.
"""
import json
import threading

import numpy as np
import pytest

from repro.core import ServiceDB, tail_cache_stats
from repro.core import telemetry
from repro.core.telemetry import (
    MetricsRegistry,
    merge_snapshots,
)


def _counter_total(snap, name):
    v = snap["counters"].get(name, 0)
    if isinstance(v, dict):
        return sum(v.values())
    return v


def make_service(tmp_path, name="db", **kw):
    opts = dict(max_id=9999, n_partitions=16, n_levels=3, branching=4,
                buffer_cap=2000, max_partition_edges=8000,
                persist_min_edges=512, wal_segment_bytes=64 << 10,
                checkpoint_interval_ops=10 ** 9)
    opts.update(kw)
    return ServiceDB.create(str(tmp_path / name), **opts)


# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_counter_sums_across_threads(self):
        r = MetricsRegistry()
        c = r.counter("x.threads")
        n_threads, per = 8, 1000

        def worker():
            for _ in range(per):
                c.inc()

        ts = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value() == n_threads * per
        assert r.snapshot()["counters"]["x.threads"] == n_threads * per

    def test_labeled_counter(self):
        r = MetricsRegistry()
        c = r.counter("x.labeled")
        c.inc(3, label="a")
        c.inc(label="b")
        c.inc(5)  # unlabeled remainder folds under ""
        assert c.value() == {"a": 3, "b": 1, "": 5}

    def test_catalog_enforced(self):
        r = MetricsRegistry()
        with pytest.raises(KeyError):
            r.counter("not.a.real.metric")  # lint: phantom-ok
        # a catalog name used with the wrong kind is a unit bug
        with pytest.raises(KeyError):
            r.counter("wal.append.seconds")
        # the escape prefix is caller-owned
        r.counter("x.anything.goes").inc()
        with pytest.raises(KeyError):
            with telemetry.span("not.a.span"):  # lint: phantom-ok
                pass

    def test_gauge_last_write_wins(self):
        r = MetricsRegistry()
        g = r.gauge("x.gauge")
        g.set(7)
        g.set(3)
        assert g.value() == 3

    def test_kill_switch(self):
        r = MetricsRegistry()
        c = r.counter("x.killed")
        telemetry.set_enabled(False)
        try:
            c.inc()
            with telemetry.span("x.killed.span") as sp:
                assert sp.trace is None  # the null handle
            assert c.value() == 0
        finally:
            telemetry.set_enabled(True)
        c.inc()
        assert c.value() == 1

    def test_register_stats_sums_live_instances(self):
        class Bag:
            def __init__(self, n):
                self.hits = n

        r = MetricsRegistry()
        a, b = Bag(3), Bag(4)
        r.register_stats(a, {"hits": "x.bag.hits"})
        r.register_stats(b, {"hits": "x.bag.hits"})
        assert r.snapshot()["counters"]["x.bag.hits"] == 7
        del b  # dead refs are pruned, their contribution disappears
        assert r.snapshot()["counters"]["x.bag.hits"] == 3


# ---------------------------------------------------------------------------
# histograms + exact merge
# ---------------------------------------------------------------------------
class TestHistogram:
    def test_count_sum_percentiles(self):
        r = MetricsRegistry()
        h = r.histogram("x.lat")
        for s in (0.001, 0.001, 0.002, 0.010):
            h.observe(s)
        v = h.value()[""]
        assert v["count"] == 4
        assert v["sum"] == pytest.approx(0.014)
        # p50 falls in the 1ms bucket; upper bounds are powers of two in us
        assert 1000 <= v["p50_us"] <= 2100
        assert v["p99_us"] >= v["p50_us"]

    def test_merge_is_exact(self):
        """merge_snapshots(two halves) == one registry seeing everything."""
        rng = np.random.default_rng(11)
        samples = rng.exponential(0.002, 400)
        r1, r2, ref = (MetricsRegistry() for _ in range(3))
        for i, s in enumerate(samples):
            (r1 if i % 2 else r2).histogram("x.lat").observe(s, label="l")
            ref.histogram("x.lat").observe(s, label="l")
        merged = merge_snapshots([r1.snapshot(), r2.snapshot()])
        got = merged["histograms"]["x.lat"]["l"]
        want = ref.snapshot()["histograms"]["x.lat"]["l"]
        assert got["buckets"] == want["buckets"]
        assert got["count"] == want["count"]
        assert got["sum"] == pytest.approx(want["sum"])
        assert got["p99_us"] == want["p99_us"]

    def test_merge_counters_scalar_and_labeled(self):
        a = {"pid": 1, "counters": {"x.c": 2, "x.d": {"k": 1}},
             "gauges": {}, "histograms": {}}
        b = {"pid": 2, "counters": {"x.c": 3, "x.d": 4},
             "gauges": {"x.g": 9}, "histograms": {}}
        m = merge_snapshots([a, b])
        assert m["counters"]["x.c"] == 5
        assert m["counters"]["x.d"] == {"k": 1, "": 4}
        assert m["gauges"]["x.g"] == 9
        assert m["pids"] == [1, 2]


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
class TestSpans:
    def test_nesting_shares_trace(self):
        with telemetry.span("x.outer") as outer:
            with telemetry.span("x.inner") as inner:
                assert inner.trace == outer.trace
                assert inner.parent == outer.span
        evs = telemetry.trace_events()
        by_name = {e["name"]: e for e in evs[-2:]}
        assert by_name["x.inner"]["args"]["parent"] == outer.span
        assert by_name["x.outer"]["args"]["trace"] == outer.trace

    def test_attach_joins_remote_trace(self):
        """The cross-process stitch, in miniature: a context exported on
        one thread re-establishes the same trace on another."""
        got = {}

        def remote(ctx):
            with telemetry.attach(ctx):
                with telemetry.span("x.remote") as sp:
                    got["trace"], got["parent"] = sp.trace, sp.parent

        with telemetry.span("x.root") as root:
            ctx = telemetry.current_context()
            t = threading.Thread(target=remote, args=(ctx,))
            t.start()
            t.join()
        assert got["trace"] == root.trace
        assert got["parent"] == root.span
        # attach(None) is a no-op, not an error
        with telemetry.attach(None):
            pass

    def test_chrome_trace_document(self, tmp_path):
        with telemetry.span("x.export", flavor="test") as sp:
            sp.tag(extra=1)
        out = tmp_path / "trace.json"
        doc = telemetry.trace_export(path=str(out))
        json.dumps(doc)  # loadable = serializable + right envelope
        assert doc["traceEvents"]
        ev = next(e for e in reversed(doc["traceEvents"])
                  if e["name"] == "x.export")
        assert ev["ph"] == "X" and ev["cat"] == "graphdb"
        for field in ("ts", "dur", "pid", "tid"):
            assert isinstance(ev[field], int)
        assert ev["args"]["flavor"] == "test"
        assert ev["args"]["extra"] == 1
        assert ev["args"]["trace"] == sp.trace
        assert json.loads(out.read_text())["displayTimeUnit"] == "ms"


# ---------------------------------------------------------------------------
# spans on the profiler's host plane
# ---------------------------------------------------------------------------
PROGRAM_PREFIXES = ("multihop.", "psw.")
HOP_CHILDREN = ("multihop.probe", "multihop.kernel.prep",
                "multihop.kernel.wait", "multihop.merge")


def _tiny_store():
    from repro.core import IntervalMap, LSMTree, dense_plan
    rng = np.random.default_rng(5)
    t = LSMTree(IntervalMap.for_capacity(199, 16), n_levels=3, branching=4,
                buffer_cap=400, max_partition_edges=800,
                column_dtypes={"w": np.float32})
    t.insert_edges(rng.integers(0, 200, 3000), rng.integers(0, 200, 3000),
                   columns={"w": rng.random(3000, dtype=np.float32)})
    # held: hops and relax rounds over the threshold run on the device
    dense_plan(t, "out")
    dense_plan(t, "out", weight="w")
    return t


def _run_khop(t):
    from repro.core import khop
    res = khop(t, [3], 6)
    assert len(res.levels) > 2


def _run_sssp(t):
    from repro.core import sssp
    assert np.isfinite(sssp(t, 3)).sum() > 100


RELAX_CHILDREN = ("multihop.probe", "multihop.kernel.prep",
                  "multihop.kernel.wait")


def _run_pagerank(t):
    from repro.core.psw import build_device_graph, pagerank_device
    pagerank_device(build_device_graph(t), n_iters=2).block_until_ready()


def _profiled(tmp_path, fn):
    """Run `fn` under the JAX profiler: the program spans in the ring,
    and the program's events on the host plane of the recorded xplane."""
    import jax
    from jax.profiler import ProfileData
    telemetry.trace_events(clear=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    ring = [e["name"] for e in telemetry.trace_events(clear=True)
            if e["name"].startswith(PROGRAM_PREFIXES)]
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    host = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(PROGRAM_PREFIXES)]
    return ring, host


class TestProfilerSpans:
    @pytest.mark.parametrize("on", [True, False])
    def test_span_opens_one_annotation_of_its_name(self, monkeypatch, on):
        opened = []

        class Recorder:
            def __init__(self, name, **kw):
                assert not kw   # the name only: tags stay on the ring
                self.name = name

            def __enter__(self):
                opened.append(("enter", self.name))

            def __exit__(self, *exc):
                opened.append(("exit", self.name))

        monkeypatch.setattr(telemetry, "_TRACE_ANNOTATION", Recorder)
        telemetry.set_enabled(on)
        try:
            with telemetry.span("x.outer", tag=1):
                with telemetry.span("x.inner"):
                    pass
        finally:
            telemetry.set_enabled(True)
        want = [("enter", "x.outer"), ("enter", "x.inner"),
                ("exit", "x.inner"), ("exit", "x.outer")]
        assert opened == (want if on else [])

    @pytest.mark.parametrize("run,spans,parents", [
        (_run_khop, {"multihop.hop", *HOP_CHILDREN},
         {c: "multihop.hop" for c in HOP_CHILDREN}),
        (_run_pagerank, {"psw.pagerank"}, {}),
        (_run_sssp, {"multihop.relax", *RELAX_CHILDREN},
         {c: "multihop.relax" for c in RELAX_CHILDREN}),
    ], ids=["khop", "pagerank", "sssp"])
    def test_program_spans_land_on_the_host_plane(self, tmp_path, run,
                                                  spans, parents):
        t = _tiny_store()
        ring, host = _profiled(tmp_path, lambda: run(t))
        names = [n for _, _, n in host]
        # as many events on the host plane as the ring holds, by name
        assert sorted(names) == sorted(ring)
        assert set(names) == spans
        # nested as in the code: each child lies inside a parent event
        for a, b, n in host:
            if n in parents:
                assert any(pa <= a and b <= pb
                           for pa, pb, pn in host if pn == parents[n]), n


# ---------------------------------------------------------------------------
# ServiceDB integration
# ---------------------------------------------------------------------------
class TestServiceIntegration:
    def test_instrumented_paths_record(self, tmp_path):
        before = telemetry.snapshot()
        svc = make_service(tmp_path)
        rng = np.random.default_rng(0)
        src = rng.integers(0, 10000, 6000)
        dst = rng.integers(0, 10000, 6000)
        svc.insert_edges(src, dst)
        svc.checkpoint()
        with svc.read_view() as view:
            view.storage_engine().out_neighbors_batch(
                np.unique(src[:256]))
        sess = svc.begin_snapshot()  # bumps the legacy ServiceStats bag
        sess.release()
        snap = svc.metrics_snapshot()
        for name in ("wal.appends", "wal.append.bytes",
                     "manifest.publishes", "disk.interval.read_edges"):
            assert (_counter_total(snap, name)
                    > _counter_total(before, name)), name
        # collector-backed legacy stats appear in the same snapshot
        assert (_counter_total(snap, "service.snapshots")
                >= svc.stats.snapshots > 0)
        hist = snap["histograms"]["wal.append.seconds"][""]
        assert hist["count"] > 0 and hist["sum"] > 0
        svc.close()

    def test_legacy_stats_shims_unchanged(self, tmp_path):
        """Satellite 1 back-compat: the dataclasses stay plain attribute
        bags — existing callers never see the registry."""
        svc = make_service(tmp_path)
        svc.insert_edges([1, 2, 3], [4, 5, 6])
        svc.checkpoint()
        assert isinstance(svc.stats.flushes, int)
        assert isinstance(svc.db.tree.stats.inserts, int)
        assert svc.db.tree.stats.inserts >= 3
        io = svc.db.io.snapshot()
        assert {"gathers", "block_reads", "bytes_read"} <= set(io)
        tc = tail_cache_stats()
        assert {"hits", "misses"} <= set(tc)
        svc.close()

    def test_prometheus_text(self, tmp_path):
        svc = make_service(tmp_path)
        svc.insert_edges([1], [2])
        text = svc.prometheus_text()
        assert "# TYPE graphdb_wal_appends counter" in text
        assert "graphdb_wal_append_seconds_bucket" in text
        assert 'le="+Inf"' in text
        svc.close()

    def test_health_readiness_fields(self, tmp_path):
        svc = make_service(tmp_path)
        svc.insert_edges([1, 2], [3, 4])
        h = svc.health()
        for key in ("wal_tail_budget_bytes", "wal_tail_ok", "backlog_ok",
                    "backlog_edges", "poisoned_count", "ready"):
            assert key in h, key
        assert h["wal_tail_bytes"] <= h["wal_tail_budget_bytes"]
        assert h["ready"] and h["wal_tail_ok"] and h["backlog_ok"]
        assert h["poisoned_count"] == 0
        # a tiny budget flips readiness without flipping liveness
        svc.wal_tail_budget_bytes = 1
        h2 = svc.health()
        assert not h2["wal_tail_ok"] and not h2["ready"]
        assert h2["maintenance_alive"]
        svc.close()

    def test_snapshot_thread_safe_under_load(self, tmp_path):
        """Regression: concurrent snapshot() readers against a writer and
        live maintenance must neither raise nor observe regressing
        counters (cells only grow; dict iteration must be race-free)."""
        svc = make_service(tmp_path)
        rng = np.random.default_rng(3)
        stop = threading.Event()
        errors = []

        def writer():
            try:
                while not stop.is_set():
                    svc.insert_edges(rng.integers(0, 10000, 500),
                                     rng.integers(0, 10000, 500))
            except Exception as exc:  # pragma: no cover
                errors.append(exc)

        def sampler():
            last = 0
            try:
                while not stop.is_set():
                    snap = telemetry.snapshot()
                    cur = _counter_total(snap, "wal.appends")
                    assert cur >= last, "counter went backwards"
                    last = cur
                    telemetry.prometheus_text()
            except Exception as exc:  # pragma: no cover
                errors.append(exc)

        threads = [threading.Thread(target=writer)] + \
            [threading.Thread(target=sampler) for _ in range(2)]
        for t in threads:
            t.start()
        import time
        time.sleep(1.0)
        stop.set()
        for t in threads:
            t.join()
        assert not errors
        svc.close()
