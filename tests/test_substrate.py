"""Substrate tests: the synthetic workload generators and incremental
LSM graph checkpoints."""
import numpy as np

from repro.checkpoint import restore_lsm, save_lsm
from repro.core import IntervalMap, LSMTree
from repro.data import (GraphStream, LinkBenchConfig, LinkBenchWorkload,
                        REQUEST_MIX)


class TestData:
    def test_graph_stream_power_law(self):
        gs = GraphStream(10_000, alpha=1.8, seed=0)
        src, dst = gs.next_edges(20_000)
        counts = np.bincount(dst, minlength=10_000)
        # heavy tail: top-1% of vertices should hold a large share
        top = np.sort(counts)[-100:].sum()
        assert top > 0.25 * counts.sum()

    def test_linkbench_mix(self):
        wl = LinkBenchWorkload(LinkBenchConfig(n_vertices=1000, seed=1))
        reqs = list(wl.requests(5000))
        frac = sum(r["op"] == "edge_outnbrs" for r in reqs) / len(reqs)
        assert abs(frac - REQUEST_MIX["edge_outnbrs"]) < 0.05
        src, dst, ts = wl.initial_graph()
        assert src.shape == dst.shape == ts.shape
        assert np.all(np.diff(ts) >= 0)


class TestLSMCheckpoint:
    def test_incremental_graph_checkpoint(self, tmp_path):
        iv = IntervalMap.for_capacity(9999, 8)
        t = LSMTree(iv, n_levels=2, branching=4, buffer_cap=200,
                    max_partition_edges=500)
        rng = np.random.default_rng(0)
        t.insert_edges(rng.integers(0, 10000, 1000), rng.integers(0, 10000, 1000))
        t.flush_all()
        d = str(tmp_path / "g")
        m1 = save_lsm(t, d)
        # second save with no changes: everything reused
        m2 = save_lsm(t, d)
        assert m2["written"] == 0 and m2["reused"] > 0
        # modify a little -> only touched partitions rewritten
        t.insert_edges(rng.integers(0, 10000, 300), rng.integers(0, 10000, 300))
        t.flush_all()
        m3 = save_lsm(t, d)
        assert 0 < m3["written"] <= m3["written"] + m3["reused"]

        t2 = restore_lsm(d)
        assert t2.n_edges == t.n_edges
        v = int(rng.integers(0, 10000))
        np.testing.assert_array_equal(np.sort(t.out_neighbors(v)),
                                      np.sort(t2.out_neighbors(v)))
