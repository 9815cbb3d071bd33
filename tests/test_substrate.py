"""Substrate tests: optimizer, compression, data pipeline, checkpoints,
fault-tolerant resume."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, restore_lsm, save_lsm
from repro.core import IntervalMap, LSMTree
from repro.data import (GraphStream, LinkBenchConfig, LinkBenchWorkload,
                        REQUEST_MIX, TokenStream, TokenStreamConfig)
from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                         compressed_psum_tree, ef_compress, ef_decompress,
                         global_norm, linear_warmup_cosine)


class TestAdamW:
    def test_converges_on_quadratic(self):
        params = {"w": jnp.asarray([5.0, -3.0])}
        state = adamw_init(params)
        cfg = AdamWConfig(lr=0.1, weight_decay=0.0)

        def loss(p):
            return jnp.sum((p["w"] - 1.0) ** 2)

        for _ in range(200):
            g = jax.grad(loss)(params)
            params, state, _ = adamw_update(g, state, params, cfg)
        np.testing.assert_allclose(np.asarray(params["w"]), [1.0, 1.0], atol=1e-2)

    def test_clip_and_metrics(self):
        params = {"w": jnp.ones(4)}
        state = adamw_init(params)
        cfg = AdamWConfig(lr=1e-3, clip_norm=0.5)
        g = {"w": jnp.full(4, 100.0)}
        _, _, metrics = adamw_update(g, state, params, cfg)
        assert float(metrics["grad_norm"]) == pytest.approx(200.0)

    def test_schedule_warmup(self):
        sched = linear_warmup_cosine(10, 100)
        assert float(sched(jnp.asarray(0))) == 0.0
        assert float(sched(jnp.asarray(10))) == pytest.approx(1.0, abs=1e-3)
        assert float(sched(jnp.asarray(100))) < 0.6


class TestCompression:
    def test_error_feedback_reduces_bias(self):
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=(1000,)).astype(np.float32))
        r = jnp.zeros_like(g)
        # repeated compression of the same gradient: error feedback makes the
        # RUNNING SUM converge to the true sum (bounded bias)
        total = jnp.zeros_like(g)
        for i in range(20):
            q, s, r = ef_compress(g, r)
            total = total + ef_decompress(q, s)
        np.testing.assert_allclose(np.asarray(total / 20), np.asarray(g),
                                   atol=float(jnp.abs(g).max()) / 127)

    def test_compressed_psum_shardmap(self):
        # 1-device mesh still exercises the shard_map plumbing
        from jax.sharding import Mesh
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
        g = {"w": jnp.arange(8.0)}
        r = {"w": jnp.zeros(8)}

        def f(g, r):
            return compressed_psum_tree(g, r, "dp")

        out, _ = shard_map(f, mesh=mesh, in_specs=(P(), P()),
                           out_specs=(P(), P()))(g, r)
        np.testing.assert_allclose(np.asarray(out["w"]), np.arange(8.0),
                                   atol=7.0 / 127)


class TestData:
    def test_token_stream_deterministic_random_access(self):
        ts = TokenStream(TokenStreamConfig(vocab_size=100, batch=4, seq_len=16,
                                           seed=3))
        b7a = ts.batch_at(7)
        b7b = ts.batch_at(7)
        np.testing.assert_array_equal(b7a["tokens"], b7b["tokens"])
        assert b7a["tokens"].shape == (4, 16)
        assert b7a["tokens"].max() < 100
        # labels are next-token shifted
        assert not np.array_equal(ts.batch_at(8)["tokens"], b7a["tokens"])

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


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        tree = {"a": jnp.arange(5.0), "b": {"c": jnp.ones((2, 3), jnp.bfloat16)}}
        mgr.save(10, tree)
        out, step = mgr.restore(tree)
        assert step == 10
        np.testing.assert_array_equal(np.asarray(out["a"]), np.arange(5.0))
        assert out["b"]["c"].dtype == jnp.bfloat16

    def test_keep_policy_and_latest(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        tree = {"x": jnp.zeros(2)}
        for s in [1, 2, 3]:
            mgr.save(s, jax.tree.map(lambda x: x + s, tree))
        assert mgr.latest_step() == 3
        files = [f for f in os.listdir(tmp_path) if f.endswith(".npz")]
        assert len(files) == 2  # step 1 evicted
        out, _ = mgr.restore(tree, step=2)
        np.testing.assert_array_equal(np.asarray(out["x"]), [2.0, 2.0])

    def test_async_save(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(5, {"x": jnp.ones(3)}, blocking=False)
        mgr.wait()
        out, step = mgr.restore({"x": jnp.zeros(3)})
        assert step == 5

    def test_crash_mid_save_leaves_previous_intact(self, tmp_path):
        """A leftover .tmp file (simulated crash) must not break restore."""
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, {"x": jnp.ones(2)})
        # simulate crash: partial tmp file for step 2
        with open(os.path.join(tmp_path, "step_0000000002.npz.tmp"), "wb") as f:
            f.write(b"garbage")
        out, step = mgr.restore({"x": jnp.zeros(2)})
        assert step == 1

    def test_resume_training_bit_identical(self, tmp_path):
        """Train 10 steps straight vs train 5 + checkpoint + restore + 5:
        identical parameters — the fault-tolerance contract."""
        cfg = AdamWConfig(lr=0.05, weight_decay=0.01)
        ts = TokenStream(TokenStreamConfig(vocab_size=13, batch=2, seq_len=4))

        def make():
            p = {"w": jnp.ones((13, 13))}
            return p, adamw_init(p)

        def loss(p, batch):
            logits = p["w"][batch["tokens"].reshape(-1)]
            logz = jax.scipy.special.logsumexp(logits, -1)
            gold = jnp.take_along_axis(
                logits, batch["labels"].reshape(-1)[:, None], -1)[:, 0]
            return (logz - gold).mean()

        def step_fn(p, s, i):
            g = jax.grad(loss)(p, ts.batch_at(i))
            return adamw_update(g, s, p, cfg)[:2]

        p1, s1 = make()
        for i in range(10):
            p1, s1 = step_fn(p1, s1, i)

        p2, s2 = make()
        for i in range(5):
            p2, s2 = step_fn(p2, s2, i)
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(5, {"params": p2, "opt": s2})
        restored, rstep = mgr.restore({"params": p2, "opt": s2})
        p3, s3 = restored["params"], restored["opt"]
        for i in range(rstep, 10):
            p3, s3 = step_fn(p3, s3, i)
        np.testing.assert_array_equal(np.asarray(p1["w"]), np.asarray(p3["w"]))


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
