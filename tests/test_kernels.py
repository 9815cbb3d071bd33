"""Per-kernel allclose tests vs ref.py oracles: shape/dtype sweeps +
hypothesis property tests (interpret mode on CPU)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.embedding_bag import embedding_bag, embedding_bag_ref
from repro.kernels.flash_attention import (attention_ref, flash_attention,
                                           flash_attention_pallas)
from repro.kernels.common import round_up
from repro.kernels.frontier_expand import (build_frontier_plan,
                                           frontier_expand_counts,
                                           frontier_expand_launch,
                                           frontier_expand_np,
                                           frontier_expand_ref, relax_staged,
                                           stage_frontier, upload_plan)
from repro.kernels.frontier_expand.frontier_expand import frontier_expand_pallas
from repro.kernels.psw_spmm import psw_spmm_edges, spmm_dense_ref
from repro.kernels.segment_ell import (segment_ell, segment_ell_from_edges,
                                       segment_ell_ref)


class TestPswSpmm:
    @pytest.mark.parametrize("n,e,f", [(100, 500, 16), (300, 3000, 64),
                                       (513, 4000, 130), (64, 64, 256)])
    def test_matches_edge_oracle(self, n, e, f):
        rng = np.random.default_rng(n + e)
        src = rng.integers(0, n, e)
        dst = rng.integers(0, n, e)
        x = jnp.asarray(rng.normal(size=(n, f)).astype(np.float32))
        out = psw_spmm_edges(src, dst, x, n, block=128)
        ref = spmm_dense_ref(jnp.asarray(src), jnp.asarray(dst), x, n)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_empty_dst_blocks_zeroed(self):
        # all edges target node 0 — other blocks must still be initialized
        src = np.arange(50)
        dst = np.zeros(50, np.int64)
        x = jnp.ones((300, 8), jnp.float32)
        out = psw_spmm_edges(src, dst, x, 300, block=128)
        assert float(jnp.abs(out[1:]).max()) == 0.0
        np.testing.assert_allclose(np.asarray(out[0]), 50.0)

    @given(st.integers(0, 2**31 - 1), st.integers(1, 300),
           st.sampled_from([1, 8, 40, 128]))
    @settings(max_examples=10, deadline=None)
    def test_property_random_graphs(self, seed, e, f):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(2, 400))
        src = rng.integers(0, n, e)
        dst = rng.integers(0, n, e)
        x = jnp.asarray(rng.normal(size=(n, f)).astype(np.float32))
        out = psw_spmm_edges(src, dst, x, n, block=128)
        ref = spmm_dense_ref(jnp.asarray(src), jnp.asarray(dst), x, n)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


class TestSegmentEll:
    @pytest.mark.parametrize("n,k,m,f", [(100, 8, 50, 30), (256, 16, 256, 128),
                                         (33, 5, 20, 200), (128, 1, 10, 128)])
    def test_matches_oracle(self, n, k, m, f):
        rng = np.random.default_rng(n * k)
        idx = jnp.asarray(rng.integers(0, m, (n, k)), jnp.int32)
        mask = jnp.asarray(rng.random((n, k)) < 0.7)
        x = jnp.asarray(rng.normal(size=(m, f)).astype(np.float32))
        out = segment_ell(idx, mask, x)
        ref = segment_ell_ref(idx, mask, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)

    def test_from_edges_matches_spmm(self):
        rng = np.random.default_rng(7)
        n, e, f = 60, 200, 24
        src = rng.integers(0, n, e)
        dst = rng.integers(0, n, e)
        # cap above max in-degree so nothing is dropped
        x = jnp.asarray(rng.normal(size=(n, f)).astype(np.float32))
        out = segment_ell_from_edges(src, dst, x, n, max_degree=e)
        ref = spmm_dense_ref(jnp.asarray(src), jnp.asarray(dst), x, n)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_all_masked(self):
        idx = jnp.zeros((128, 4), jnp.int32)
        mask = jnp.zeros((128, 4), bool)
        x = jnp.ones((8, 128), jnp.float32)
        out = segment_ell(idx, mask, x)
        assert float(jnp.abs(out).max()) == 0.0


class TestFlashAttention:
    @pytest.mark.parametrize("b,s,h,hkv,d", [
        (1, 128, 2, 2, 64), (2, 256, 4, 2, 64), (2, 256, 8, 1, 128),
        (1, 512, 4, 4, 128),
    ])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_oracle(self, b, s, h, hkv, d, causal):
        key = jax.random.PRNGKey(b * s + h)
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)
        out = flash_attention_pallas(q, k, v, causal=causal)
        ref = attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_bf16(self):
        key = jax.random.PRNGKey(0)
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (1, 128, 2, 64), jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, 128, 2, 64), jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, 128, 2, 64), jnp.bfloat16)
        out = flash_attention_pallas(q, k, v, causal=True)
        ref = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                            v.astype(jnp.float32), causal=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), rtol=2e-2, atol=2e-2)

    def test_custom_vjp_grads(self):
        key = jax.random.PRNGKey(1)
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (1, 128, 2, 64), jnp.float32)
        k = jax.random.normal(ks[1], (1, 128, 1, 64), jnp.float32)
        v = jax.random.normal(ks[2], (1, 128, 1, 64), jnp.float32)

        def f(q, k, v):
            return (flash_attention(q, k, v, True) ** 2).sum()

        def f_ref(q, k, v):
            return (attention_ref(q, k, v, True) ** 2).sum()

        g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_cross_attention_longer_kv(self):
        """Decode-ish: S < T (query block over a longer kv history)."""
        key = jax.random.PRNGKey(2)
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (2, 128, 4, 64), jnp.float32)
        k = jax.random.normal(ks[1], (2, 512, 2, 64), jnp.float32)
        v = jax.random.normal(ks[2], (2, 512, 2, 64), jnp.float32)
        out = flash_attention_pallas(q, k, v, causal=False)
        ref = attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestEmbeddingBag:
    @pytest.mark.parametrize("b,k,v,d", [(64, 4, 1000, 32), (128, 16, 500, 64),
                                         (200, 2, 50, 128), (128, 1, 10, 16)])
    @pytest.mark.parametrize("mode", ["sum", "mean"])
    def test_matches_oracle(self, b, k, v, d, mode):
        rng = np.random.default_rng(b + k)
        idx = jnp.asarray(rng.integers(0, v, (b, k)), jnp.int32)
        w = jnp.asarray(rng.random((b, k)).astype(np.float32))
        table = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32))
        out = embedding_bag(idx, w, table, mode=mode)
        ref = embedding_bag_ref(idx, w, table, mode=mode)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=10, deadline=None)
    def test_property_weighted_bags(self, seed):
        rng = np.random.default_rng(seed)
        b = int(rng.integers(1, 80))
        k = int(rng.integers(1, 12))
        v = int(rng.integers(1, 300))
        d = int(rng.integers(1, 100))
        idx = jnp.asarray(rng.integers(0, v, (b, k)), jnp.int32)
        w = jnp.asarray((rng.random((b, k)) < 0.8).astype(np.float32)
                        * rng.random((b, k)).astype(np.float32))
        table = jnp.asarray(rng.normal(size=(v, d)).astype(np.float32))
        out = embedding_bag(idx, w, table)
        ref = embedding_bag_ref(idx, w, table)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


class TestFrontierExpand:
    @pytest.mark.parametrize("n,e,b", [(100, 500, 16), (300, 4000, 130),
                                       (64, 64, 1), (513, 9000, 64)])
    def test_pallas_matches_oracles(self, n, e, b):
        rng = np.random.default_rng(n + e)
        src = rng.integers(0, n, e)
        dst = rng.integers(0, n, e)
        plan = build_frontier_plan(src, dst, n, n, k_slots=8)
        # row budget: virtual rows are linear in edges + touched dsts
        assert plan.idx.shape[0] <= round_up(
            np.unique(dst * n + src).size // 8 + np.unique(dst).size + 1, 128)
        x = rng.random((plan.idx.shape[1] and n, b)).astype(np.float32)
        xp = np.zeros((round_up(n, 128), round_up(b, 128)), np.float32)
        xp[:n, :b] = x
        out = frontier_expand_pallas(jnp.asarray(plan.idx),
                                     jnp.asarray(plan.mask),
                                     jnp.asarray(xp), interpret=True)
        ref = frontier_expand_ref(jnp.asarray(plan.idx),
                                  jnp.asarray(plan.mask), jnp.asarray(xp))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        npo = frontier_expand_np(plan.idx, plan.mask, xp)
        np.testing.assert_allclose(npo, np.asarray(ref), rtol=1e-5, atol=1e-5)

    # B = 1 and 5 fill part of one 128-lane tile, 128 all of it, 130 spills
    # into a second: the launch pads the panel on the device
    @pytest.mark.parametrize("b", [1, 5, 128, 130])
    @pytest.mark.parametrize("use_kernel", [False, True])
    def test_counts_match_dedup_matmul(self, use_kernel, b):
        rng = np.random.default_rng(7)
        n, e = 220, 3000
        src = rng.integers(0, n, e)
        dst = rng.integers(0, n, e)
        plan = build_frontier_plan(src, dst, n, n)
        x = (rng.random((n, b)) < 0.3).astype(np.float32)
        got = frontier_expand_counts(plan, x, use_kernel=use_kernel,
                                     interpret=True)
        a = np.zeros((n, n), np.float32)
        a[dst, src] = 1.0  # dedup: multi-edges count once
        np.testing.assert_allclose(got, a @ x, rtol=1e-5, atol=1e-5)

    # a one-column launch is the flat gather on every backend: it equals
    # the numpy K-loop folded per destination bit for bit, and traces no
    # kernel, whatever use_kernel says
    @pytest.mark.parametrize("case", ["hub", "sparse_slots", "vertex0",
                                      "empty_frontier", "full_frontier"])
    @pytest.mark.parametrize("use_kernel", [False, True])
    def test_one_column_launch_is_the_oracle_bitwise(self, use_kernel, case):
        rng = np.random.default_rng(17)
        n, k = 300, 8
        if case == "hub":
            # every vertex sends to vertex 3, four times over: 300
            # distinct in-neighbours, 38 virtual rows of one destination
            src = np.concatenate([rng.permutation(n).repeat(4),
                                  rng.integers(0, n, 900)])
            dst = np.concatenate([np.full(4 * n, 3),
                                  rng.integers(0, n, 900)])
        else:
            # in-degree about 2 in rows of 32 slots: most slots empty,
            # and padding rows past the last destination
            src, dst = rng.integers(0, n, 600), rng.integers(0, n, 600)
            k = 32
        plan = build_frontier_plan(src, dst, n, n, k_slots=k)
        assert (~plan.mask).any() and (plan.row_dst == n).any()
        x = np.zeros((n, 1), np.float32)
        if case == "full_frontier":
            x[:] = 1.0
        elif case != "empty_frontier":
            x[rng.choice(n, n // 5, replace=False)] = 1.0
        if case == "vertex0":
            # empty slots read x[0] through max(slots, 0) and count 0
            x[0] = 1.0
        got = frontier_expand_counts(plan, x, use_kernel=use_kernel,
                                     interpret=True)
        rows = frontier_expand_np(plan.idx, plan.mask, x)
        want = np.zeros((n + 1, 1), np.float32)
        np.add.at(want, plan.row_dst, rows)
        assert got.shape == (n, 1) and got.dtype == np.float32
        assert np.array_equal(got, want[:n])
        dplan = upload_plan(plan)
        jaxpr = str(jax.make_jaxpr(functools.partial(
            frontier_expand_launch, n_dst=n, use_kernel=use_kernel,
            interpret=True))(dplan.slots, dplan.row_dst, x))
        assert "pallas_call" not in jaxpr

    def test_empty_plan(self):
        plan = build_frontier_plan(np.empty(0, np.int64), np.empty(0, np.int64),
                                   10, 12)
        out = frontier_expand_counts(plan, np.ones((10, 3), np.float32),
                                     use_kernel=False)
        assert out.shape == (12, 3) and not out.any()

    @pytest.mark.parametrize("k_slots", [1, 8, 32])
    def test_weighted_plan_keeps_least_weight_per_edge(self, k_slots):
        """Each slot's weight is the least over the parallel edges it
        stands for, +inf in empty slots; the slots, mask and row_dst are
        bitwise those of the unweighted plan."""
        rng = np.random.default_rng(k_slots)
        n, e = 90, 1500
        src = rng.integers(0, n, e)
        dst = rng.integers(0, n, e)
        w = rng.random(e, dtype=np.float32)
        w[::11] = 0
        plain = build_frontier_plan(src, dst, n, n, k_slots=k_slots)
        plan = build_frontier_plan(src, dst, n, n, k_slots=k_slots,
                                   weight=w)
        assert plain.weight is None and plan.weight.dtype == np.float32
        for a, b in ((plain.idx, plan.idx), (plain.mask, plan.mask),
                     (plain.row_dst, plan.row_dst)):
            assert np.array_equal(a, b)
        assert plain.n_edges == plan.n_edges
        assert np.isinf(plan.weight[~plan.mask]).all()
        least = {}
        for s_, d_, w_ in zip(src.tolist(), dst.tolist(), w.tolist()):
            least[(s_, d_)] = min(least.get((s_, d_), np.inf), w_)
        rows, cols = np.nonzero(plan.mask)
        got = {(int(plan.idx[r, c]), int(plan.row_dst[r])):
               plan.weight[r, c] for r, c in zip(rows, cols)}
        assert got == least

    @pytest.mark.parametrize("n,e", [(64, 0), (200, 3000), (513, 9000)])
    def test_relax_matches_min_plus(self, n, e):
        """One relax launch: per destination, the least x[src] + w over
        its in-edges from finite x, in float32; +inf where none."""
        rng = np.random.default_rng(n)
        src = rng.integers(0, n, e)
        dst = rng.integers(0, n, e)
        w = rng.random(e, dtype=np.float32)
        plan = build_frontier_plan(src, dst, n, n, weight=w)
        x = np.where(rng.random(n) < 0.3, rng.random(n) * 5,
                     np.inf).astype(np.float32)[:, None]
        staged, nbytes = stage_frontier(x)
        dplan = upload_plan(plan)
        assert dplan.nbytes == (plan.idx.nbytes + plan.row_dst.nbytes
                                + plan.weight.nbytes) and nbytes == n * 4
        got = relax_staged(dplan, staged)
        want = np.full(n, np.inf, np.float32)
        np.minimum.at(want, dst, x[src, 0] + w)
        assert got.shape == (n, 1) and got.dtype == np.float32
        assert np.array_equal(got[:, 0], want)

    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=10, deadline=None)
    def test_property_random_plans(self, seed):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(2, 300))
        e = int(rng.integers(0, 2000))
        src = rng.integers(0, n, e)
        dst = rng.integers(0, n, e)
        b = int(rng.integers(1, 40))
        plan = build_frontier_plan(src, dst, n, n,
                                   k_slots=int(rng.integers(1, 33)))
        x = rng.random((n, b)).astype(np.float32)
        got = frontier_expand_counts(plan, x, use_kernel=False)
        a = np.zeros((n, n), np.float32)
        a[dst, src] = 1.0
        np.testing.assert_allclose(got, a @ x, rtol=1e-4, atol=1e-4)
