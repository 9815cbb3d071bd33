"""The frontier-expansion kernel and its launches vs the ref.py oracles:
shape sweeps + hypothesis property tests (interpret mode on CPU)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.common import round_up
from repro.kernels.frontier_expand import (build_frontier_plan,
                                           expand_staged,
                                           frontier_expand_counts,
                                           frontier_expand_launch,
                                           frontier_expand_np,
                                           frontier_expand_ref, relax_staged,
                                           stage_frontier, upload_plan)
from repro.kernels.frontier_expand.frontier_expand import (
    frontier_expand_pallas, gather_rows_sum)


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

    # K = 1 to 64 slots per row, one or two 128-lane chunks per source row,
    # every slot full or about half of them empty (-1): the row-DMA gather
    # adds the occupied rows in slot order in float32, as the numpy K-loop
    @pytest.mark.parametrize("fill", ["full", "half_empty"])
    @pytest.mark.parametrize("b", [128, 256])
    @pytest.mark.parametrize("k", [1, 7, 32, 64])
    def test_gather_rows_sum_matches_numpy(self, k, b, fill):
        rng = np.random.default_rng(k * b)
        r, m = 128, 300
        slots = rng.integers(0, m, (r, k)).astype(np.int32)
        if fill == "half_empty":
            slots[rng.random((r, k)) < 0.5] = -1
        x = rng.normal(size=(m, b)).astype(np.float32)
        got = np.asarray(gather_rows_sum(jnp.asarray(slots), jnp.asarray(x),
                                         interpret=True))
        want = frontier_expand_np(np.maximum(slots, 0), slots >= 0, x)
        assert got.shape == (r, b) and got.dtype == np.float32
        assert np.array_equal(got, want)

    # panel widths on both sides of one and two 128-lane tiles, and a small
    # one: the counts of a resident plan equal the numpy K-loop folded per
    # destination, bit for bit (0/1 panels sum exactly in float32)
    @pytest.mark.parametrize("b", [2, 64, 127, 129, 256])
    @pytest.mark.parametrize("use_kernel", [False, True])
    def test_expand_staged_matches_numpy(self, use_kernel, b):
        rng = np.random.default_rng(b)
        n, e = 260, 2500
        src = rng.integers(0, n, e)
        dst = rng.integers(0, n, e)
        plan = build_frontier_plan(src, dst, n, n)
        x = (rng.random((n, b)) < 0.2).astype(np.float32)
        staged, nbytes = stage_frontier(x)
        assert nbytes == n * b * 4
        got = expand_staged(upload_plan(plan), staged, use_kernel=use_kernel,
                            interpret=True)
        rows = frontier_expand_np(plan.idx, plan.mask, x)
        want = np.zeros((n + 1, b), np.float32)
        np.add.at(want, plan.row_dst, rows)
        assert got.shape == (n, b) and got.dtype == np.float32
        assert np.array_equal(got, want[:n])

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
