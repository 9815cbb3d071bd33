"""The plain reference and the generators against independent
formulations: BFS depths against scipy's unweighted shortest paths, the
Jacobi PageRank against a per-edge loop, the device layout map against
its inverse, and the generators' sizes and determinism by seed."""
from __future__ import annotations

import numpy as np
import pytest
import scipy.sparse as sp
from scipy.sparse.csgraph import shortest_path

from bench import reference
from bench.gen import chung_lu_edges, kronecker_edges
from bench.gen.chung_lu import rank_offset


def test_bfs_depths_match_scipy():
    u, v = kronecker_edges(8, seed=3)
    n = 256
    src, dst = np.concatenate([u, v]), np.concatenate([v, u])
    csr = reference.CSR(src, dst, n)
    adj = sp.csr_matrix((np.ones(src.shape[0]), (src, dst)), shape=(n, n))
    for root in (int(u[0]), int(u[1]), int(v[7])):
        dist = shortest_path(adj, unweighted=True, indices=root)
        want = np.where(np.isinf(dist), -1, dist).astype(np.int32)
        assert np.array_equal(reference.bfs_depths(csr, root, 64), want)
        capped = np.where(want > 2, -1, want)
        assert np.array_equal(reference.bfs_depths(csr, root, 2), capped)


def test_jacobi_pagerank_matches_an_edge_loop():
    src, dst = chung_lu_edges(64, 400, 2.276, 2.276, 0.05, 0.05, seed=4)
    r, bound = reference.jacobi_pagerank(src, dst, 64, iters=3)
    deg = np.bincount(src, minlength=64)
    want = np.ones(64)
    for _ in range(3):
        acc = np.zeros(64)
        for s, d in zip(src, dst):
            acc[d] += want[s] / deg[s]
        want = 0.15 + 0.85 * acc
    assert np.allclose(r, want, rtol=1e-12)
    assert np.all(bound > 0)


def test_psw_internal_ids_invert_the_interval_hash():
    n, p = 1000, 16
    ell = -(-n // p)
    pos = reference.psw_internal_ids(n, p, ell)
    assert np.unique(pos).shape[0] == n
    ids = np.arange(n)
    assert np.array_equal((pos % ell) * p + pos // ell, ids)


@pytest.mark.parametrize("seed", [0, 2**31 + 9, 3_000_000_000])
def test_generators_are_sized_and_seeded(seed):
    u, v = kronecker_edges(9, edgefactor=16, seed=seed)
    assert u.shape == v.shape == (16 * 512,)
    assert 0 <= u.min() and max(u.max(), v.max()) < 512
    u2, v2 = kronecker_edges(9, edgefactor=16, seed=seed)
    assert np.array_equal(u, u2) and np.array_equal(v, v2)
    s, d = chung_lu_edges(512, 4000, 2.276, 2.276, 0.01, 0.005, seed=seed)
    s2, d2 = chung_lu_edges(512, 4000, 2.276, 2.276, 0.01, 0.005, seed=seed)
    assert np.array_equal(s, s2) and np.array_equal(d, d2)
    assert s.shape == (4000,) and d.max() < 512


def test_chung_lu_keeps_the_largest_degree_and_the_exponent():
    n, m, share = 1 << 16, 1 << 21, 0.002
    src, dst = chung_lu_edges(n, m, 2.276, 2.276, share, share / 4, seed=7)
    din = np.bincount(dst, minlength=n)
    dout = np.bincount(src, minlength=n)
    assert abs(din.max() / (share * m) - 1) < 0.05
    assert abs(dout.max() / (share / 4 * m) - 1) < 0.1
    # the complementary distribution falls as k^-(exponent - 1)
    k = np.array([128, 1024])
    ccdf = np.array([np.count_nonzero(din >= x) for x in k])
    slope = np.diff(np.log(ccdf)) / np.diff(np.log(k))
    assert abs(slope[0] + 1.276) < 0.1


def test_rank_offset_refuses_a_share_out_of_reach():
    with pytest.raises(ValueError):
        rank_offset(1000, 2.276, 0.0005)
