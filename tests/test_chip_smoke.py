"""chip_smoke.py's phases at a tiny size on the CPU.

The script itself demands a TPU; its phase functions do not, so the same
equalities it checks on the chip are checked here: FrontDesk answers vs the
direct engine, dense="kernel" vs dense="never" multi-hop (bitwise), device
PageRank within the float32 bound of a numpy reference, and a 2-shard
router vs the unsharded engine.
"""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

N_VERTICES = 3000
N_EDGES = 30_000
TINY_STORE = dict(n_partitions=16, n_levels=2, branching=4, buffer_cap=4000,
                  max_partition_edges=40_000)


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    d = tmp_path_factory.mktemp("smoke")
    svc, src, dst, _ = chip_smoke.load_store(
        str(d / "store"), N_VERTICES, N_EDGES, seed=3, batch=7000,
        store_kw=TINY_STORE)
    yield svc, src, dst
    svc.close()


def test_load_holds_every_edge(store):
    svc, src, _ = store
    assert svc.n_edges == src.shape[0] == N_EDGES


def test_serve_matches_engine(store):
    svc, src, _ = store
    res = chip_smoke.serve(svc, np.unique(src[:12]))
    assert res["requests"] == 4 * np.unique(src[:12]).shape[0]


def test_multihop_kernel_equals_never(store):
    svc, src, _ = store
    seeds = np.unique(src[:300])[:chip_smoke.SEED_BLOCK]
    res = chip_smoke.device_multihop(svc, seeds)
    assert res["seeds"] == chip_smoke.SEED_BLOCK
    assert res["khop_visited"] > res["seeds"]
    assert res["two_hop_pairs"] > 0
    # off the chip the kernel interprets and the hop takes the ref K-loop;
    # main() refuses exactly this evidence
    assert res["interpret"] and not res["mosaic_custom_call"]


def test_pagerank_within_float32_bound(store):
    svc, _, _ = store
    res = chip_smoke.device_analytics(svc, N_VERTICES)
    for mode in ("dense_gather", "psw_windows"):
        assert res[f"{mode}_max_err_over_bound"] <= 1.0


def test_jacobi_bound_catches_a_missing_edge():
    src, dst = chip_smoke.power_law_graph(500, 4000, seed=5)
    ref, bound = chip_smoke.jacobi_pagerank(src, dst, 500)
    keep = np.ones(src.shape[0], bool)
    keep[np.flatnonzero(np.bincount(dst, minlength=500)[dst] < 8)[0]] = False
    short, _ = chip_smoke.jacobi_pagerank(src[keep], dst[keep], 500)
    assert np.any(np.abs(short - ref) > bound)


def test_sharded_reads_match_unsharded(tmp_path):
    res = chip_smoke.sharded_reads(str(tmp_path), seed=0, n_vertices=2000,
                                   n_edges=10_000)
    assert res["shards"] == 2


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert capsys.readouterr().out == ""


def test_script_alone_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
