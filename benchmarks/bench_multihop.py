"""ISSUE 6: vectorized multi-hop operators — BENCH_multihop.json.

Three sections, each verified bitwise in-run before anything is timed:

  1. `two_hop`: a seed batch answered by the per-hop baseline (a Python
     loop of `friends_of_friends_perhop`, the PR-1-era strategy) vs ONE
     columnar `two_hop_counts` call — measured on the LIVE ServiceDB
     epoch view (`read_view()`, buffers + tombstones visible) AND on the
     same store reopened cold via `GraphDB.open`.
  2. `triangle`: directed closed wedges over a sampled middle set, per-hop
     baseline (per-vertex neighbor calls + chunked vectorized membership)
     vs `triangle_count`; the columnar operator is also timed over the
     FULL middle set (headline number — the baseline loop would take
     minutes there, which is the point).
  3. `kernel`: the dense `dense="kernel"` 2-hop on a seed panel vs the
     sparse columnar path on the same seeds — bitwise-equal, with the
     plan build (memoized in the engine plan cache) reported separately.
     Off-TPU this routes through the jit'd ref K-loop (see
     kernels/frontier_expand/ops.py), so the number is an XLA-CPU figure,
     not a Mosaic one; the section records which path ran.

Gates are in-run relative (same store, same process, seconds apart):
columnar two-hop and triangle must beat the per-hop baseline by GATE_X
on BOTH the live view and the reopened store. `--smoke` shrinks the
store and relaxes the gate; it exits non-zero on any gate or equality
failure (the CI step). Timings are best-of-3.
"""
from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from .common import power_law_graph, save

GATE_X = 10.0        # full-size: columnar must be >= 10x the per-hop loop
GATE_X_SMOKE = 3.0   # CI smoke runs a tiny store where fixed costs loom


def _best_of(fn, n=3):
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times)


def _db_opts(n_vertices):
    return dict(max_id=n_vertices - 1, n_partitions=16, n_levels=3,
                branching=4, buffer_cap=50_000, max_partition_edges=400_000,
                persist_min_edges=4096, wal_segment_bytes=4 << 20)


def _two_hop_section(g, seeds, failures, tag) -> dict:
    """Per-hop loop vs one columnar call on the same engine-like `g`."""
    from repro.core import two_hop_counts
    from repro.core.query import friends_of_friends_perhop

    def perhop():
        return [friends_of_friends_perhop(g, int(v)) for v in seeds]

    def columnar():
        return two_hop_counts(g, seeds)

    # bitwise equality first: every seed's slice vs the per-hop answer
    res = columnar()
    base = perhop()
    for i, ref in enumerate(base):
        got = res.ids[res.offsets[i]:res.offsets[i + 1]]
        if not np.array_equal(np.sort(got), np.sort(ref)):
            failures.append(f"two_hop[{tag}]: seed {seeds[i]} mismatch "
                            f"({got.shape[0]} vs {ref.shape[0]} ids)")
            break
    t_perhop = _best_of(perhop)
    t_col = _best_of(columnar)
    out = {
        "n_seeds": int(seeds.shape[0]),
        "result_ids": int(res.ids.shape[0]),
        "perhop_s": t_perhop,
        "columnar_s": t_col,
        "speedup_x": t_perhop / t_col,
    }
    print(f"    two_hop[{tag}]: perhop {t_perhop:.3f}s  columnar "
          f"{t_col:.4f}s  speedup {out['speedup_x']:.1f}x")
    return out


def _triangle_baseline(g, mids, max_id) -> int:
    """Per-vertex loop with chunked vectorized membership — the per-hop
    strategy: two neighbor calls per middle, then the wedge cross-product
    probed against the global distinct edge-key set."""
    from repro.core import as_engine

    eng = as_engine(g)
    so, do = eng.to_coo()
    N = np.int64(max_id + 1)
    keys = np.unique(so.astype(np.int64) * N + do.astype(np.int64))
    total = 0
    for v in mids:
        one = np.asarray([v], np.int64)
        inn = np.unique(eng.in_neighbors_batch(one)[0])
        out = np.unique(eng.out_neighbors_batch(one)[0])
        if inn.size == 0 or out.size == 0:
            continue
        for a in range(0, inn.size, 256):   # bound resident wedges
            pairs = (inn[a:a + 256, None] * N + out[None, :]).ravel()
            pos = np.searchsorted(keys, pairs)
            pos[pos >= keys.size] = 0
            total += int((keys[pos] == pairs).sum())
    return total


def _triangle_section(g, n_vertices, failures, tag, n_mids=1000,
                      full_headline=False) -> dict:
    from repro.core import as_engine, triangle_count
    from repro.core.multihop import _edge_keys_internal

    eng = as_engine(g)
    M = np.int64(eng.n_internal_vertices)
    ek = _edge_keys_internal(eng)
    mids_all = np.intersect1d(np.unique(ek // M), np.unique(ek % M),
                              assume_unique=True)
    mids_all = np.sort(np.asarray(eng.intervals.to_original(mids_all),
                                  np.int64))
    rng = np.random.default_rng(11)
    mids = np.sort(rng.choice(mids_all, min(n_mids, mids_all.size),
                              replace=False))

    base = _triangle_baseline(g, mids, n_vertices - 1)
    col = triangle_count(g, middles=mids)
    if base != col:
        failures.append(f"triangle[{tag}]: baseline {base} != columnar {col}")
    t_base = _best_of(lambda: _triangle_baseline(g, mids, n_vertices - 1))
    t_col = _best_of(lambda: triangle_count(g, middles=mids))
    out = {
        "n_middles": int(mids.size),
        "n_middles_total": int(mids_all.size),
        "triangles": int(col),
        "perhop_s": t_base,
        "columnar_s": t_col,
        "speedup_x": t_base / t_col,
    }
    print(f"    triangle[{tag}]: {col} wedges over {mids.size} middles  "
          f"perhop {t_base:.3f}s  columnar {t_col:.4f}s  "
          f"speedup {out['speedup_x']:.1f}x")
    if full_headline:
        t0 = time.perf_counter()
        full = triangle_count(g)
        out["full_triangles"] = int(full)
        out["full_columnar_s"] = time.perf_counter() - t0
        print(f"    triangle[{tag}]: FULL store {full} wedges in "
              f"{out['full_columnar_s']:.2f}s (columnar only)")
    return out


def _kernel_section(g, seeds, failures) -> dict:
    import jax

    from repro.core import two_hop_counts
    from repro.kernels.common import default_interpret

    backend = jax.default_backend()

    sparse = two_hop_counts(g, seeds, dense="never")
    dense = two_hop_counts(g, seeds, dense="kernel")  # builds + caches plan
    ok = (np.array_equal(sparse.ids, dense.ids)
          and np.array_equal(sparse.counts, dense.counts)
          and np.array_equal(sparse.offsets, dense.offsets))
    if not ok:
        failures.append("kernel: dense 2-hop not bitwise-equal to sparse")
    t_sparse = _best_of(lambda: two_hop_counts(g, seeds, dense="never"))
    t_dense = _best_of(lambda: two_hop_counts(g, seeds, dense="kernel"))
    out = {
        "n_seeds": int(seeds.shape[0]),
        "backend": backend,
        "mosaic_kernel": not default_interpret(),
        "bitwise_equal": ok,
        "sparse_s": t_sparse,
        "dense_s": t_dense,  # plan memoized in the engine cache by now
    }
    print(f"    kernel[{backend}]: sparse {t_sparse:.4f}s  dense "
          f"{t_dense:.4f}s  (mosaic={out['mosaic_kernel']}, "
          f"equal={ok})")
    return out


def run(scale: float = 1.0, smoke: bool = False) -> dict:
    from repro.core import GraphDB, ServiceDB

    n_vertices = max(4000, int(100_000 * scale))
    n_edges = max(30_000, int(1_000_000 * scale))
    n_seeds = 64 if smoke else 512
    n_mids = 200 if smoke else 1000
    gate = GATE_X_SMOKE if smoke else GATE_X
    src, dst = power_law_graph(n_vertices, n_edges, seed=0)
    rng = np.random.default_rng(3)
    seeds = np.unique(rng.integers(0, n_vertices, n_seeds * 2))[:n_seeds]
    panel = seeds[:min(128, n_seeds)]

    failures: list = []
    payload = {
        "scale": scale,
        "smoke": smoke,
        "n_vertices": n_vertices,
        "n_edges": n_edges,
        "gate_x": gate,
    }
    workdir = tempfile.mkdtemp(prefix="bench_multihop_")
    d = os.path.join(workdir, "db")
    try:
        svc = ServiceDB.create(d, checkpoint_interval_ops=10 ** 9,
                               **_db_opts(n_vertices))
        svc.insert_edges(src, dst)
        svc.checkpoint()
        # leave a buffered tail so the live view exercises buffer slabs
        tail_s, tail_d = power_law_graph(n_vertices, max(2000, n_edges // 50),
                                         seed=9)
        svc.insert_edges(tail_s, tail_d)

        print("  live epoch view (read_view): 2-hop + triangle + kernel ...")
        with svc.read_view() as view:
            payload["two_hop_live"] = _two_hop_section(
                view, seeds, failures, "live")
            payload["triangle_live"] = _triangle_section(
                view, n_vertices, failures, "live", n_mids=n_mids)
            payload["kernel"] = _kernel_section(view, panel, failures)
        svc.checkpoint()
        svc.close()

        print("  reopened GraphDB (cold): 2-hop + triangle ...")
        db = GraphDB.open(d)
        try:
            payload["two_hop_reopened"] = _two_hop_section(
                db, seeds, failures, "reopened")
            payload["triangle_reopened"] = _triangle_section(
                db, n_vertices, failures, "reopened", n_mids=n_mids,
                full_headline=not smoke)
        finally:
            db.close()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    for key in ("two_hop_live", "two_hop_reopened",
                "triangle_live", "triangle_reopened"):
        sp = payload[key]["speedup_x"]
        if sp < gate:
            failures.append(f"{key}: speedup {sp:.1f}x < gate {gate}x")
    payload["failures"] = failures
    save("BENCH_multihop", payload)
    if failures:
        print("  GATE FAILURES:")
        for f in failures:
            print(f"    - {f}")
        if smoke:
            sys.exit(1)
    else:
        print("  all gates passed")
    return payload


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny store, relaxed gate, non-zero exit on failure")
    args = ap.parse_args()
    run(scale=args.scale if not args.smoke else min(args.scale, 0.03),
        smoke=args.smoke)


if __name__ == "__main__":
    main()
