"""Chip smoke test: the live store's main path, device work included, once.

    python chip_smoke.py [--seed 0]

Needs a TPU and refuses to run without one (it never falls back to the
CPU). In one process, in order:

  1. device check — `jax.devices()[0].platform` must be "tpu";
  2. load — a durable `ServiceDB` fed a twitter-2010-shaped power-law graph
     (benchmarks/common.py) through `insert_edges` in large batches;
  3. serve — a `FrontDesk` answers out/in/fof/getrange requests, each equal
     to the direct engine call;
  4. device multi-hop — `khop(k=3)` and `two_hop_counts` with
     dense="kernel" (the Mosaic frontier-expansion kernel) bitwise equal to
     dense="never" on one pinned view;
  5. device analytics — `snapshot()` then `pagerank_device` in both modes
     against a numpy Jacobi PageRank;
  6. shard workers — a 2-shard `ShardRouter` started after this process
     holds the chip, its reads equal to the unsharded engine.

Sizes, cuts, timings and peak device memory go on earlier lines (set-up
facts, not benchmark numbers). The last line is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}.
Each phase is a function that tests call at a tiny size on the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import power_law_graph  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import (FrontDesk, ServiceDB, ShardRouter,  # noqa: E402
                        dense_plan, khop, pagerank_device, two_hop_counts)
from repro.core.query import consistent_engine  # noqa: E402
from repro.kernels.common import default_interpret  # noqa: E402
from repro.kernels.frontier_expand import \
    frontier_expand_launch  # noqa: E402

# twitter-2010 (Kwak et al., WWW'10), the paper's largest graph
TWITTER_VERTICES = 41_652_230
TWITTER_EDGES = 1_468_365_182
# the cut: under DENSE_MAX_VERTICES (core/multihop.py), about a quarter of HBM
N_VERTICES = 1 << 21
N_EDGES = 1 << 25
STORE_KW = dict(n_partitions=16, n_levels=2, branching=4,
                buffer_cap=1 << 20, max_partition_edges=1 << 23)
SEED_BLOCK = 128          # one kernel feature tile of seed columns
PAGERANK_ITERS = 5


class PhaseFailed(AssertionError):
    pass


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# -- phase 1 ------------------------------------------------------------------
def require_tpu():
    """The first TPU device; raises when JAX finds none."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise PhaseFailed(f"no TPU: jax.devices()[0] is {dev.platform} "
                          f"({dev.device_kind})")
    return dev


# -- phase 2 ------------------------------------------------------------------
def load_store(directory: str, n_vertices: int, n_edges: int, seed: int,
               batch: int = 1 << 22, store_kw=None):
    """A durable ServiceDB over the power-law graph, loaded through
    `insert_edges`. Returns (service, src, dst, seconds to load)."""
    src, dst = power_law_graph(n_vertices, n_edges, alpha=1.8, seed=seed,
                               hot_frac=0.5)
    svc = ServiceDB.create(directory, max_id=n_vertices - 1,
                           **(store_kw or STORE_KW))
    t0 = time.perf_counter()
    for i in range(0, n_edges, batch):
        svc.insert_edges(src[i:i + batch], dst[i:i + batch])
    load_s = time.perf_counter() - t0
    _check(svc.n_edges == n_edges,
           f"load: store holds {svc.n_edges} edges, inserted {n_edges}")
    return svc, src, dst, load_s


# -- phase 3 ------------------------------------------------------------------
def _canon_edges(src, dst, etype):
    order = np.lexsort((etype, dst, src))
    return src[order], dst[order], etype[order]


def serve(svc, vs) -> dict:
    """FrontDesk answers vs x {out, in, fof, getrange}; each equals the
    direct engine call on a pinned read view."""
    vs = [int(v) for v in vs]
    ops = ("out_neighbors", "in_neighbors", "fof", "getrange")
    t0 = time.perf_counter()
    with FrontDesk(svc, dispatchers=2) as fd:
        futs = [(op, v, fd.submit(op, v=v)) for op in ops for v in vs]
        got = [(op, v, f.result()) for op, v, f in futs]
    serve_s = time.perf_counter() - t0
    with svc.read_view() as view:
        eng = view.storage_engine()
        for op, v, ans in got:
            if op in ("out_neighbors", "in_neighbors"):
                vals, _ = eng._neighbors_batch(np.asarray([v], np.int64),
                                               op[:-len("_neighbors")])
                _check(np.array_equal(ans, np.sort(vals)), f"serve: {op}({v})")
            elif op == "fof":
                want = two_hop_counts(eng, [v]).ids
                _check(np.array_equal(ans, want), f"serve: fof({v})")
            else:
                eb = eng.edge_columns_batch([v])
                want = _canon_edges(eb.src, eb.dst, eb.etype)
                have = _canon_edges(ans["src"], ans["dst"], ans["etype"])
                _check(all(np.array_equal(a, b) for a, b in zip(have, want)),
                       f"serve: getrange({v})")
    return {"requests": len(got), "serve_s": serve_s}


# -- phase 4 ------------------------------------------------------------------
def device_multihop(svc, seeds, k: int = 3) -> dict:
    """khop and two_hop_counts with dense="kernel" vs dense="never" on ONE
    pinned view (bitwise, DESIGN.md §10.4), plus evidence of which hop
    program ran: whether the lowered kernel is a Mosaic custom call and
    what `interpret` resolved to."""
    seeds = np.asarray(seeds, np.int64)
    out = {"seeds": int(seeds.shape[0]), "k": k}
    with svc.read_view() as view:
        t0 = time.perf_counter()
        plan = dense_plan(view, "out")
        out["plan_build_s"] = time.perf_counter() - t0
        out["plan_rows"], out["plan_slots"] = plan.idx.shape
        out["plan_edges"] = plan.n_edges

        x = jax.ShapeDtypeStruct((plan.n_src, SEED_BLOCK), jnp.float32)
        slots = jax.ShapeDtypeStruct((plan.idx.size,), jnp.int32)
        row_dst = jax.ShapeDtypeStruct(plan.row_dst.shape, jnp.int32)
        t0 = time.perf_counter()
        # the hop's own program, as `expand_staged` launches it here
        lowered = frontier_expand_launch.lower(
            slots, row_dst, x, n_dst=plan.n_dst,
            use_kernel=not default_interpret())
        lowered.compile()
        out["kernel_compile_s"] = time.perf_counter() - t0
        out["mosaic_custom_call"] = "tpu_custom_call" in lowered.as_text()
        out["interpret"] = default_interpret()

        t0 = time.perf_counter()
        sparse = khop(view, seeds, k, dense="never")
        out["khop_sparse_s"] = time.perf_counter() - t0
        for run in ("first", "second"):
            t0 = time.perf_counter()
            dense = khop(view, seeds, k, dense="kernel")
            out[f"khop_kernel_{run}_s"] = time.perf_counter() - t0
        _check(len(sparse.levels) == len(dense.levels)
               and all(np.array_equal(a, b)
                       for a, b in zip(sparse.levels, dense.levels))
               and np.array_equal(sparse.visited, dense.visited),
               "multihop: khop kernel != never")
        out["khop_visited"] = int(sparse.visited.shape[0])

        t0 = time.perf_counter()
        sparse2 = two_hop_counts(view, seeds, dense="never")
        out["two_hop_sparse_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        dense2 = two_hop_counts(view, seeds, dense="kernel")
        out["two_hop_kernel_s"] = time.perf_counter() - t0
        _check(np.array_equal(sparse2.offsets, dense2.offsets)
               and np.array_equal(sparse2.ids, dense2.ids)
               and np.array_equal(sparse2.counts, dense2.counts),
               "multihop: two_hop_counts kernel != never")
        out["two_hop_pairs"] = int(sparse2.ids.shape[0])
    return out


# -- phase 5 ------------------------------------------------------------------
def jacobi_pagerank(src, dst, n, iters=PAGERANK_ITERS, damping=0.85):
    """Plain float64 synchronous PageRank over a COO edge list, and the
    tolerance a float32 evaluation of it is held to: a first-order bound
    on each vertex's rounding error. Per iteration, a sum of d in-edge
    terms taken in any order is within γ(d) = d·u/(1 − d·u) of its value
    (u = 2^-24; d + 2 covers the float32 damping factor and its multiply),
    the divide and multiply of each contribution round once each, every
    in-neighbour's own bound carries over through its contribution (and
    through the sum, whose γ(d) applies to the carried error too: at a hub
    γ is near 1), and the float32 constant 1 − damping and the final add
    round once each. Returns (ranks, bound)."""
    u = 2.0 ** -24
    inv_deg = 1.0 / np.maximum(np.bincount(src, minlength=n), 1)
    du = (np.bincount(dst, minlength=n) + 2) * u
    gamma = np.where(du < 1, du / np.maximum(1 - du, u), np.inf)
    r, bound = np.ones(n), np.zeros(n)
    for _ in range(iters):
        acc = np.bincount(dst, weights=(r * inv_deg)[src], minlength=n)
        carried = np.bincount(dst, weights=((bound + 2 * u * r) * inv_deg)[src],
                              minlength=n)
        r = (1 - damping) + damping * acc
        bound = (damping * (gamma * (acc + carried) + carried)
                 + u * ((1 - damping) + r))
    return r, bound


def device_analytics(svc, n_vertices: int) -> dict:
    """snapshot() -> pagerank_device in dense_gather and psw_windows modes,
    each within `jacobi_pagerank`'s per-vertex bound of its ranks on the
    same COO."""
    out = {}
    with svc.read_view() as view:
        t0 = time.perf_counter()
        dg = view.snapshot()
        jax.block_until_ready(dg.src)
        out["snapshot_s"] = time.perf_counter() - t0
        src, dst = view.to_coo()
        intern = np.asarray(view.intervals.to_internal(np.arange(n_vertices)))
    ref, bound = jacobi_pagerank(np.asarray(src), np.asarray(dst), n_vertices)
    out["max_in_degree"] = int(np.bincount(np.asarray(dst)).max())
    out["tolerance"] = "per-vertex float32 rounding bound (jacobi_pagerank)"
    out["max_rel_bound"] = float(np.max(bound / ref))
    out["median_rel_bound"] = float(np.median(bound / ref))
    for mode in ("dense_gather", "psw_windows"):
        for run in ("first", "second"):
            t0 = time.perf_counter()
            ranks = jax.block_until_ready(
                pagerank_device(dg, n_iters=PAGERANK_ITERS, mode=mode))
            out[f"{mode}_{run}_s"] = time.perf_counter() - t0
        err = np.abs(np.asarray(ranks).reshape(-1)[intern] - ref)
        out[f"{mode}_max_rel_err"] = float(np.max(err / ref))
        out[f"{mode}_max_err_over_bound"] = float(np.max(err / bound))
        _check(bool(np.all(err <= bound)),
               f"analytics: {mode} exceeds the float32 bound at "
               f"{int(np.sum(~(err <= bound)))} vertices: {json.dumps(out)}")
    return out


# -- phase 6 ------------------------------------------------------------------
def sharded_reads(directory: str, seed: int, n_vertices: int = 20_000,
                  n_edges: int = 100_000) -> dict:
    """A 2-shard ShardRouter (host-only workers) next to a process that
    holds the chip: its reads equal the unsharded engine's."""
    kw = dict(n_partitions=8, n_levels=2, branching=4, buffer_cap=20_000,
              max_partition_edges=200_000)
    src, dst = power_law_graph(n_vertices, n_edges, seed=seed + 1)
    vs = np.unique(src[:32])
    t0 = time.perf_counter()
    ref = ServiceDB.create(os.path.join(directory, "ref"),
                           max_id=n_vertices - 1, **kw)
    router = ShardRouter.create(os.path.join(directory, "sharded"),
                                max_id=n_vertices - 1, n_shards=2, **kw)
    try:
        ref.insert_edges(src, dst)
        router.insert_edges(src, dst)
        with consistent_engine(router) as eng, ref.read_view() as view:
            reng = view.storage_engine()
            for direction in ("out", "in"):
                a, ao = eng._neighbors_batch(vs, direction)
                b, bo = reng._neighbors_batch(vs, direction)
                _check(all(np.array_equal(np.sort(a[ao[i]:ao[i + 1]]),
                                          np.sort(b[bo[i]:bo[i + 1]]))
                           for i in range(vs.shape[0])),
                       f"shards: {direction} neighbors")
                ka = khop(eng, vs, 2, direction=direction)
                kb = khop(reng, vs, 2, direction=direction)
                _check(np.array_equal(ka.visited, kb.visited),
                       f"shards: khop {direction}")
            fa, fb = two_hop_counts(eng, vs), two_hop_counts(reng, vs)
            _check(np.array_equal(fa.offsets, fb.offsets)
                   and np.array_equal(fa.ids, fb.ids)
                   and np.array_equal(fa.counts, fb.counts),
                   "shards: two_hop_counts")
    finally:
        router.close()
        ref.close()
    return {"shards": 2, "edges": n_edges,
            "seconds": time.perf_counter() - t0}


# -- main ---------------------------------------------------------------------
def _count_compiles() -> dict:
    """Totals of this process's XLA compiles, from JAX's monitoring events:
    programs compiled or loaded from the persistent cache, the seconds that
    took (a cache hit counts its load time), and the cache's hits and
    misses. Cold vs warm runs differ here."""
    tot = {"programs": 0, "seconds": 0.0, "cache_hits": 0, "cache_misses": 0}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            tot["programs"] += 1
            tot["seconds"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            tot["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            tot["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return tot


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        dev = require_tpu()
    except PhaseFailed as e:
        print(f"[chip_smoke] FAILED device check: {e}", file=sys.stderr)
        return 2
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"jax {jax.__version__}; compile cache {enable_compile_cache()}")
    compiles = _count_compiles()
    log(f"graph: power-law alpha 1.8 hot_frac 0.5 seed {args.seed}: "
        f"{N_VERTICES} vertices, {N_EDGES} edges; cut from "
        f"twitter-2010 ({TWITTER_VERTICES} vertices, {TWITTER_EDGES} edges)"
        f" by {TWITTER_VERTICES / N_VERTICES:.1f}x vertices, "
        f"{TWITTER_EDGES / N_EDGES:.1f}x edges")
    rng = np.random.default_rng(args.seed)
    t_all = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_", suffix=".graphdb",
                                     dir=REPO) as tmp:
        svc, src, _, load_s = load_store(os.path.join(tmp, "store"),
                                         N_VERTICES, N_EDGES, args.seed)
        try:
            log(f"load: {N_EDGES} edges in {load_s:.1f}s (set-up)")
            res = serve(svc, src[rng.integers(0, N_EDGES, 16)])
            log(f"serve: {json.dumps(res)}")
            seeds = np.unique(src[rng.integers(0, N_EDGES,
                                               SEED_BLOCK * 2)])[:SEED_BLOCK]
            res = device_multihop(svc, seeds)
            log(f"multihop: {json.dumps(res)}")
            _check(res["mosaic_custom_call"] and not res["interpret"],
                   "multihop: the hop did not run the compiled Mosaic kernel")
            res = device_analytics(svc, N_VERTICES)
            log(f"analytics: {json.dumps(res)}")
        finally:
            svc.close()
        res = sharded_reads(os.path.join(tmp, "shards"), args.seed)
        log(f"shards: {json.dumps(res)}")
    stats = dev.memory_stats() or {}
    log(f"compiles: {json.dumps(compiles)}")
    log(f"peak device memory: {stats.get('peak_bytes_in_use')} bytes; "
        f"total {time.perf_counter() - t_all:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
