"""Single-root SSSP to convergence, back to back (the Graph500 SSSP
kernel), through `sssp` on one pinned read view whose weighted plan is
held, as a deployment that runs repeated analytics on one epoch holds it.

The configuration's `weights` name the edge column (`column`); the store
takes the weights with the edges, through `insert_edges(columns=)`, both
stored directions of an input edge carrying its one weight.

Traffic parameters (`traffic/<mix>.json`): `direction`, `roots` (search
keys among vertices with at least `root_min_degree` neighbours other than
themselves: `data.search_keys`), `warmup_roots` (traversed in set-up,
never in the window). Set-up holds the weighted plan of `direction`
(`dense_plan(view, direction, weight=column)`); the warm-up traversal
makes its device copy and compiles the one relax launch.

End-to-end: `bfs_teps`, Graph500 TEPS over the window, counted as for
BFS: for each traversal that completed, the input (undirected) edges with
both endpoints reached, summed, over the whole elapsed window, which ends
at a traversal boundary. Checked against the float64 reference
(`bench/reference_sssp.py`): the vertices whose reached state differs
(`sssp_reached_mismatches`, exact: limit 0), and the largest distance
error over its vertex's float32 rounding bound (`sssp_err_over_bound`).
"""
from __future__ import annotations

import gc
import os
import time
import traceback

import numpy as np

from bench import data, reference_sssp
from bench.gen.weights import edge_weights
from bench.harness import Check, Window, annotate

LIMIT_REACHED_MISMATCHES = 0  # an exact comparison
# Set between the readings at the cell's size on the chip (PERF.md, "How
# correct is decided"): sound runs read up to 0.97575 (at most 1 by the
# bound's derivation), the bfloat16 control at least 96,283.
LIMIT_ERR_OVER_BOUND = 2.0


def stored_weights(cfg, w: np.ndarray) -> np.ndarray:
    """The weight of each stored edge, in `data.stored_edges`' order."""
    return w if cfg["directed"] else np.concatenate([w, w])


def load_store(cfg, directory: str, src, dst, w, log):
    """`data.load_store` with the weights: a durable ServiceDB fed
    `src -> dst` and the weight column through `insert_edges` in
    `load_batch` batches, then checkpointed."""
    from repro.core import ServiceDB

    column = cfg["weights"]["column"]
    svc = ServiceDB.create(os.path.join(directory, "store"),
                           max_id=data.n_vertices(cfg) - 1, **cfg["store"])
    batch = int(cfg["load_batch"])
    t0 = time.perf_counter()
    for i in range(0, src.shape[0], batch):
        svc.insert_edges(src[i:i + batch], dst[i:i + batch],
                         columns={column: w[i:i + batch]})
    t1 = time.perf_counter()
    svc.checkpoint()
    log(f"load: {src.shape[0]} weighted edges in {t1 - t0} s, checkpoint "
        f"{time.perf_counter() - t1} s")
    if svc.n_edges != src.shape[0]:
        svc.close()
        raise RuntimeError(f"store holds {svc.n_edges} edges, "
                           f"{src.shape[0]} were inserted")
    return svc


def inputs(cfg, mix, seed):
    """The run's input edges, their weights, the stored edges and weights,
    and the roots in the order a window takes them."""
    u, v = data.edges(cfg, seed)
    w = edge_weights(seed, u.shape[0])
    src, dst = data.stored_edges(cfg, u, v)
    roots = data.search_keys(cfg, seed, src, dst, mix["roots"],
                             mix["root_min_degree"])
    return u, v, src, dst, stored_weights(cfg, w), roots


def setup(ctx):
    # first: a program without `sssp` fails here, in seconds
    from repro.core import dense_plan, sssp

    cfg, mix = ctx.config, ctx.traffic
    column = cfg["weights"]["column"]
    n = data.n_vertices(cfg)
    t0 = time.perf_counter()
    u, v, src, dst, w, roots = inputs(cfg, mix, ctx.seed)
    ctx.log(f"generate: {u.shape[0]} input edges, {src.shape[0]} stored, "
            f"{time.perf_counter() - t0} s")
    svc = load_store(cfg, ctx.workdir, src, dst, w, ctx.log)
    view = svc.read_view()
    t0 = time.perf_counter()
    dense_plan(view, mix["direction"], weight=column)
    ctx.log(f"weighted plan: {time.perf_counter() - t0} s")

    def run(root):
        return sssp(view, root, weight=column, direction=mix["direction"])

    n_warm = int(mix["warmup_roots"])
    for r in roots[:n_warm]:
        t0 = time.perf_counter()
        dist = run(int(r))
        ctx.log(f"warm-up traversal from {int(r)}: "
                f"{int(np.isfinite(dist).sum())} reached, "
                f"{time.perf_counter() - t0} s")
    return {"ctx": ctx, "u": u, "v": v, "src": src, "dst": dst, "w": w,
            "n": n, "roots": roots[n_warm:], "svc": svc, "view": view,
            "results": [], "run": run}


def window(state, seconds):
    ctx, run, roots = state["ctx"], state["run"], state["roots"]
    results, failed = state["results"], 0
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < seconds:
        root = int(roots[i % roots.shape[0]])
        i += 1
        with annotate("bench.sssp", root=root):
            try:
                dist = run(root)
            except Exception:  # noqa: BLE001 — counted, reported, not hidden
                failed += 1
                ctx.log(f"traversal from {root} failed:\n"
                        f"{traceback.format_exc()}")
                continue
        results.append((root, dist[:state["n"]]))
    elapsed = time.perf_counter() - t0
    return Window(attempted=i, failed=failed, elapsed_s=elapsed, metrics={})


def summarize(state, win, trace):
    """TEPS from the generated edge list; with a trace, the sizes the
    relax roofline counts."""
    u, v, n = state["u"], state["v"], state["n"]
    traversed = 0
    for _, dist in state["results"]:
        reached = np.isfinite(dist)
        traversed += int(np.count_nonzero(reached[u] & reached[v]))
    win.metrics["bfs_teps"] = traversed / win.elapsed_s
    win.facts["traversals"] = len(state["results"])
    win.facts["n_vertices"] = n
    if trace:
        keys = np.unique(state["src"] * np.int64(n) + state["dst"])
        win.facts["n_edges_distinct"] = int(keys.shape[0])


def release(state):
    """Free the program's state: the pinned view, the plans, the store."""
    view, svc = state.pop("view", None), state.pop("svc", None)
    state.pop("run", None)
    if view is not None:
        view.release()
    if svc is not None:
        svc.close()
    gc.collect()


def compare(answers, src, dst, w, n):
    """The comparison that decides `correct`: for each (root, distances)
    against the float64 reference, the vertices whose reached state
    differs, summed, and the largest error over its rounding bound."""
    csr = reference_sssp.WeightedCSR(src, dst, w, n)
    mismatches, worst = 0, 0.0
    for root, dist in answers:
        d64, lo, hi = reference_sssp.sssp_bounds(csr, root)
        dist = np.asarray(dist)[:n]
        mismatches += int(np.count_nonzero(np.isfinite(dist)
                                           != np.isfinite(d64)))
        worst = max(worst, reference_sssp.err_over_bound(dist, d64, lo, hi))
    return [Check("sssp_reached_mismatches", mismatches,
                  LIMIT_REACHED_MISMATCHES),
            Check("sssp_err_over_bound", worst, LIMIT_ERR_OVER_BOUND)]


def verify(state):
    return compare(state["results"], state["src"], state["dst"], state["w"],
                   state["n"])
