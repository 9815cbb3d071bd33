"""Single-root BFS to exhaustion, back to back (the Graph500 BFS kernel),
through `khop` on one pinned read view whose frontier-expansion plan is
held, as a deployment that runs repeated analytics on one epoch holds it.

Traffic parameters (`traffic/<mix>.json`): `k` (hop cap), `direction`,
`roots` (search keys among vertices with at least `root_min_degree`
neighbours other than themselves: `data.search_keys`), `warmup_roots`
(traversed in set-up, never in the window). Set-up holds the plan of
`direction` (`dense_plan`), so hops over the program's density threshold
run the frontier-expansion kernel.

End-to-end: `bfs_teps`, Graph500 TEPS over the window: for each traversal
that completed, the input (undirected) edges with both endpoints reached,
summed, over the whole elapsed window, which ends at a traversal boundary.
Checked: every traversal's depth of every vertex against the reference
BFS (`bfs_depth_mismatches`, exact: limit 0).
"""
from __future__ import annotations

import gc
import time
import traceback

import numpy as np

from bench import data, reference
from bench.harness import Check, Window, annotate

LIMIT_DEPTH_MISMATCHES = 0  # an exact comparison


def setup(ctx):
    from repro.core import dense_plan, khop

    cfg, mix = ctx.config, ctx.traffic
    n = data.n_vertices(cfg)
    t0 = time.perf_counter()
    u, v = data.edges(cfg, ctx.seed)
    src, dst = data.stored_edges(cfg, u, v)
    roots = data.search_keys(cfg, ctx.seed, src, dst, mix["roots"],
                             mix["root_min_degree"])
    ctx.log(f"generate: {u.shape[0]} input edges, {src.shape[0]} stored, "
            f"{time.perf_counter() - t0} s")
    svc = data.load_store(cfg, ctx.workdir, src, dst, ctx.log)
    view = svc.read_view()
    t0 = time.perf_counter()
    dense_plan(view, mix["direction"])
    ctx.log(f"plan: {time.perf_counter() - t0} s")
    n_warm = int(mix["warmup_roots"])
    for r in roots[:n_warm]:
        t0 = time.perf_counter()
        res = khop(view, [int(r)], mix["k"], direction=mix["direction"])
        ctx.log(f"warm-up traversal from {int(r)}: {len(res.levels)} levels,"
                f" {res.visited.shape[0]} reached, "
                f"{time.perf_counter() - t0} s")
    return {"ctx": ctx, "u": u, "v": v, "src": src, "dst": dst, "n": n,
            "roots": roots[n_warm:], "svc": svc, "view": view,
            "results": [], "khop": khop}


def window(state, seconds):
    ctx, mix = state["ctx"], state["ctx"].traffic
    khop, view, roots = state["khop"], state["view"], state["roots"]
    results, failed = state["results"], 0
    t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - t0 < seconds:
        root = int(roots[i % roots.shape[0]])
        i += 1
        with annotate("bench.bfs", root=root):
            try:
                res = khop(view, [root], mix["k"], direction=mix["direction"])
            except Exception:  # noqa: BLE001 — counted, reported, not hidden
                failed += 1
                ctx.log(f"traversal from {root} failed:\n"
                        f"{traceback.format_exc()}")
                continue
        results.append((root, res))
    elapsed = time.perf_counter() - t0
    return Window(attempted=i, failed=failed, elapsed_s=elapsed, metrics={})


def summarize(state, win, trace):
    """TEPS from the generated edge list; with a trace, the sizes the
    hop roofline counts."""
    u, v, n = state["u"], state["v"], state["n"]
    traversed = 0
    for _, res in state["results"]:
        reached = np.zeros(n, bool)
        reached[res.visited] = True
        traversed += int(np.count_nonzero(reached[u] & reached[v]))
    win.metrics["bfs_teps"] = traversed / win.elapsed_s
    win.facts["traversals"] = len(state["results"])
    win.facts["n_vertices"] = n
    if trace:
        keys = np.unique(state["src"] * np.int64(n) + state["dst"])
        win.facts["n_edges_distinct"] = int(keys.shape[0])
        win.facts["frontier_columns"] = 1   # one root per traversal


def release(state):
    """Free the program's state: the pinned view, the plan, the store."""
    view, svc = state.pop("view", None), state.pop("svc", None)
    if view is not None:
        view.release()
    if svc is not None:
        svc.close()
    gc.collect()


def program_depths(levels, n):
    """Depth per vertex from a KHopResult's levels (-1: not reached), and
    how many entries of the levels repeat a vertex already placed."""
    depth = np.full(n, -1, np.int32)
    repeats = 0
    for d, lv in enumerate(levels):
        lv = np.asarray(lv, np.int64)
        repeats += int(np.count_nonzero(depth[lv] >= 0))
        repeats += int(lv.shape[0] - np.unique(lv).shape[0])
        depth[lv] = np.where(depth[lv] >= 0, depth[lv], d)
    return depth, repeats


def compare(answers, src, dst, n, k):
    """The comparison that decides `correct`: for each (root, levels) the
    vertices whose depth differs from the reference BFS's, plus repeated
    entries, summed."""
    csr = reference.CSR(src, dst, n)
    bad = 0
    for root, levels in answers:
        want = reference.bfs_depths(csr, root, k)
        have, repeats = program_depths(levels, n)
        bad += int(np.count_nonzero(have != want)) + repeats
    return [Check("bfs_depth_mismatches", bad, LIMIT_DEPTH_MISMATCHES)]


def verify(state):
    mix = state["ctx"].traffic
    answers = [(root, res.levels) for root, res in state["results"]]
    return compare(answers, state["src"], state["dst"], state["n"], mix["k"])
