"""Device PageRank passes, back to back, over the snapshot of one
published epoch: `pagerank_device(dg)` at the program's defaults, each
pass ended with `block_until_ready`.

Traffic parameters (`traffic/<mix>.json`): `warmup_passes` (run in
set-up, never in the window).

End-to-end: `pagerank_eps`, edges swept per second: stored edges times
iterations completed, over the whole elapsed window, which ends at a pass
boundary. Checked: every pass's ranks against the float64 reference
within its per-vertex float32 rounding bound (`pagerank_err_over_bound`,
the largest |rank - reference| / bound over vertices and passes).
"""
from __future__ import annotations

import gc
import inspect
import time
import traceback

import numpy as np

from bench import data, reference
from bench.harness import Check, Window, annotate

# Set between the readings at the cell's size on the chip (PERF.md, "How
# correct is decided"): sound runs read up to 0.742 of the float32 bound,
# the bfloat16 control at least 39,996.
LIMIT_ERR_OVER_BOUND = 4.0


def default_iterations():
    """The program's own default number of iterations per pass."""
    from repro.core import pagerank_device
    return int(inspect.signature(pagerank_device)
               .parameters["n_iters"].default)


def setup(ctx):
    import jax
    from repro.core import pagerank_device

    cfg, mix = ctx.config, ctx.traffic
    n = data.n_vertices(cfg)
    t0 = time.perf_counter()
    u, v = data.edges(cfg, ctx.seed)
    src, dst = data.stored_edges(cfg, u, v)
    ctx.log(f"generate: {src.shape[0]} stored edges, "
            f"{time.perf_counter() - t0} s")
    svc = data.load_store(cfg, ctx.workdir, src, dst, ctx.log)
    view = svc.read_view()
    t0 = time.perf_counter()
    dg = view.snapshot()
    jax.block_until_ready(dg.src)
    ctx.log(f"snapshot: {time.perf_counter() - t0} s")
    for _ in range(int(mix["warmup_passes"])):
        t0 = time.perf_counter()
        jax.block_until_ready(pagerank_device(dg))
        ctx.log(f"warm-up pass: {time.perf_counter() - t0} s")
    return {"ctx": ctx, "src": src, "dst": dst, "n": n, "svc": svc,
            "view": view, "dg": dg, "ranks": [], "run": pagerank_device,
            "iters": default_iterations()}


def window(state, seconds):
    import jax

    run, dg, ranks = state["run"], state["dg"], state["ranks"]
    failed = attempted = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        attempted += 1
        with annotate("bench.pagerank"):
            try:
                ranks.append(jax.block_until_ready(run(dg)))
            except Exception:  # noqa: BLE001 — counted, reported, not hidden
                failed += 1
                state["ctx"].log(f"pass failed:\n{traceback.format_exc()}")
    elapsed = time.perf_counter() - t0
    return Window(attempted=attempted, failed=failed, elapsed_s=elapsed,
                  metrics={})


def summarize(state, win, trace):
    iterations = len(state["ranks"]) * state["iters"]
    win.metrics["pagerank_eps"] = (state["src"].shape[0] * iterations
                                   / win.elapsed_s)
    win.facts.update(iterations=iterations, n_vertices=state["n"],
                     n_edges=int(state["src"].shape[0]))


def release(state):
    """Copy each pass's ranks to the host, then free the program's state:
    the device graph, the pinned view, the store."""
    state["ranks"] = [np.asarray(r) for r in state["ranks"]]
    state.pop("dg", None)
    view, svc = state.pop("view", None), state.pop("svc", None)
    if view is not None:
        view.release()
    if svc is not None:
        svc.close()
    gc.collect()


def compare(passes, src, dst, n, iters):
    """The comparison that decides `correct`: the largest error of any
    vertex in any pass over its float32 rounding bound. Ranks come in the
    store's device layout, (P, L) by internal id."""
    want, bound = reference.jacobi_pagerank(src, dst, n, iters)
    worst = 0.0
    for r in passes:
        r = np.asarray(r, np.float64)
        pos = reference.psw_internal_ids(n, r.shape[0], r.shape[1])
        err = np.abs(r.reshape(-1)[pos] - want) / bound
        worst = max(worst, float(np.max(np.nan_to_num(err, nan=np.inf))))
    return [Check("pagerank_err_over_bound", worst, LIMIT_ERR_OVER_BOUND)]


def verify(state):
    return compare(state["ranks"], state["src"], state["dst"], state["n"],
                   state["iters"])
