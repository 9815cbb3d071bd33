"""The cells `twitter2010-s20.bfs` and `graph500w-s20.sssp` end to end at
a tiny size on the CPU, through the same harness, drivers and comparison
the chip run uses; the SSSP controls and faults, each of which the
comparison must catch; the plain SSSP reference against a textbook
Dijkstra; and the relax readers, which read nothing where there is
nothing to read."""
from __future__ import annotations

import functools
import heapq
import json

import numpy as np
import pytest

import repro.core
from bench import control_sssp, harness, reference_sssp
from bench.tests.conftest import BENCH, TINY_STORE

CELLS = ("twitter2010-s20.bfs", "graph500w-s20.sssp")


def tiny_sssp_config():
    """`graph500w-s20` cut to 1,024 vertices, as conftest cuts the others."""
    g = json.loads((BENCH / "configs" / "graph500w-s20.json").read_text())
    g.update(vertices=1024, load_batch=8192,
             store=dict(TINY_STORE,
                        column_dtypes=g["store"]["column_dtypes"]),
             generator=dict(g["generator"], scale=10))
    return g


@pytest.fixture
def run_new(run_tiny, tiny_bench):
    (tiny_bench / "configs" / "graph500w-s20.json").write_text(
        json.dumps(tiny_sssp_config()))
    return functools.partial(run_tiny, bench=tiny_bench)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_new_cell_is_correct_at_tiny_size(run_new, spec, cell, trace):
    res = run_new(cell, trace=trace)
    assert res["correct"], res
    assert res["attempted"] >= 1 and res["failed"] == 0
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]
    if not trace:
        want = {m["name"] for m in harness.cell_metrics(spec, cell,
                                                        "end_to_end")}
        assert set(res["metrics"]) == want == {"setup_s", "bfs_teps"}
        assert all(m["value"] > 0 for m in res["metrics"].values())
    else:
        allowed = {m["name"] for m in harness.cell_metrics(spec, cell,
                                                           "per_layer")}
        assert set(res["metrics"]) <= allowed
        assert "busy_s" not in res["device"]


def test_sssp_cell_reads_its_kernel_share(run_new):
    res = run_new("graph500w-s20.sssp", trace=True)
    share = res["metrics"]["relax_kernel_share.sssp"]
    assert share["unit"] == "%" and 0 < share["value"] <= 100
    # no device plane on the CPU: the roofline reads nothing
    assert "relax_roofline.sssp" not in res["metrics"]
    assert set(res["checks"]) == {"sssp_reached_mismatches",
                                  "sssp_err_over_bound"}


def _sssp_fault(kind):
    real = repro.core.sssp

    @functools.wraps(real)
    def sssp(g, root, **kw):
        d = real(g, root, **kw).copy()
        reached = np.flatnonzero(np.isfinite(d) & (d > 0))
        if kind == "unchanged":          # the traversal never advances
            d[reached] = np.inf
        elif kind == "bf16":             # distances held one step down
            import jax.numpy as jnp
            d = np.asarray(jnp.asarray(d).astype(jnp.bfloat16), np.float32)
        else:                            # one answer altered where made
            d[reached[-1]] *= np.float32(1.001)
        return d
    return sssp


@pytest.mark.parametrize("kind", ["unchanged", "bf16", "altered"])
def test_sssp_fault_is_not_correct(run_new, monkeypatch, kind):
    monkeypatch.setattr(repro.core, "sssp", _sssp_fault(kind))
    res = run_new("graph500w-s20.sssp")
    assert res["correct"] is False
    c = res["checks"]
    assert (c["sssp_reached_mismatches"]["value"] > 0
            or c["sssp_err_over_bound"]["value"]
            > c["sssp_err_over_bound"]["limit"])


@pytest.mark.parametrize("kind", ["bf16", "stale"])
def test_sssp_control_fails(kind):
    checks = {c.name: c for c in control_sssp.sssp_control(
        tiny_sssp_config(), harness.traffic("sssp"), seed=5, kind=kind,
        traversals=3)}
    assert not all(c.ok for c in checks.values())
    if kind == "bf16":      # same reach, and far outside the float32 bound
        assert checks["sssp_reached_mismatches"].value == 0
        assert checks["sssp_err_over_bound"].value \
            > 10 * checks["sssp_err_over_bound"].limit


def test_a_program_without_sssp_fails_in_set_up(run_new, monkeypatch):
    """The parent of this cell has no `sssp`: its run fails at the import,
    before the store is loaded."""
    monkeypatch.delattr(repro.core, "sssp")
    with pytest.raises(ImportError):
        run_new("graph500w-s20.sssp")


def _dijkstra(src, dst, w, n, root):
    adj = [[] for _ in range(n)]
    for a, b, c in zip(src.tolist(), dst.tolist(), w.tolist()):
        adj[a].append((b, c))
    dist = [np.inf] * n
    dist[root] = 0.0
    heap = [(0.0, root)]
    while heap:
        d, a = heapq.heappop(heap)
        if d > dist[a]:
            continue
        for b, c in adj[a]:
            if d + c < dist[b]:
                dist[b] = d + c
                heapq.heappush(heap, (d + c, b))
    return np.asarray(dist)


def _fold32(src, dst, w, n, root):
    """The least float32 left-to-right path sums, by plain Bellman-Ford."""
    d = np.full(n, np.inf, np.float32)
    d[root] = 0
    while True:
        c = np.full(n, np.inf, np.float32)
        np.minimum.at(c, dst, d[src] + w.astype(np.float32))
        new = np.minimum(d, c)
        if np.array_equal(new, d):
            return d
        d = new


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_is_dijkstra_and_its_bound_holds_float32(seed):
    """Parallel edges with other weights, zero weights, a zero-weight
    cycle and unreached vertices: d64 is the exact Dijkstra answer, and
    the float32 answer lies inside [lo, hi] (err over bound <= 1)."""
    rng = np.random.default_rng(seed)
    n, m = 300, 1500
    src = rng.integers(0, n - 20, m)       # the last 20 are never left
    dst = rng.integers(0, n - 10, m)       # the last 10 never reached
    w = rng.random(m, dtype=np.float32) * np.float32(rng.choice([1, 64]))
    w[::37] = 0
    src = np.concatenate([src, src[:50], [3, 4]])
    dst = np.concatenate([dst, dst[:50], [4, 3]])
    w = np.concatenate([w, w[:50] * np.float32(0.5), [0, 0]]).astype(
        np.float32)
    csr = reference_sssp.WeightedCSR(src, dst, w, n)
    for root in (0, 3, int(src[7])):
        d64, lo, hi = reference_sssp.sssp_bounds(csr, root)
        want = _dijkstra(src, dst, w.astype(np.float64), n, root)
        assert np.array_equal(d64, want)
        d32 = _fold32(src, dst, w, n, root)
        assert np.array_equal(np.isfinite(d32), np.isfinite(d64))
        fin = np.isfinite(d64)
        assert (lo[fin] <= d32[fin]).all() and (d32[fin] <= hi[fin]).all()
        assert reference_sssp.err_over_bound(d32, d64, lo, hi) <= 1.0


def _reading(spans, trace=None, peaks=None):
    win = harness.Window(1, 0, 2.0, {}, {"n_vertices": 1000,
                                         "n_edges_distinct": 16000})
    return harness.Reading("graph500w-s20.sssp", win, spans, {}, {}, trace,
                           peaks)


def _relax(mode, dur):
    return {"name": "multihop.relax", "dur": dur, "args": {"mode": mode}}


def test_relax_readers_read_nothing_without_kernel_rounds_or_trace():
    roof = harness.metric_reader("relax_roofline.sssp")
    share = harness.metric_reader("relax_kernel_share.sssp")
    peaks = {"flops": 1e12, "hbm_bytes_per_s": 1e9}
    traced = {"busy_s": 1.0, "window_s": 2.0}
    sparse_only = [_relax("sparse", 10), _relax("stream", 5)]
    kernel = sparse_only + [_relax("kernel", 500_000), _relax("kernel", 0)]
    # no kernel-mode round: nothing, traced or not
    assert roof.read("relax_roofline.sssp",
                     _reading(sparse_only, traced, peaks)) is None
    assert share.read("relax_kernel_share.sssp",
                      _reading(sparse_only)) is None
    # no trace, or no device row: no roofline
    assert roof.read("relax_roofline.sssp", _reading(kernel)) is None
    assert roof.read("relax_roofline.sssp",
                     _reading(kernel, traced, None)) is None
    # two kernel rounds over 16,000 edges and 1,000 vertices: bound by
    # bytes, (8·16000 + 8·1000) B / 1e9 B/s each, over 1 s busy
    assert roof.read("relax_roofline.sssp",
                     _reading(kernel, traced, peaks)) == pytest.approx(
        100 * 2 * 136_000 / 1e9)
    assert share.read("relax_kernel_share.sssp",
                      _reading(kernel)) == pytest.approx(25.0)
