"""The peaks table and the algorithmic work counts of bench/roofline.py,
and the per-layer readers that use them."""
from __future__ import annotations

import pytest

from bench import harness, roofline


def test_v5e_peaks_with_source():
    pk = roofline.peaks("TPU v5 lite")
    assert pk["flops"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    assert pk["hbm_bytes"] == 16e9 and "TPU v5e" in pk["source"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_frontier_hop_work():
    # 4 bytes per edge, 8 per vertex and column; one add per edge and column
    assert roofline.frontier_hop_work(10, 100, 1) == (100, 480)
    assert roofline.frontier_hop_work(10, 100, 128) == (12800, 400 + 10240)


def test_pagerank_sweep_work():
    assert roofline.pagerank_sweep_work(10, 100) == (230, 920)


def test_least_seconds_takes_the_binding_bound():
    pk = {"flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert roofline.least_seconds(1000, 10, pk) == 10.0
    assert roofline.least_seconds(10, 1000, pk) == 100.0


def _reading(facts, spans=(), trace=None, peaks=None):
    win = harness.Window(1, 0, 2.0, {}, dict(facts))
    return harness.Reading("c", win, list(spans), {}, {}, trace, peaks)


def test_readers_read_nothing_without_their_inputs():
    for name in ("hop_roofline.bfs", "pagerank_roofline", "device_idle.bfs",
                 "multihop_kernel_share.bfs"):
        assert harness.metric_reader(name).read(name, _reading({})) is None


def test_roofline_readers():
    pk = roofline.peaks("TPU v5 lite")
    trace = {"busy_s": 0.5, "window_s": 2.0}
    kernel = {"name": "multihop.hop", "dur": 250_000,
              "args": {"mode": "kernel"}}
    sparse = {"name": "multihop.hop", "dur": 250_000,
              "args": {"mode": "sparse"}}
    r = _reading({"n_vertices": 1000, "n_edges_distinct": 10**6,
                  "frontier_columns": 1}, [kernel, kernel, sparse], trace, pk)
    least = roofline.least_seconds(*roofline.frontier_hop_work(1000, 10**6, 1),
                                   pk)
    got = harness.metric_reader("hop_roofline.bfs").read("hop_roofline.bfs", r)
    assert got == pytest.approx(100 * 2 * least / 0.5)
    share = harness.metric_reader("multihop_kernel_share.bfs").read(
        "multihop_kernel_share.bfs", r)
    assert share == pytest.approx(100 * 0.5 / 2.0)
    idle = harness.metric_reader("device_idle.bfs").read("device_idle.bfs", r)
    assert idle == pytest.approx(75.0)
    r = _reading({"n_vertices": 1000, "n_edges": 10**6, "iterations": 10},
                 trace=trace, peaks=pk)
    least = roofline.least_seconds(*roofline.pagerank_sweep_work(1000, 10**6),
                                   pk)
    got = harness.metric_reader("pagerank_roofline").read("pagerank_roofline",
                                                          r)
    assert got == pytest.approx(100 * 10 * least / 0.5)
