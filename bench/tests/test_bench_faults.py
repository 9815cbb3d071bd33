"""The comparison that decides `correct` catches the faults each cell can
have: the harness's look for a chip is skipped, the rest of a run is
driven with the timed path broken underneath, and `correct` comes out
false. And each control, fed to the same comparison, fails it."""
from __future__ import annotations

import dataclasses
import functools

import numpy as np
import pytest

import repro.core
from bench import control, harness
from bench.tests.conftest import _tiny_configs


def _bfs_fault(kind):
    real = repro.core.khop

    @functools.wraps(real)
    def khop(g, seeds, k, **kw):
        res = real(g, seeds, k, **kw)
        levels = [np.asarray(lv) for lv in res.levels]
        if kind == "unchanged":          # the traversal never advances
            levels = levels[:1]
        elif kind == "half":             # half of every level left out
            levels = [levels[0]] + [lv[: lv.shape[0] // 2]
                                    for lv in levels[1:]]
        elif kind == "altered":          # one answer altered where made
            last = levels[-1].copy()
            last[0] = levels[0][0]
            levels[-1] = last
        visited = np.unique(np.concatenate(levels))
        return dataclasses.replace(res, levels=levels, visited=visited)
    return khop


def _pagerank_fault(kind):
    real = repro.core.pagerank_device

    @functools.wraps(real)
    def pagerank_device(dg, **kw):
        if kind == "unchanged":          # the state is returned unchanged
            import jax.numpy as jnp
            return jnp.ones((dg.n_partitions, dg.interval_len), jnp.float32)
        if kind == "half":               # half of the edges left out
            dg = dataclasses.replace(dg, mask=dg.mask.at[:, ::2].set(False))
            return real(dg, **kw)
        r = real(dg, **kw)               # one answer altered where made
        return r.at[0, 0].multiply(1.01)
    return pagerank_device


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_bfs_fault_is_not_correct(run_tiny, monkeypatch, kind):
    monkeypatch.setattr(repro.core, "khop", _bfs_fault(kind))
    res = run_tiny("graph500-s20.bfs")
    assert res["correct"] is False
    assert res["checks"]["bfs_depth_mismatches"]["value"] > 0


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_pagerank_fault_is_not_correct(run_tiny, monkeypatch, kind):
    monkeypatch.setattr(repro.core, "pagerank_device", _pagerank_fault(kind))
    res = run_tiny("twitter2010-s20.pagerank")
    assert res["correct"] is False
    c = res["checks"]["pagerank_err_over_bound"]
    assert c["value"] > c["limit"]


def test_a_traversal_that_fails_in_the_window_is_not_correct(
        run_tiny, monkeypatch):
    real, calls = repro.core.khop, []

    def khop(*a, **kw):
        calls.append(1)
        if len(calls) > 1:          # the warm-up traversal passes
            raise RuntimeError("planted")
        return real(*a, **kw)
    monkeypatch.setattr(repro.core, "khop", khop)
    res = run_tiny("graph500-s20.bfs")
    assert res["correct"] is False and res["failed"] == res["attempted"]


def test_bfs_control_fails():
    cfg = _tiny_configs()["graph500-s20"]
    mix = harness.traffic("bfs")
    checks = control.bfs_control(cfg, mix, seed=5, traversals=4)
    assert not all(c.ok for c in checks)


def test_pagerank_control_fails():
    cfg = _tiny_configs()["twitter2010-s20"]
    mix = harness.traffic("pagerank")
    iters = harness.driver("pagerank").default_iterations()
    checks = control.pagerank_control(cfg, mix, seed=5, iters=iters)
    assert not all(c.ok for c in checks)
