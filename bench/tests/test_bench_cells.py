"""Each cell end to end at a tiny size on the CPU, through the same
harness, drivers and comparison the chip run uses."""
from __future__ import annotations

import json

import pytest

from bench import harness

CELLS = ("graph500-s20.bfs", "twitter2010-s20.pagerank")


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_is_correct_at_tiny_size(run_tiny, spec, cell, trace):
    res = run_tiny(cell, trace=trace)
    assert res["correct"], res
    assert res["attempted"] >= 1 and res["failed"] == 0
    keys = list(res)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics",
                        "device"]
    assert keys[-1] == "checks"
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]
    if not trace:
        want = {m["name"] for m in harness.cell_metrics(spec, cell,
                                                        "end_to_end")}
        assert set(res["metrics"]) == want
        assert all(m["value"] > 0 for m in res["metrics"].values())
    else:
        allowed = {m["name"] for m in harness.cell_metrics(spec, cell,
                                                           "per_layer")}
        assert set(res["metrics"]) <= allowed
        # no device plane on the CPU: device numbers are left out, never 0
        assert "busy_s" not in res["device"]


def test_bfs_kernel_share_is_read_from_spans(run_tiny):
    res = run_tiny("graph500-s20.bfs", trace=True)
    share = res["metrics"]["multihop_kernel_share.bfs"]
    assert share["unit"] == "%" and 0 < share["value"] <= 100


def test_result_and_checks_are_the_last_lines(run_tiny, capsys):
    res = run_tiny("twitter2010-s20.pagerank")
    harness.print_result(res)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == res
    last = err.strip().splitlines()[-len(res["checks"]):]
    for line, (name, c) in zip(last, res["checks"].items()):
        assert line == f"check {name}: {c['value']} (limit {c['limit']})"
