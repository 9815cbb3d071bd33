"""Discovery by name, and BENCHMARK.json against the benchmark contract.

A further configuration, traffic mix or per-layer metric is new files plus
new BENCHMARK.json entries, with no edit to an existing file: a test-only
driver, mix, configuration and metric reader are found by name alone."""
from __future__ import annotations

import json
import re

import pytest

from bench import harness
from bench.tests.conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_every_name_in_the_spec_has_its_files(spec):
    assert spec["paths"] == ["bench"]
    assert spec["command"] == ["python3", "bench/run.py"]
    for c in spec["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for w in spec["workloads"]:
        mix = harness.traffic(w["traffic"])
        assert (BENCH / "drivers" / f"{mix['driver']}.py").is_file()
        assert w["config"] in {c["name"] for c in spec["configs"]}
    for m in spec["per_layer"]:
        assert hasattr(harness.metric_reader(m["name"]), "read")


def test_the_spec_keeps_the_contract(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert isinstance(spec["run_seconds"], int)
    assert 1 <= spec["run_seconds"] <= 51
    # a full check with 24 cells fits its 43,200 s
    runs = 2 + 14 * 24
    assert runs * (spec["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    cells = {w["name"] for w in spec["workloads"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e["setup_s"]
    metrics = spec["end_to_end"] + spec["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= cells
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        if m["name"].split(".")[0].endswith("_roofline"):
            assert m["unit"] == "%"
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        reported = harness.cell_metrics(spec, w["name"], "end_to_end")
        assert "setup_s" in {m["name"] for m in reported}
        assert len(reported) >= 2
        assert harness.cell_metrics(spec, w["name"], "per_layer")
    assert len({(w["config"], w["traffic"])
                for w in spec["workloads"]}) == len(spec["workloads"])
    texts = ([c["source"] for c in spec["configs"]]
             + [c["why"] for c in spec["configs"]]
             + [w["why"] for w in spec["workloads"]]
             + [m["layer"] for m in spec["per_layer"]] + spec["command"])
    assert all(1 <= len(t) <= 200 and "\n" not in t and "\t" not in t
               for t in texts)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_cell_metrics_follow_workloads_and_moves():
    spec = {"end_to_end": [{"name": "setup_s"},
                           {"name": "a", "workloads": ["x"]},
                           {"name": "b", "workloads": ["y"]}],
            "per_layer": [{"name": "l1", "moves": "a"},
                          {"name": "l2", "moves": "b", "workloads": ["y"]},
                          {"name": "l3", "moves": "setup_s"}]}
    names = lambda ms: [m["name"] for m in ms]  # noqa: E731
    assert names(harness.cell_metrics(spec, "x", "end_to_end")) == \
        ["setup_s", "a"]
    assert names(harness.cell_metrics(spec, "x", "per_layer")) == \
        ["l1", "l3"]
    assert names(harness.cell_metrics(spec, "y", "per_layer")) == \
        ["l2", "l3"]


ECHO_DRIVER = '''
"""A test-only driver: counts the steps it takes in the window."""
import time
from bench.harness import Check, Window


def setup(ctx):
    return {"steps": 0, "per_step": ctx.traffic["per_step"]}


def window(state, seconds):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        state["steps"] += state["per_step"]
    return Window(state["steps"], 0, time.perf_counter() - t0, {})


def summarize(state, win, trace):
    win.metrics["echo_rate"] = state["steps"] / win.elapsed_s
    win.facts["steps"] = state["steps"]


def release(state):
    pass


def verify(state):
    return [Check("echo_steps_odd", state["steps"] % 2, 0)]
'''

ECHO_METRIC = '''
def read(name, reading):
    return float(reading.window.facts["steps"])
'''


def test_new_cell_is_found_by_name_alone(tiny_bench, spec, run_tiny):
    (tiny_bench / "drivers" / "echo.py").write_text(ECHO_DRIVER)
    (tiny_bench / "metrics" / "echo_steps.py").write_text(ECHO_METRIC)
    (tiny_bench / "traffic" / "echo.json").write_text(
        json.dumps({"driver": "echo", "per_step": 2}))
    (tiny_bench / "configs" / "echo-cfg.json").write_text(
        json.dumps({"name": "echo-cfg"}))
    spec = dict(spec)
    spec["configs"] = spec["configs"] + [
        {"name": "echo-cfg", "source": "test", "reduced": [], "why": "test",
         "file": "bench/configs/echo-cfg.json"}]
    spec["workloads"] = spec["workloads"] + [
        {"name": "echo-cfg.echo", "config": "echo-cfg", "traffic": "echo",
         "chips": 1, "why": "test"}]
    spec["end_to_end"] = spec["end_to_end"] + [
        {"name": "echo_rate", "unit": "steps/s", "better": "higher",
         "bound": 0.1, "source": "host_clock", "workloads": ["echo-cfg.echo"]}]
    spec["per_layer"] = spec["per_layer"] + [
        {"name": "echo_steps.echo", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "echo", "moves": "echo_rate"}]
    res = run_tiny("echo-cfg.echo", spec_=spec, seconds=0.05)
    assert res["correct"] and set(res["metrics"]) == {"setup_s", "echo_rate"}
    res = run_tiny("echo-cfg.echo", spec_=spec, seconds=0.05, trace=True)
    assert set(res["metrics"]) == {"echo_steps.echo"}
    assert res["metrics"]["echo_steps.echo"]["value"] == res["attempted"]


def test_unknown_names_are_refused(spec):
    with pytest.raises(harness.BenchError):
        harness.workload(spec, "no-such-cell")
    with pytest.raises(harness.BenchError):
        harness.driver("no_such_driver")
    with pytest.raises(harness.BenchError):
        harness.metric_reader("no_such_metric.bfs")
