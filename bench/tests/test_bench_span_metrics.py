"""The readers of the program's spans and counters on the device's clock:
`span_idle.*` (device idle put down to the innermost program span) on a
synthetic trace with known intervals, and `hop_h2d_mb.bfs` in the tiny
traced BFS run; both read nothing from a program that lacks the span or
the counter."""
from __future__ import annotations

import pytest
from jax.profiler import ProfileData

from bench import harness

# host plane, one line per thread, times in us from the window's start:
# window [0, 100), bench.bfs [10, 90), multihop.hop [20, 80) holding
# probe [20, 35), kernel.prep [40, 55), kernel.wait [55, 70) and merge
# [70, 78); another thread's service.job over the whole window. One
# device op [58, 68): idle [0, 58) and [68, 100)
SYNTHETIC = '''
planes {
  id: 1
  name: "/host:CPU"
  lines {
    id: 1
    name: "python"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 80000000 }
    events { metadata_id: 3 offset_ps: 20000000 duration_ps: 60000000 }
    events { metadata_id: 4 offset_ps: 20000000 duration_ps: 15000000 }
    events { metadata_id: 5 offset_ps: 40000000 duration_ps: 15000000 }
    events { metadata_id: 6 offset_ps: 55000000 duration_ps: 15000000 }
    events { metadata_id: 7 offset_ps: 70000000 duration_ps: 8000000 }
  }
  lines {
    id: 2
    name: "maintenance"
    timestamp_ns: 1000
    events { metadata_id: 8 offset_ps: 0 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.bfs" } }
  event_metadata { key: 3 value { id: 3 name: "multihop.hop" } }
  event_metadata { key: 4 value { id: 4 name: "multihop.probe" } }
  event_metadata { key: 5 value { id: 5 name: "multihop.kernel.prep" } }
  event_metadata { key: 6 value { id: 6 name: "multihop.kernel.wait" } }
  event_metadata { key: 7 value { id: 7 name: "multihop.merge" } }
  event_metadata { key: 8 value { id: 8 name: "service.job" } }
}
planes {
  id: 2
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 58000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "frontier_expand" } }
}
'''
# idle us by innermost note: window 10 + 10, bench.bfs 10 + 10, probe 15,
# hop 5 + 2, prep 15, wait 3 + 2, merge 8: 90 us of the 100 us window
WANT_IDLE_US = {"bench.window": 20, "bench.bfs": 20, "multihop.probe": 15,
                "multihop.hop": 7, "multihop.kernel.prep": 15,
                "multihop.kernel.wait": 5, "multihop.merge": 8}
WANT_SHARE = {"span_idle.probe.bfs": 15.0, "span_idle.merge.bfs": 8.0,
              "span_idle.kernel_prep.bfs": 15.0,
              "span_idle.kernel_wait.bfs": 5.0}


@pytest.fixture
def span_idle():
    return harness.metric_reader("span_idle.probe.bfs")


def test_nested_program_spans_take_the_idle_under_them(span_idle):
    r = span_idle.reduce_profile(ProfileData.from_text_proto(SYNTHETIC),
                                 span_idle._program_spans())
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["idle_s"] == pytest.approx(
        {k: v * 1e-6 for k, v in WANT_IDLE_US.items()})
    assert "service.job" not in r["idle_s"]


def _reading(trace=None, before=None, after=None):
    return harness.Reading("graph500-s20.bfs", harness.Window(1, 0, 1.0, {}),
                           [], {"counters": before or {}},
                           {"counters": after or {}}, trace, None)


def test_span_idle_reads_the_newest_trace(span_idle, tmp_path,
                                          monkeypatch):
    run = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    run.mkdir(parents=True)
    (run / "vm.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(SYNTHETIC))
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    traced = _reading(trace={"window_s": 100e-6, "busy_s": 10e-6})
    for name, want in WANT_SHARE.items():
        assert span_idle.read(name, traced) == pytest.approx(want), name
    # a span the program never opened (a program without it) reads nothing
    assert span_idle.read("span_idle.pagerank.pagerank", traced) is None
    # no device op in the window: nothing to read
    assert span_idle.read("span_idle.probe.bfs", _reading()) is None
    host_only = SYNTHETIC.split("planes {\n  id: 2")[0]
    assert span_idle.reduce_profile(ProfileData.from_text_proto(host_only),
                                    span_idle._program_spans()) is None


@pytest.mark.parametrize("before,after,want", [
    ({"multihop.hops": {"kernel": 2}, "multihop.kernel.h2d_bytes": 10},
     {"multihop.hops": {"kernel": 5, "sparse": 9},
      "multihop.kernel.h2d_bytes": 3_000_010}, 1.0),
    ({}, {"multihop.hops": {"sparse": 3},
          "multihop.kernel.h2d_bytes": 0}, None),
    ({}, {"multihop.hops": {"kernel": 3}}, None),
], ids=["per-kernel-hop", "no-kernel-hop", "no-counter"])
def test_hop_h2d_mb_reads_the_counter_per_kernel_hop(before, after, want):
    reader = harness.metric_reader("hop_h2d_mb.bfs")
    assert reader.read("hop_h2d_mb.bfs", _reading(None, before, after)) \
        == want


def test_tiny_traced_bfs_reads_the_upload_bytes(run_tiny, tmp_path,
                                                monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    res = run_tiny("graph500-s20.bfs", trace=True)
    assert res["correct"]
    mb = res["metrics"]["hop_h2d_mb.bfs"]
    assert mb["unit"] == "MB" and mb["value"] > 0
    # every kernel hop ships the same plan and panel: a whole byte count
    assert mb["value"] * 1e6 == pytest.approx(round(mb["value"] * 1e6),
                                              abs=1e-3)
    # the CPU writes no device plane: the device metrics read nothing
    assert not any(n.startswith("span_idle.") for n in res["metrics"])
