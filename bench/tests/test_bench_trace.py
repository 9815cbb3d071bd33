"""bench/trace.py's reduction: on a synthetic trace with known intervals
(the device planes a TPU writes), and on a small trace the JAX profiler
recorded on the CPU (`data/cpu_trace.xplane.pb`: a `bench.window` holding
a `bench.device` matmul and a 10 ms host sleep annotated `bench.host`),
which has host planes only; and on the same trace recorded on a TPU v5e
(`data/v5e_trace.xplane.pb`)."""
from __future__ import annotations

from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import trace

RECORDED = Path(__file__).parent / "data" / "cpu_trace.xplane.pb"
RECORDED_V5E = Path(__file__).parent / "data" / "v5e_trace.xplane.pb"

SYNTHETIC = '''
planes {
  id: 1
  name: "/host:CPU"
  lines {
    id: 1
    name: "python"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 60000000 duration_ps: 30000000 }
    events { metadata_id: 3 offset_ps: 65000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.host" } }
  event_metadata { key: 3 value { id: 3 name: "bench.inner" } }
}
planes {
  id: 2
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 30000000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 30000000 }
    events { metadata_id: 1 offset_ps: 95000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "copy.2" } }
}
planes {
  id: 3
  name: "/device:TPU:0 SparseCore"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "other" } }
}
'''


def test_synthetic_trace_reduces_to_known_numbers():
    # window [0, 100) us; ops [10, 40) + [20, 50) + [95, 105): busy 45 us
    # after clipping; idle [0, 10) and [50, 95), of which inner [65, 75)
    # lies within host [60, 90); the rest, 25 us, is the window's own
    r = trace.reduce_profile(ProfileData.from_text_proto(SYNTHETIC),
                             "bench.window")
    assert r["busy_s"] == pytest.approx(45e-6)
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["devices"] == 1
    assert dict(r["device_ops"]) == pytest.approx({"fusion.1": 35e-6,
                                                   "copy.2": 30e-6})
    assert r["device_ops"][0][0] == "fusion.1"
    assert dict(r["idle_gaps"]) == pytest.approx({
        "bench.window": 25e-6, "bench.host": 20e-6, "bench.inner": 10e-6})


def test_no_window_or_no_device_reads_nothing():
    pd = ProfileData.from_text_proto(SYNTHETIC)
    assert trace.reduce_profile(pd, "bench.absent") is None
    host_only = SYNTHETIC.split("planes {\n  id: 2")[0]
    assert trace.reduce_profile(ProfileData.from_text_proto(host_only),
                                "bench.window") is None


def test_recorded_trace_is_parsed():
    pd = ProfileData.from_file(str(RECORDED))
    notes = trace._host_annotations(pd)
    names = [n for _, _, n in notes]
    assert names == ["bench.window", "bench.device", "bench.host"]
    (w0, w1, _), _, (h0, h1, _) = notes
    assert w0 <= h0 < h1 <= w1
    assert (h1 - h0) * 1e-9 == pytest.approx(0.01, rel=0.5)
    # the CPU writes no /device:TPU plane: nothing to read, never a 0
    assert trace.reduce_file(RECORDED, "bench.window") is None


def test_v5e_trace_has_the_planes_the_reduction_reads():
    """A trace recorded on a TPU v5e (`data/record_trace.py`): the device
    plane and its op line carry the names the reduction matches."""
    pd = ProfileData.from_file(str(RECORDED_V5E))
    ops = trace._device_ops(pd)
    assert list(ops) == ["/device:TPU:0"]
    assert any("fusion" in name for _, _, name in ops["/device:TPU:0"])
    names = [n for _, _, n in trace._host_annotations(pd)]
    assert names == ["bench.window", "bench.device", "bench.host"]
