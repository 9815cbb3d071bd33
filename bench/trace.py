"""The reduction of a JAX profiler trace to the benchmark's device numbers.

From the `.xplane.pb` the profiler writes, over the window the harness
marked with a host annotation:

- `busy_s`: the union of the intervals in which an operation ran on a
  device (the "XLA Ops" line of each `/device:TPU:<n>` plane), averaged
  over the devices that ran any; `window_s`: the window's length;
- `op_seconds`: device seconds by operation name (summed over devices),
  and `device_ops`, the ten names that took the most;
- `idle_gaps`: the device's idle time within the window, attributed to
  what the host was doing, i.e. the innermost `bench.*` host annotation
  active over each part of each gap, summed by name, the ten largest.

Checked on a synthetic trace and on a small recorded one
(`bench/tests/test_bench_trace.py`).
"""
from __future__ import annotations

import bisect
import heapq
import re
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "/host:"
ANNOTATION_PREFIX = "bench."
TOP = 10

Interval = Tuple[float, float]


def newest_xplane(trace_dir: Path) -> Optional[Path]:
    found = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def reduce_dir(trace_dir: Path, window_name: str) -> Optional[Dict[str, Any]]:
    path = newest_xplane(trace_dir)
    return None if path is None else reduce_file(path, window_name)


def reduce_file(path: Path, window_name: str) -> Optional[Dict[str, Any]]:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(path)), window_name)


def _union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _host_annotations(profile) -> List[Tuple[float, float, str]]:
    out = []
    for plane in profile.planes:
        if not plane.name.startswith(HOST_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(ANNOTATION_PREFIX):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
    return sorted(out)


def _device_ops(profile) -> Dict[str, List[Tuple[float, float, str]]]:
    out = {}
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                out[plane.name] = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                                    ev.name) for ev in line.events]
    return out


def reduce_profile(profile, window_name: str) -> Optional[Dict[str, Any]]:
    """The reduction of one loaded trace (`jax.profiler.ProfileData`), or
    None where it holds no device or no window."""
    notes = _host_annotations(profile)
    windows = [(a, b) for a, b, name in notes if name == window_name]
    devices = _device_ops(profile)
    if not windows or not devices:
        return None
    w0, w1 = windows[0]
    busy_per_device = []
    op_time: Dict[str, float] = {}
    idle: List[Interval] = []
    for plane in sorted(devices):
        clipped = [(max(a, w0), min(b, w1), name)
                   for a, b, name in devices[plane] if b > w0 and a < w1]
        if not clipped:
            continue
        for a, b, name in clipped:
            op_time[name] = op_time.get(name, 0.0) + (b - a) * 1e-9
        busy = _union([(a, b) for a, b, _ in clipped])
        busy_per_device.append(sum(b - a for a, b in busy))
        if not idle:   # gaps are attributed on the first busy device
            edges = [w0] + [t for iv in busy for t in iv] + [w1]
            idle = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    if not busy_per_device:
        return None
    return {
        "busy_s": sum(busy_per_device) / len(busy_per_device) * 1e-9,
        "window_s": (w1 - w0) * 1e-9,
        "devices": len(busy_per_device),
        "op_seconds": op_time,
        "device_ops": _top(op_time),
        "idle_gaps": _top(_attribute(idle, notes, window_name)),
    }


def _innermost(notes, window_name: str) -> List[Tuple[float, float, str]]:
    """The window cut into segments, each labelled with the innermost host
    annotation active in it (the latest started; the window's own where no
    other is)."""
    inner = sorted((a, b, n) for a, b, n in notes if n != window_name)
    cuts = sorted({t for a, b, _ in inner for t in (a, b)})
    segments = []
    active: List[Tuple[float, float, str]] = []   # heap by -start
    i = 0
    for t0, t1 in zip(cuts, cuts[1:]):
        while i < len(inner) and inner[i][0] <= t0:
            a, b, n = inner[i]
            heapq.heappush(active, (-a, b, n))
            i += 1
        while active and active[0][1] <= t0:
            heapq.heappop(active)
        # an ended annotation below the top is dropped when it surfaces
        if active:
            segments.append((t0, t1, active[0][2]))
    return segments


def _attribute(gaps: List[Interval], notes, window_name: str
               ) -> Dict[str, float]:
    """Idle seconds by the innermost host annotation active in them."""
    segments = _innermost(notes, window_name)
    starts = [a for a, _, _ in segments]
    out: Dict[str, float] = {}
    for g0, g1 in gaps:
        covered = 0.0
        j = max(0, bisect.bisect_right(starts, g0) - 1)
        while j < len(segments) and segments[j][0] < g1:
            a, b, n = segments[j]
            overlap = min(b, g1) - max(a, g0)
            if overlap > 0:
                out[n] = out.get(n, 0.0) + overlap * 1e-9
                covered += overlap
            j += 1
        rest = (g1 - g0) - covered
        if rest > 0:
            out[window_name] = out.get(window_name, 0.0) + rest * 1e-9
    return out


def _top(totals: Dict[str, float]) -> List[List[Any]]:
    return [[k, v] for k, v in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:TOP]]
