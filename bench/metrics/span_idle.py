"""The device's idle share of the traced window put down to one program
span: the idle seconds during which that span is the innermost note open
on the profiler's host plane, over the window, in %.

One reader for every `span_idle.<part>.<cell kind>`; `<part>` names the
span through `SPANS`. The host notes are the harness's `bench.*`
annotations and the program's `multihop.*` and `psw.*` telemetry spans,
which the program also opens as profiler annotations: a program span
nested in `bench.bfs` takes the idle under it, and what no program span
covers stays with `bench.bfs`. Spans of other names (a maintenance
thread's `service.job`) are left out, since the innermost note is taken
across threads. The newest trace under the harness's trace directory is
reduced once, with bench/trace.py's helpers. Nothing to read without a
trace, a device plane, or an event of the span in the window (a program
that does not open it).
"""
from __future__ import annotations

from pathlib import Path
from typing import Any, Dict, Optional

from bench import harness, trace

SPANS = {
    "probe": "multihop.probe",
    "merge": "multihop.merge",
    "kernel_prep": "multihop.kernel.prep",
    "kernel_wait": "multihop.kernel.wait",
    "pagerank": "psw.pagerank",
}
PROGRAM_PREFIXES = ("multihop.", "psw.")

_cache: Dict[Any, Optional[Dict[str, Any]]] = {}


def _program_spans():
    from repro.core import telemetry
    return {n for n, (kind, _) in telemetry.CATALOG.items()
            if kind == "span" and n.startswith(PROGRAM_PREFIXES)}


def _host_notes(profile, program_spans):
    out = []
    for plane in profile.planes:
        if not plane.name.startswith(trace.HOST_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if (ev.name.startswith(trace.ANNOTATION_PREFIX)
                        or ev.name in program_spans):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
    return sorted(out)


def reduce_profile(profile, program_spans,
                   window_name: str = harness.WINDOW_ANNOTATION
                   ) -> Optional[Dict[str, Any]]:
    """{"window_s", "idle_s": {note: s}, "seen": notes in the window}, or
    None where the trace holds no window or no device op in it. Gaps are
    taken on the first device that ran an op, as bench/trace.py does."""
    notes = _host_notes(profile, program_spans)
    windows = [(a, b) for a, b, n in notes if n == window_name]
    devices = trace._device_ops(profile)
    if not windows or not devices:
        return None
    w0, w1 = windows[0]
    for plane in sorted(devices):
        busy = trace._union([(max(a, w0), min(b, w1))
                             for a, b, _ in devices[plane]
                             if b > w0 and a < w1])
        if busy:
            break
    else:
        return None
    edges = [w0] + [t for iv in busy for t in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    inside = [(a, b, n) for a, b, n in notes if b > w0 and a < w1]
    return {"window_s": (w1 - w0) * 1e-9,
            "idle_s": trace._attribute(gaps, inside, window_name),
            "seen": {n for _, _, n in inside}}


def _reduced(path: Path) -> Optional[Dict[str, Any]]:
    st = path.stat()
    key = (str(path), st.st_mtime_ns, st.st_size)
    if key not in _cache:
        from jax.profiler import ProfileData
        _cache.clear()
        _cache[key] = reduce_profile(ProfileData.from_file(str(path)),
                                     _program_spans())
    return _cache[key]


def share(reduced: Optional[Dict[str, Any]], span: str) -> Optional[float]:
    if reduced is None or span not in reduced["seen"]:
        return None
    return 100.0 * reduced["idle_s"].get(span, 0.0) / reduced["window_s"]


def read(name, reading):
    if reading.trace is None:
        return None
    path = trace.newest_xplane(harness.TRACE_DIR)
    if path is None:
        return None
    return share(_reduced(path), SPANS[name.split(".")[1]])
