"""The benchmark's harness: discovery by name, the run's timeline, the
window's tracing, and the result line.

A run of one cell goes: set-up (the driver builds the store from the seed
and warms every shape its window uses) -> the measured window -> the
device's peak memory is read -> the program's device state is freed -> the
driver compares what the window produced with the plain reference ->
one JSON result line. With `trace=True` the window runs under the JAX
profiler and the per-layer metrics are read; otherwise the end-to-end ones.

Nothing here knows a cell: a configuration is `configs/<name>.json`, a
traffic mix `traffic/<name>.json` naming its driver `drivers/<driver>.py`,
and a per-layer metric `metrics/<name>.py` (or, for a metric named
`<base>.<suffix>`, `metrics/<base>.py`).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
OUT_DIR = BENCH_DIR / "out"
TRACE_DIR = OUT_DIR / "trace"
WINDOW_ANNOTATION = "bench.window"


class BenchError(RuntimeError):
    """A cell that cannot run as specified (no chip, unknown name)."""


# ---------------------------------------------------------------------------
# discovery by name
# ---------------------------------------------------------------------------
def read_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark_spec(root: Path = ROOT) -> Dict[str, Any]:
    return read_json(root / "BENCHMARK.json")


def workload(spec: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str, bench_dir: Path = BENCH_DIR) -> Dict[str, Any]:
    return read_json(bench_dir / "configs" / f"{name}.json")


def traffic(name: str, bench_dir: Path = BENCH_DIR) -> Dict[str, Any]:
    return read_json(bench_dir / "traffic" / f"{name}.json")


def load_module(path: Path):
    """Import one file of the benchmark by its path (names may hold '.' and
    '-', so not through the package system)."""
    modname = "bench_dyn_" + "".join(c if c.isalnum() else "_"
                                     for c in str(path.resolve()))
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None or spec.loader is None:
        raise BenchError(f"cannot import {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def driver(name: str, bench_dir: Path = BENCH_DIR):
    path = bench_dir / "drivers" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no driver {name!r} ({path})")
    return load_module(path)


def metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The reader of a per-layer metric: `metrics/<name>.py`, else the file
    of its base name (`device_idle.bfs` -> `metrics/device_idle.py`)."""
    for stem in (name, name.split(".", 1)[0]):
        path = bench_dir / "metrics" / f"{stem}.py"
        if path.is_file():
            return load_module(path)
    raise BenchError(f"no reader for metric {name!r}")


def cell_metrics(spec: Dict[str, Any], cell: str, kind: str
                 ) -> List[Dict[str, Any]]:
    """The metrics of `kind` ("end_to_end" or "per_layer") a cell reports.
    A metric with a `workloads` key is reported in those cells; an
    end-to-end one without it in every cell; a per-layer one without it in
    every cell that reports the end-to-end metric it moves."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


# ---------------------------------------------------------------------------
# what drivers and readers exchange with the harness
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Ctx:
    """What a driver's `setup` gets."""

    cell: str
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    workdir: str
    log: Callable[[str], None]


@dataclasses.dataclass
class Window:
    """What a driver's `window` returns: requests or traversals attempted
    and failed in it, and its elapsed host seconds. The driver's
    `summarize` then fills its end-to-end values and the facts the
    per-layer readers need (sizes, counts)."""

    attempted: int
    failed: int
    elapsed_s: float
    metrics: Dict[str, float]
    facts: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Check:
    """One number of the comparison with the plain reference, and its
    limit: the run is correct where every value is at most its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Reading:
    """What a per-layer reader gets."""

    cell: str
    window: Window
    spans: List[Dict[str, Any]]          # telemetry events of the window
    counters_before: Dict[str, Any]      # telemetry snapshot at its start
    counters_after: Dict[str, Any]       # ... and at its end
    trace: Optional[Dict[str, Any]]      # bench/trace.py's reduction
    peaks: Optional[Dict[str, Any]]      # bench/roofline.py's device row


def annotate(name: str, **kw):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax
    return jax.profiler.TraceAnnotation(name, **kw)


class CompileCounter:
    """XLA compiles of this process, from JAX's monitoring events: programs
    compiled or loaded from the persistent cache (a cache hit counts too),
    and the cache's hits and misses."""

    def __init__(self):
        import jax
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def totals(self) -> Dict[str, Any]:
        return {"programs": self.programs, "seconds": self.seconds,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def _telemetry():
    from repro.core import telemetry
    return telemetry


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0    # a Python tracer would swamp the window
    opts.host_tracer_level = 1      # TraceAnnotations and runtime TraceMes
    return opts


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def require_devices(chips: int):
    """The devices of a TPU with at least `chips` chips; a BenchError
    otherwise (the benchmark never falls back to the CPU)."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: jax.devices()[0] is {devs[0].platform} "
                         f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    return devs


def _device_info(devs) -> Dict[str, Any]:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def run_cell(cell: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             spec: Optional[Dict[str, Any]] = None,
             bench_dir: Path = BENCH_DIR, compile_cache: bool = True,
             log: Callable[[str], None] = None) -> Dict[str, Any]:
    """Run one cell and return its result object (the last stdout line).
    `t_start` is the host clock when the process began: set-up is counted
    from there to the window's start. Tests on the CPU pass
    `require_tpu=False`, `compile_cache=False` and a `spec` and `bench_dir`
    of their own."""
    import jax
    from repro.compile_cache import enable_compile_cache

    if log is None:
        def log(msg):
            print(f"[bench] {msg}", file=sys.stderr, flush=True)
    spec = benchmark_spec() if spec is None else spec
    w = workload(spec, cell)
    devs = require_devices(w["chips"]) if require_tpu else jax.devices()
    cache = enable_compile_cache() if compile_cache else None
    log(f"cell {cell} seed {seed} seconds {seconds} trace {int(trace)}; "
        f"device {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"compile cache {cache}")
    compiles = CompileCounter()
    cfg = config(w["config"], bench_dir)
    mix = traffic(w["traffic"], bench_dir)
    drv = driver(mix["driver"], bench_dir)
    telemetry = _telemetry()

    with tempfile.TemporaryDirectory(prefix="bench_store_") as workdir:
        ctx = Ctx(cell, cfg, mix, int(seed), float(seconds), workdir, log)
        state = drv.setup(ctx)
        try:
            c0 = compiles.totals()
            telemetry.trace_events(clear=True)
            before = telemetry.snapshot()
            if trace:
                shutil.rmtree(TRACE_DIR, ignore_errors=True)
                jax.profiler.start_trace(str(TRACE_DIR),
                                         profiler_options=_profile_options())
            setup_s = time.perf_counter() - t_start
            try:
                with annotate(WINDOW_ANNOTATION):
                    win = drv.window(state, float(seconds))
            finally:
                if trace:
                    jax.profiler.stop_trace()
            after = telemetry.snapshot()
            spans = telemetry.trace_events(clear=True)
            c1 = compiles.totals()
            in_window = {k: c1[k] - c0[k] for k in c1}
            log(f"set-up {setup_s} s; compiles in set-up {json.dumps(c0)}")
            log(f"compiles in the window: {json.dumps(in_window)}")
            drv.summarize(state, win, trace)
            device = _device_info(devs)
        finally:
            drv.release(state)
        t0 = time.perf_counter()
        checks = drv.verify(state)
        log(f"reference comparison: {time.perf_counter() - t0} s")

    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if trace:
        from . import roofline
        from . import trace as trace_mod
        reduced = trace_mod.reduce_dir(TRACE_DIR, WINDOW_ANNOTATION)
        peaks = (roofline.peaks(device["kind"])
                 if device["platform"] == "tpu" else None)
        if reduced is None and require_tpu:
            raise BenchError(f"the trace in {TRACE_DIR} holds no TPU op "
                             f"inside the {WINDOW_ANNOTATION} annotation")
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
        reading = Reading(cell, win, spans, before, after, reduced, peaks)
        for m in cell_metrics(spec, cell, "per_layer"):
            value = metric_reader(m["name"], bench_dir).read(m["name"],
                                                             reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell_metrics(spec, cell, "end_to_end"):
            value = setup_s if m["name"] == "setup_s" \
                else win.metrics.get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    correct = bool(checks) and all(c.ok for c in checks) and win.failed == 0
    result = {"correct": correct, "attempted": win.attempted,
              "failed": win.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window_compiles"] = in_window
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def print_result(result: Dict[str, Any]) -> None:
    """The checks as the last lines on stderr, then the result as the last
    line on stdout."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
