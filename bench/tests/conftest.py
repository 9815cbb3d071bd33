"""Shared fixtures of the benchmark's CPU tests: a benchmark directory at a
tiny size, built from the real configuration and traffic files with only
their sizes cut, and the real drivers and metric readers."""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

BENCH = ROOT / "bench"
TINY_STORE = {"n_partitions": 4, "n_levels": 2, "branching": 4,
              "buffer_cap": 4096, "max_partition_edges": 65536}


def _tiny_configs():
    g = json.loads((BENCH / "configs" / "graph500-s20.json").read_text())
    g.update(vertices=1024, store=TINY_STORE, load_batch=8192,
             generator=dict(g["generator"], scale=10))
    t = json.loads((BENCH / "configs" / "twitter2010-s20.json").read_text())
    t.update(vertices=4096, store=TINY_STORE, load_batch=8192,
             generator=dict(t["generator"], n_vertices=4096, n_edges=36000))
    return {"graph500-s20": g, "twitter2010-s20": t}


def make_tiny_bench(dest: Path) -> Path:
    """`dest` laid out as `bench/`: the real drivers, metric readers and
    traffic files, and the real configurations cut to ~1k vertices."""
    for sub in ("drivers", "metrics", "traffic"):
        shutil.copytree(BENCH / sub, dest / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (dest / "configs").mkdir()
    for name, cfg in _tiny_configs().items():
        (dest / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    return dest


@pytest.fixture
def tiny_bench(tmp_path):
    return make_tiny_bench(tmp_path / "bench")


@pytest.fixture
def spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.fixture
def run_tiny(tiny_bench, spec):
    """Run a cell of the real BENCHMARK.json at the tiny size on the CPU,
    through the harness the chip run uses."""
    from bench.harness import run_cell

    def run(cell, trace=False, seconds=0.3, seed=2**31 + 11, bench=None,
            spec_=None):
        return run_cell(cell, seed, seconds, trace, time.perf_counter(),
                        require_tpu=False, spec=spec_ or spec,
                        bench_dir=bench or tiny_bench, compile_cache=False,
                        log=lambda msg: None)
    return run
