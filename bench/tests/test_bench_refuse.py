"""The command refuses, with a non-zero exit and no result line, where it
cannot measure: without a TPU (it never falls back to the CPU), and in a
directory that holds only BENCHMARK.json and the benchmark's own files."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

from bench.tests.conftest import ROOT

ARGS = ["bench/run.py", "--workload", "graph500-s20.bfs",
        "--seed", "3000000000", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_refuses_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_a_traced_run_whose_trace_holds_no_device_op_is_refused(
        tiny_bench, spec, monkeypatch):
    """On a chip a trace without a device plane is a fault of the
    reduction or the profiler, never a reason to leave metrics out."""
    import time

    import jax
    import pytest

    from bench import harness

    monkeypatch.setattr(harness, "require_devices",
                        lambda chips: jax.devices())
    with pytest.raises(harness.BenchError, match="holds no TPU op"):
        harness.run_cell("twitter2010-s20.pagerank", 5, 0.2, True,
                         time.perf_counter(), require_tpu=True, spec=spec,
                         bench_dir=tiny_bench, compile_cache=False,
                         log=lambda msg: None)
