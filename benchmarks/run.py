"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--scale 1.0] [--only NAME]

Writes JSON to experiments/bench/ and prints the tables. `--scale` shrinks
graph sizes for CI (1.0 ≈ a laptop-minute per table; the paper's twitter-2010
scale is reached with --scale 1500 and a large SSD).
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.compile_cache import enable_compile_cache

from . import (bench_disk, bench_fof, bench_insert, bench_linkbench,
               bench_multihop, bench_psw, bench_query, bench_service,
               bench_storage)

SUITES = {
    "storage": bench_storage.run,      # paper Table 1
    "insert": bench_insert.run,        # paper Fig 7a
    "linkbench": bench_linkbench.run,  # paper Table 2 + Fig 8a
    "query": bench_query.run,          # paper Fig 7b + Fig 8c
    "fof": bench_fof.run,              # paper Table 3 + Fig 8b
    "psw": bench_psw.run,              # paper §6 + device PSW
    "disk": bench_disk.run,            # ISSUE 3: out-of-core + Fig 8c real I/O
    "service": bench_service.run,      # ISSUE 4: snapshot readers + maintenance
    "multihop": bench_multihop.run,    # ISSUE 6: columnar k-hop operators
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    names = [args.only] if args.only else list(SUITES)
    for name in names:
        print(f"\n=== bench: {name} (scale={args.scale}) ===")
        t0 = time.time()
        SUITES[name](scale=args.scale)
        print(f"=== {name} done in {time.time() - t0:.1f}s ===")


if __name__ == "__main__":
    main()
