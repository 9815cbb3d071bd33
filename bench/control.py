"""The controls: the plain reference put in the program's place with one
guarantee broken or one precision step down, fed to the same comparison
that decides a run's `correct`. Each must come out not correct; its
numbers set the upper reading of each limit (PERF.md).

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...]

prints one JSON line per seed with the control's numbers and limits. It
runs at the cell's own size, on the chip it is started on, without a
store: the control's answers do not need one.

- `bfs` cells: the reference BFS over a stale epoch, the stored edges
  without the last acknowledged load batch (breaks "every traversal
  reads one published epoch that holds every acknowledged write"), for
  the window's own first roots.
- `pagerank` cells: the reference PageRank with ranks, contributions and
  sums in bfloat16 on the device (one precision step below the float32
  the program states).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List

import numpy as np

if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import data, harness, reference  # noqa: E402


def bfs_control(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int,
                traversals: int) -> List[harness.Check]:
    bfs = harness.driver("bfs")
    u, v = data.edges(cfg, seed)
    src, dst = data.stored_edges(cfg, u, v)
    n = data.n_vertices(cfg)
    roots = data.search_keys(cfg, seed, src, dst, mix["roots"],
                             mix["root_min_degree"])
    roots = roots[int(mix["warmup_roots"]):][:traversals]
    keep = src.shape[0] - int(cfg["load_batch"])
    stale = reference.CSR(src[:keep], dst[:keep], n)
    answers = []
    for root in roots:
        depth = reference.bfs_depths(stale, int(root), mix["k"])
        levels = [np.flatnonzero(depth == d) for d in range(depth.max() + 1)]
        answers.append((int(root), levels))
    return bfs.compare(answers, src, dst, n, mix["k"])


def pagerank_bf16(src, dst, n, n_partitions, iters, damping=0.85):
    """The reference PageRank computed in bfloat16 on the default device,
    returned in the store's device layout (P, L) by internal id."""
    import jax
    import jax.numpy as jnp

    bf = jnp.bfloat16
    s, d = jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32)
    inv_deg = (1.0 / jnp.maximum(jnp.bincount(s, length=n), 1)).astype(bf)

    @jax.jit
    def run(r):
        def body(r, _):
            contrib = (r * inv_deg)[s]
            acc = jax.ops.segment_sum(contrib, d, num_segments=n)
            return (bf(1 - damping) + bf(damping) * acc).astype(bf), None
        return jax.lax.scan(body, r, None, length=iters)[0]

    r = np.asarray(run(jnp.ones(n, bf)), np.float32)
    interval_len = -(-n // n_partitions)
    out = np.zeros(n_partitions * interval_len, np.float32)
    out[reference.psw_internal_ids(n, n_partitions, interval_len)] = r
    return out.reshape(n_partitions, interval_len)


def pagerank_control(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int,
                     iters: int) -> List[harness.Check]:
    pr = harness.driver("pagerank")
    u, v = data.edges(cfg, seed)
    src, dst = data.stored_edges(cfg, u, v)
    n = data.n_vertices(cfg)
    ranks = pagerank_bf16(src, dst, n, cfg["store"]["n_partitions"], iters)
    return pr.compare([ranks], src, dst, n, iters)


def control(cell: str, seed: int, spec=None) -> List[harness.Check]:
    spec = harness.benchmark_spec() if spec is None else spec
    w = harness.workload(spec, cell)
    cfg, mix = harness.config(w["config"]), harness.traffic(w["traffic"])
    if mix["driver"] == "bfs":
        return bfs_control(cfg, mix, seed, traversals=4)
    if mix["driver"] == "pagerank":
        return pagerank_control(cfg, mix, seed,
                                harness.driver("pagerank")
                                .default_iterations())
    raise harness.BenchError(f"no control for driver {mix['driver']!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    harness.require_devices(1)
    for seed in args.seeds:
        checks = control(args.workload, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": all(c.ok for c in checks),
                          "checks": {c.name: {"value": c.value,
                                              "limit": c.limit}
                                     for c in checks}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
