"""The controls of the SSSP cells: the plain reference put in the
program's place with one precision step down or one guarantee broken, fed
to the same comparison that decides a run's `correct`. Each must come out
not correct; the bfloat16 control's numbers set the upper reading of the
`sssp_err_over_bound` limit (PERF.md).

    python3 bench/control_sssp.py --workload <cell> --seeds <n> [<n> ...]

prints one JSON line per seed and control with its numbers and limits. It
runs at the cell's own size, on the chip it is started on, without a
store: the controls' answers do not need one.

- `bf16`: the same relax in bfloat16 on the device: distances, weights and
  each add in bfloat16, the least candidate per destination, until no
  distance falls (one step below the float32 the program states).
- `stale`: the float64 reference over a stale epoch, the stored edges
  without the last acknowledged load batch (breaks "every traversal reads
  one published epoch that holds every acknowledged write").

Both for the window's own first `TRAVERSALS` roots.
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

from bench import data, harness, reference_sssp  # noqa: E402

TRAVERSALS = 2  # the window's first roots each control answers for


def sssp_bf16(src, dst, w, n, roots):
    """Bellman-Ford in bfloat16 on the default device over every stored
    edge, from each root in turn: float32 copies of the distances."""
    import jax
    import jax.numpy as jnp

    order = np.argsort(dst, kind="stable")
    s = jnp.asarray(np.asarray(src)[order], jnp.int32)
    d = jnp.asarray(np.asarray(dst)[order], jnp.int32)
    wb = jnp.asarray(np.asarray(w, np.float32)[order]).astype(jnp.bfloat16)

    @jax.jit
    def run(root):
        dist = jnp.full(n, jnp.inf, jnp.bfloat16).at[root].set(0)

        def body(carry):
            dist, _ = carry
            cand = jax.ops.segment_min(dist[s] + wb, d, num_segments=n,
                                       indices_are_sorted=True)
            new = jnp.minimum(dist, cand)
            return new, jnp.any(new < dist)

        return jax.lax.while_loop(lambda c: c[1], body,
                                  (dist, jnp.bool_(True)))[0]

    return [np.asarray(run(int(r)), np.float32) for r in roots]


def sssp_control(cfg: Dict[str, Any], mix: Dict[str, Any], seed: int,
                 kind: str, traversals: int) -> List[harness.Check]:
    sssp = harness.driver("sssp")
    _, _, src, dst, w, roots = sssp.inputs(cfg, mix, seed)
    n = data.n_vertices(cfg)
    roots = [int(r) for r in roots[int(mix["warmup_roots"]):][:traversals]]
    if kind == "bf16":
        dists = sssp_bf16(src, dst, w, n, roots)
    elif kind == "stale":
        keep = src.shape[0] - int(cfg["load_batch"])
        stale = reference_sssp.WeightedCSR(src[:keep], dst[:keep], w[:keep],
                                           n)
        dists = [reference_sssp.sssp_bounds(stale, r)[0] for r in roots]
    else:
        raise harness.BenchError(f"no SSSP control {kind!r}")
    return sssp.compare(list(zip(roots, dists)), src, dst, w, n)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    harness.require_devices(1)
    spec = harness.benchmark_spec()
    w = harness.workload(spec, args.workload)
    cfg, mix = harness.config(w["config"]), harness.traffic(w["traffic"])
    for seed in args.seeds:
        for kind in ("bf16", "stale"):
            checks = sssp_control(cfg, mix, seed, kind, TRAVERSALS)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "control": kind,
                              "correct": all(c.ok for c in checks),
                              "checks": {c.name: {"value": c.value,
                                                  "limit": c.limit}
                                         for c in checks}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
