"""Run one cell of the benchmark on the TPU this process is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. Exits 2, printing no result, where JAX finds
no TPU or fewer chips than the cell asks for. The last stdout line is the
result object; the last stderr lines are the numbers compared with the
plain reference, each beside its limit.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench.harness import BenchError, print_result, run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START)
    except BenchError as e:
        print(f"[bench] refused: {e}", file=sys.stderr, flush=True)
        return 2
    print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
