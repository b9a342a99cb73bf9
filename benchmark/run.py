#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up, measures for ``--seconds``, compares what the window
produced with the plain reference, and prints the result as one JSON object
on the last line of standard output; each number compared is printed beside
its limit as the last lines of standard error.  With ``--trace 1`` the
window runs under the profiler and the per-layer metrics are reported in
place of the end-to-end ones.  Exits non-zero, with no result line, when JAX
finds no TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()      # set-up is counted from here

import argparse                    # noqa: E402
import json                        # noqa: E402
import os                          # noqa: E402
import sys                         # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import check, harness

    bench = harness.load_benchmark()
    try:
        result = harness.run_cell(bench, args.workload, args.seed,
                                  args.seconds, bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    sys.stdout.flush()
    check.report(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
