#!/usr/bin/env python3
"""Readings that limits are set from: a cell's compared numbers over many
seeds, and the control's, in one process that pays the set-up once.

    python3 benchmark/readings.py --workload <name> [--workload <name> ...]
        --seeds 12 --control 3 [--first-seed N]

Workloads of one configuration share the engine.  For every seed the mix
runs its shortest window (one apply, one whole solve) at the cell's own
size, and what it produced is compared exactly as a run compares it.  For
the first ``--control`` seeds the control's answers are compared too.  One
JSON line per reading, and a last line with the largest sound reading and
the smallest control reading of every number.  Not part of a benchmark run.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", action="append", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_500_000_000)
    args = ap.parse_args(argv)

    from benchmark import harness, traffic
    from benchmark.system import System

    bench = harness.load_benchmark()
    cells = [harness.find(bench["workloads"], w, "workload")
             for w in args.workload]
    if len({c["config"] for c in cells}) != 1:
        raise SystemExit("the workloads must share one configuration")
    config = harness.load_config(bench, cells[0]["config"])
    system = System(config)
    harness.require_chips(system.start(), max(c["chips"] for c in cells))
    n_states = system.enumerate()
    system.build_engine()
    ref = None
    summary = {}
    for cell in cells:
        sound, control = {}, {}
        for i in range(args.seeds):
            seed = args.first_seed + i
            mix = traffic.make(cell["traffic"], seed)
            if "start_seed" in mix.params:
                # a run's solves all start alike; the readings take a
                # start vector per seed, so that limits cover them all
                mix.params["start_seed"] = seed
            t0 = time.perf_counter()
            if i == 0:
                mix.warm_up(system, n_states)
            else:
                mix.prepare(system, n_states)
            window = mix.window(system, 0.0, harness.annotator(False))
            answers = mix.collect(system)
            if ref is None:
                ref = mix.reference(config)
            ref.resample(seed)
            numbers = mix.compare(ref, answers)
            line = {"workload": cell["name"], "seed": seed, "kind": "sound",
                    "numbers": numbers, "window": window,
                    "seconds": time.perf_counter() - t0}
            print(json.dumps(line), flush=True)
            for k, v in numbers.items():
                sound[k] = max(sound.get(k, v), v)
            if i < args.control:
                numbers = mix.compare(ref, mix.control(ref, answers))
                print(json.dumps({"workload": cell["name"], "seed": seed,
                                  "kind": "control", "numbers": numbers}),
                      flush=True)
                for k, v in numbers.items():
                    control[k] = min(control.get(k, v), v)
        summary[cell["name"]] = {"largest_sound": sound,
                                 "smallest_control": control}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
