#!/usr/bin/env python
"""profile_diff — op-by-op differential of two HLO cost profiles.

Usage:
    python tools/profile_diff.py BASE.json NEW.json [--threshold 0.25]
                                 [--top 10] [--json]

BASE/NEW are content-addressed ``hlo-profile`` artifacts written by the
compile path (``obs/hlo.py`` via ``precompile()``) under the XLA
artifact cache (``hlo-profile/<fp2>/<fp>.json``).  Exit 1 when any op
axis grew beyond the threshold (direction-aware: every HLO cost is
cost-like, growth is the regression — the same gate semantics as
``obs_report diff``), exit 2 when an input is not a profile artifact.

For diffing whole RUNS (resolving the newest artifact through their
``hlo_cost`` events) use ``obs_report profile <run> <run>``; this tool
is the artifact-level primitive.

Standalone by construction: loads ``obs/hlo.py`` by file (its
import-dual header keeps the pure diff surface), never imports the
package, never initializes a JAX backend.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys


def _load_hlo():
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "distributed_matvec_tpu", "obs", "hlo.py")
    spec = importlib.util.spec_from_file_location("dmt_obs_hlo_diff", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="diff two hlo-profile artifacts op-by-op "
                    "(exit 1 on gated regression)")
    ap.add_argument("base", help="baseline hlo-profile artifact .json")
    ap.add_argument("new", help="candidate hlo-profile artifact .json")
    ap.add_argument("--threshold", type=float, default=0.25,
                    help="per-op relative growth that gates as a "
                         "regression (default 0.25)")
    ap.add_argument("--top", type=int, default=10,
                    help="rows per table (default 10)")
    ap.add_argument("--json", action="store_true",
                    help="print the machine-readable diff dict")
    args = ap.parse_args(argv)

    hlo = _load_hlo()
    profs = []
    for path in (args.base, args.new):
        try:
            profs.append(hlo.load_profile(path))
        except (OSError, ValueError, json.JSONDecodeError) as e:
            print(f"profile_diff: not an hlo profile artifact: "
                  f"{path} ({e})", file=sys.stderr)
            return 2
    base, new = profs
    diff = hlo.diff_profiles(base, new, threshold=args.threshold,
                             top=args.top)
    if args.json:
        print(json.dumps(diff, indent=1, sort_keys=True))
    else:
        print(f"base {base.get('program')} "
              f"[{str(base.get('fingerprint', ''))[:16]}]  ->  "
              f"new {new.get('program')} "
              f"[{str(new.get('fingerprint', ''))[:16]}]")
        hlo.print_profile_diff(diff)
    if diff["regressions"]:
        if not args.json:
            print(f"\nREGRESSION: {len(diff['regressions'])} op-axis(es) "
                  f"grew beyond {args.threshold:.0%}")
        return 1
    if not args.json:
        print(f"\nno per-op regression beyond {args.threshold:.0%}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
