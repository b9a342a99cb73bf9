#!/usr/bin/env python
"""Enumerate the reference's two remaining benchmark lattices into
census-validated shard files.

The reference's Makefile carries kagome_36 and pyrochlore_2x2x2 as
benchmark-states-enumeration workloads (Makefile:84-85,107-108; data files
not shipped).  The TPU-native forms this tool stages:

* ``kagome_36`` — 4×3 kagome torus, hw=18, momentum (0,0) + spin
  inversion: |G| = 24, census 378,143,714 representatives (the full
  C(36,18) ≈ 9.1·10⁹ hamming space is disk-infeasible here; the
  symmetry-adapted sector is the same physics at 1/24 the footprint).
* ``pyrochlore_2x2x2`` — 32 sites, hw=16, no symmetry: census
  C(32,16) = 601,080,390 representatives, exactly the commented reference
  workload's basis.

Streams through ``enumerate_to_shards`` (bounded memory, per-shard sorted,
census-validated); ``--ranks R`` exercises the multi-process part-file
path (cyclic chunk dealing) with a final ``finalize_shard_parts``.

    python tools/big_lattice_enum.py --lattice kagome_36 \
        --out /tmp/shards_kagome36.h5 --shards 8
"""

import argparse
import json
import os
import resource
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# a host-only scale tool: hold JAX to the CPU whatever the environment says
os.environ["JAX_PLATFORMS"] = "cpu"


def log(phase, **kv):
    print(json.dumps({"phase": phase, **kv}), flush=True)


def make_basis(lattice: str):
    from distributed_matvec_tpu.models.basis import SpinBasis
    from distributed_matvec_tpu.models.lattices import (
        kagome_torus_translations)

    if lattice == "kagome_36":
        return SpinBasis(36, 18, 1, kagome_torus_translations(4, 3, 0, 0))
    if lattice == "pyrochlore_2x2x2":
        return SpinBasis(32, 16)
    raise SystemExit(f"unknown lattice {lattice!r}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--lattice", required=True,
                    choices=("kagome_36", "pyrochlore_2x2x2"))
    ap.add_argument("--out", required=True)
    ap.add_argument("--shards", type=int, default=8)
    ap.add_argument("--ranks", type=int, default=1)
    ap.add_argument("--rank", type=int, default=None,
                    help="(internal) run ONE rank's part and exit")
    args = ap.parse_args()

    b = make_basis(args.lattice)
    hw = b.hamming_weight
    from distributed_matvec_tpu.enumeration.sharded import (
        enumerate_to_shards, finalize_shard_parts)

    if args.rank is not None:
        man = enumerate_to_shards(b.number_spins, hw, b.group, args.shards,
                                  args.out, rank=args.rank,
                                  n_ranks=args.ranks)
        log("rank_done", rank=args.rank, counts=man["counts"],
            peak_rss_mb=resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
            // 1024)
        return

    census = b.group.sector_dimension_census(hw)
    log("start", lattice=args.lattice, census=census, shards=args.shards,
        ranks=args.ranks, loadavg=list(os.getloadavg()))
    t0 = time.time()
    if args.ranks == 1:
        man = enumerate_to_shards(b.number_spins, hw, b.group, args.shards,
                                  args.out)
    else:
        procs = [subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--lattice", args.lattice, "--out", args.out,
             "--shards", str(args.shards), "--ranks", str(args.ranks),
             "--rank", str(r)]) for r in range(args.ranks)]
        failed = None
        for p in procs:
            if p.wait() != 0 and failed is None:
                failed = p.returncode
                for q in procs:       # don't leave orphan ranks grinding
                    if q.poll() is None:
                        q.terminate()
        if failed is not None:
            raise SystemExit(f"rank subprocess failed: {failed}")
        man = finalize_shard_parts(b.number_spins, hw, b.group, args.shards,
                                   args.out, n_ranks=args.ranks)
    wall = time.time() - t0
    assert man["total"] == census, (man["total"], census)
    log("done", total=man["total"], census=census, seconds=round(wall, 1),
        restored=man["restored"], counts=man["counts"],
        states_per_s=int(man["total"] / max(wall, 1e-9)),
        peak_rss_mb=resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        // 1024, loadavg=list(os.getloadavg()))


if __name__ == "__main__":
    main()
