#!/usr/bin/env python
"""Scale driver for the distributed-memory (sharded) enumeration.

Streams a big config's representatives straight into per-shard datasets —
never a global host array (StatesEnumeration.chpl:305-514 analog; see
``enumeration/sharded.py``) — and validates the total against the
pure-combinatorics sector-dimension census.

The headline target is ``heisenberg_chain_40_symm`` (C(40,20) = 137.8G
candidates, census 861 725 794 representatives, ~13.8 GB of shard data):

    python tools/sharded_enum_scale.py --config heisenberg_chain_40_symm \
        --out /tmp/shards_chain40.h5 --shards 8

Progress and peak RSS are printed at the end; the shard file doubles as a
checkpoint (reruns restore).
"""

import argparse
import os
import resource
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _rank_worker(args):
    """One rank's slice of a multi-process enumeration (spawned process;
    the group is rebuilt in-process from the YAML config)."""
    config, out, n_shards, rank, n_ranks, chunks, threads = args
    from distributed_matvec_tpu.enumeration.sharded import enumerate_to_shards
    from distributed_matvec_tpu.models.yaml_io import (
        DATA_DIR, load_config_from_yaml)

    cfg = load_config_from_yaml(
        os.path.join(DATA_DIR, config + ".yaml"))
    b = cfg.basis
    t0 = time.time()
    man = enumerate_to_shards(b.number_spins, b.hamming_weight, b.group,
                              n_shards, out, rank=rank, n_ranks=n_ranks,
                              n_chunks=chunks, n_threads=threads)
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024
    return rank, man["total"], time.time() - t0, rss, man["restored"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="heisenberg_chain_40_symm")
    ap.add_argument("--out", default=None)
    ap.add_argument("--shards", type=int, default=8)
    ap.add_argument("--ranks", type=int, default=1,
                    help="enumerating processes: each rank streams a "
                         "disjoint index-space slice into its own part "
                         "file concurrently (the per-locale parallel "
                         "enumeration of StatesEnumeration.chpl:321-334), "
                         "then one finalize census-validates the union")
    ap.add_argument("--threads-per-rank", type=int, default=None,
                    help="native threads per rank (default: cpus/ranks)")
    ap.add_argument("--chunks", type=int, default=None,
                    help="enumeration range chunks (default: sized so one "
                         "256-task batch stays under ~1 GB of buffers)")
    args = ap.parse_args()

    from distributed_matvec_tpu.enumeration.sharded import enumerate_to_shards
    from distributed_matvec_tpu.models.yaml_io import (
        DATA_DIR, load_config_from_yaml)
    from math import comb

    cfg = load_config_from_yaml(
        os.path.join(DATA_DIR, args.config + ".yaml"))
    basis = cfg.basis
    n, hw = basis.number_spins, basis.hamming_weight
    group = basis.group
    out = args.out or f"/tmp/shards_{args.config}.h5"

    candidates = comb(n, hw) if hw is not None else 1 << n
    census = group.sector_dimension_census(hw)
    print(f"{args.config}: {candidates} candidates, |G|={len(group)}, "
          f"census {census} representatives", flush=True)

    chunks = args.chunks
    if chunks is None:
        # per-task survivor cap ~ span/(G/4); keep one 256-task batch's
        # buffers under ~1 GB: 256·(span/chunks)/(G/4)·16B <= 1 GB
        per_batch = 1 << 30
        g4 = max(len(group) // 4, 1)
        chunks = max(64, int(256 * candidates / g4 * 16 / per_batch))
    print(f"using {chunks} range chunks, {args.shards} shards -> {out}",
          flush=True)

    t0 = time.time()
    if args.ranks > 1:
        import multiprocessing as mp
        from concurrent.futures import ProcessPoolExecutor

        from distributed_matvec_tpu.enumeration.sharded import (
            finalize_shard_parts)

        threads = args.threads_per_rank or max(
            (os.cpu_count() or 1) // args.ranks, 1)
        ctx = mp.get_context("spawn")
        with ProcessPoolExecutor(max_workers=args.ranks,
                                 mp_context=ctx) as ex:
            results = list(ex.map(_rank_worker, [
                (args.config, out, args.shards, r, args.ranks,
                 chunks, threads) for r in range(args.ranks)]))
        for rank, tot, dt_r, rss_r, restored in results:
            print(f"rank {rank}: {tot} representatives "
                  f"({'restored' if restored else f'{dt_r:.1f} s'}), "
                  f"peak RSS {rss_r} MB", flush=True)
        man = finalize_shard_parts(n, hw, group, args.shards, out,
                                   args.ranks)
        dt = time.time() - t0
        print(f"total {man['total']} representatives in {dt:.1f} s wall "
              f"({args.ranks} ranks x {threads} threads), "
              f"counts {man['counts']}", flush=True)
    else:
        man = enumerate_to_shards(n, hw, group, args.shards, out,
                                  n_chunks=chunks)
        dt = time.time() - t0
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024
        print(f"total {man['total']} representatives "
              f"({'restored' if man['restored'] else f'{dt:.1f} s'}), "
              f"counts {man['counts']}, peak RSS {rss} MB", flush=True)
    assert man["total"] == census, (man["total"], census)
    print("CENSUS_OK", flush=True)


if __name__ == "__main__":
    main()
