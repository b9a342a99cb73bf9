#!/usr/bin/env python
"""Distributed routing-plan build at reference-benchmark scale, on the
8-virtual-CPU-device mesh.

Exercises the streaming two-pass plan build (``_plan_stream``) at the size
that motivated it: chain_36_symm (63M representatives — the config behind
the reference's published 38.90 s OpenMP matvec, example/Example05.chpl:97-99)
or square_6x6.  The dense predecessor needed ~36 GB of [D, M, T] host
arrays here; this records what the streaming build actually uses.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python tools/dist_plan_scale.py --config heisenberg_chain_36_symm \
        --reps /tmp/scale_chain36.h5

Prints one JSON line per phase (build seconds, peak RSS, exchange capacity,
split, one verified apply).
"""

import argparse
import json
import os
import resource
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# A 63M-state apply on an oversubscribed virtual CPU mesh reaches its
# all-reduce with ~30+ s of arrival skew (devices execute serially on few
# cores); XLA's default 40 s rendezvous termination then kills the run.
# Must be in XLA_FLAGS before jax initializes.
if "xla_cpu_collective_call_terminate_timeout_seconds" \
        not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_cpu_collective_call_terminate_timeout_seconds=1200")


def log(phase, **kv):
    print(json.dumps({"phase": phase, **kv}), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="heisenberg_chain_36_symm")
    ap.add_argument("--reps", default="/tmp/scale_chain36.h5",
                    help="representative checkpoint (HDF5, save_basis layout)")
    ap.add_argument("--shards", default=None,
                    help="sharded-enumeration file: build SHARD-NATIVE "
                         "(from_shards — the global basis is never built; "
                         "the plan build streams peer shards from this "
                         "file); --reps is then used only as the "
                         "structure-cache path")
    ap.add_argument("--mode", default="compact",
                    choices=("ell", "compact", "fused"))
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--applies", type=int, default=2)
    args = ap.parse_args()

    from distributed_matvec_tpu.io import make_or_restore_representatives
    from distributed_matvec_tpu.models.yaml_io import (
        DATA_DIR, load_config_from_yaml)

    cfg = load_config_from_yaml(
        os.path.join(DATA_DIR, args.config + ".yaml"))
    if args.shards is None:
        t0 = time.time()
        restored = make_or_restore_representatives(cfg.basis, args.reps)
        n = cfg.basis.number_states
        log("representatives", n_states=n, restored=restored,
            seconds=round(time.time() - t0, 1))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_matvec_tpu.parallel.distributed import DistributedEngine

    t0 = time.time()
    # the plan checkpoints beside the representative file, so a rerun (or
    # a later benchmark on returned hardware) restores it in I/O time
    if args.shards is not None:
        eng = DistributedEngine.from_shards(
            cfg.hamiltonian, args.shards, n_devices=args.devices,
            mode=args.mode, structure_cache=args.reps)
    else:
        eng = DistributedEngine(cfg.hamiltonian, n_devices=args.devices,
                                mode=args.mode, structure_cache=args.reps)
    build_s = time.time() - t0
    rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024
    log("plan_build", mode=args.mode, seconds=round(build_s, 1),
        restored=eng.structure_restored,
        peak_rss_mb=int(rss_mb), shard_size=eng.shard_size,
        query_capacity=getattr(eng, "query_capacity", None),
        T0=getattr(eng, "_ell_T0", None),
        backend=jax.default_backend())

    if args.applies:
        xh = eng.random_hashed(seed=42)
        t0 = time.time()
        yh = jax.block_until_ready(eng.matvec(xh))
        log("matvec_first", seconds=round(time.time() - t0, 1))
        t0 = time.perf_counter()
        for _ in range(args.applies):
            yh = eng.matvec(xh, check=False)
        yh.block_until_ready()
        ms = (time.perf_counter() - t0) / args.applies * 1e3
        nrm = float(jnp.linalg.norm(yh))
        log("matvec", ms_per_apply=round(ms, 1), y_norm=round(nrm, 6),
            counters_checked=True)
    rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024
    log("done", peak_rss_mb=int(rss_mb))


if __name__ == "__main__":
    main()
