#!/usr/bin/env python
"""Measured distributed apply at reference-benchmark scale, from a shard file.

The scale rung this tool exists for is chain_40_symm: 862M representatives
(the ≥10⁹-state regime of the reference's README.md:69-116; its in-tree
OpenMP chain_40 matvec anchor is 682.93 s, example/Example05.chpl:100-102).
Fused mode needs no plan build, so the staged shard file multiplies
directly.

Verification protocol (all cross-mesh comparable):
* counters validated on the first eager apply (overflow / out-of-sector);
* the probe vector is STATE-KEYED (``DistributedEngine.state_keyed_hashed``)
  — a pure function of the basis state — so ⟨x, Hx⟩ and ‖Hx‖ must agree
  between mesh sizes (run once with --devices 8 on the 8-shard file, once
  with --devices 4 on its ``reshard_shards`` copy) and between repeated
  runs at the same size.

Run context (loadavg before/after) is recorded in the JSON so wall-clock
numbers stay comparable round over round (VERDICT r4 "weak" #1).

    python tools/scale_apply.py --config heisenberg_chain_40_symm \
        --shards /tmp/shards_chain40.h5 --mode fused --devices 8 --applies 1
"""

import argparse
import json
import os
import resource
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The default kRemoteBufferSize-parity cap (150k) clips the per-peer
# exchange capacity below the per-chunk mean at benchmark-scale term
# counts (measured: chain_32_symm B=65536, T=32 needs ~165k) — the engine
# then fails validation loudly.  Scale runs default the cap high; the
# engine still sizes the actual buffers by mean×headroom when smaller.
os.environ.setdefault("DMT_REMOTE_BUFFER_SIZE", "3000000")


def log(phase, **kv):
    print(json.dumps({"phase": phase, **kv}), flush=True)


def _load():
    return list(os.getloadavg())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="heisenberg_chain_40_symm")
    ap.add_argument("--shards", default="/tmp/shards_chain40.h5")
    ap.add_argument("--mode", default="fused",
                    choices=("ell", "compact", "fused"))
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--applies", type=int, default=1,
                    help="timed applies after the first (compiling) one")
    ap.add_argument("--salt", type=int, default=0)
    ap.add_argument("--structure-cache", default=None)
    ap.add_argument("--platform", default="cpu",
                    help="cpu (default: a virtual-device mesh of --devices "
                         "CPU devices) or anything else to leave the "
                         "platform to JAX")
    args = ap.parse_args()

    if args.platform == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            flags += (" --xla_force_host_platform_device_count="
                      f"{args.devices}")
        # Arrival skew at a collective scales with per-apply wall time on
        # an oversubscribed virtual mesh (XLA's CPU runtime kills the
        # process at 40 s by default; a chain_40 fused apply legitimately
        # takes far longer).  CPU rig only: the TPU runtime parses the same
        # XLA_FLAGS string and aborts on names it does not know.
        if "xla_cpu_collective_call_terminate_timeout_seconds" not in flags:
            flags += (" --xla_cpu_collective_call_terminate_timeout_seconds="
                      + os.environ.get("DMT_SCALE_RDV_TIMEOUT", "43200"))
        os.environ["XLA_FLAGS"] = flags
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp

    from distributed_matvec_tpu.models.yaml_io import (
        DATA_DIR, load_config_from_yaml)
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine

    class _Cfg:                      # the two benchmark lattices whose YAMLs
        pass                         # the reference never shipped (its
    # Makefile:84-85,107-108 references them commented out) are built from
    # the package's lattice generators; S-form ops matching the reference's
    # kagome configs (data/heisenberg_kagome_16.yaml)
    if args.config == "kagome_36_symm":
        from distributed_matvec_tpu.models.basis import SpinBasis
        from distributed_matvec_tpu.models.lattices import (
            heisenberg_from_edges, kagome_36_edges,
            kagome_torus_translations)

        cfg = _Cfg()
        basis = SpinBasis(36, 18, 1, kagome_torus_translations(4, 3, 0, 0))
        cfg.hamiltonian = heisenberg_from_edges(
            basis, kagome_36_edges(), spin_half_ops=True)
    elif args.config == "pyrochlore_2x2x2":
        from distributed_matvec_tpu.models.lattices import (
            heisenberg_pyrochlore)

        cfg = _Cfg()
        cfg.hamiltonian = heisenberg_pyrochlore(2, 2, 2)
    else:
        cfg = load_config_from_yaml(
            os.path.join(DATA_DIR, args.config + ".yaml"))
    log("start", config=args.config, shards=args.shards, mode=args.mode,
        devices=args.devices, backend=jax.default_backend(),
        loadavg=_load())

    t0 = time.time()
    eng = DistributedEngine.from_shards(
        cfg.hamiltonian, args.shards, n_devices=args.devices,
        mode=args.mode, structure_cache=args.structure_cache)
    log("engine", n_states=eng.n_states, shard_size=eng.shard_size,
        mode=eng.mode, seconds=round(time.time() - t0, 1),
        restored=eng.structure_restored,
        peak_rss_mb=resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        // 1024)

    t0 = time.time()
    xh = eng.state_keyed_hashed(salt=args.salt)
    xh = jax.block_until_ready(xh)
    log("probe_vector", seconds=round(time.time() - t0, 1),
        x_norm=float(jnp.linalg.norm(xh)))

    t0 = time.time()
    yh = jax.block_until_ready(eng.matvec(xh))   # eager: validates counters
    first_s = time.time() - t0
    log("matvec_first", seconds=round(first_s, 1), counters_checked=True,
        loadavg=_load())

    steady_s = None
    if args.applies:
        t0 = time.perf_counter()
        for _ in range(args.applies):
            yh = eng.matvec(xh, check=False)
        yh.block_until_ready()
        steady_s = (time.perf_counter() - t0) / args.applies

    xhx = float(eng.dot(xh, yh)) if eng.real else complex(eng.dot(xh, yh))
    y_norm = float(jnp.linalg.norm(yh))
    log("result", s_per_apply=None if steady_s is None
        else round(steady_s, 2),
        first_apply_s=round(first_s, 1),
        xHx=repr(xhx), y_norm=repr(y_norm),
        n_states=eng.n_states, devices=args.devices, mode=args.mode,
        peak_rss_mb=resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        // 1024,
        loadavg=_load())


if __name__ == "__main__":
    main()
