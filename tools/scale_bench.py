#!/usr/bin/env python
"""Scale benchmark: the reference's own benchmark workload, square_6x6.

``make benchmark-states-enumeration`` / ``benchmark-matrix-vector-product``
in the reference run ``data/heisenberg_square_6x6.yaml`` (Makefile:82-86) —
9.08e9 candidate states, |G| = 288 (Tx·Ty·Px·Py·inversion), far beyond the
config matrix the tests run.  This script drives the same config end to end
on whatever backend is default:

  1. enumerate representatives (native C++ streaming kernel), checkpointing
     them into an HDF5 file so a rerun skips straight to the compute;
  2. build the jitted engine (ELL if the packed tables fit, else compact
     4 B/entry for qualifying isotropic sectors, else fused);
  3. time the steady-state matvec and a few Lanczos iterations.

Prints one JSON line per phase.  Usage:

    python tools/scale_bench.py [--out /tmp/square_6x6.h5] [--config NAME]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_matvec_tpu.utils.cache import enable_compilation_cache

enable_compilation_cache()

import numpy as np                                     # noqa: E402


def log(phase, **kv):
    print(json.dumps({"phase": phase, **kv}), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="heisenberg_square_6x6.yaml")
    ap.add_argument("--out", default="/tmp/scale_square_6x6.h5",
                    help="representative checkpoint (HDF5)")
    ap.add_argument("--mode", default=None,
                    choices=(None, "ell", "compact", "fused"))
    ap.add_argument("--solver-iters", type=int, default=8)
    args = ap.parse_args()

    from distributed_matvec_tpu.io import make_or_restore_representatives
    from distributed_matvec_tpu.models.yaml_io import (
        DATA_DIR, load_config_from_yaml)

    cfg = load_config_from_yaml(
        os.path.join(DATA_DIR, args.config))
    t0 = time.time()
    restored = make_or_restore_representatives(cfg.basis, args.out)
    n = cfg.basis.number_states
    log("enumerate", n_states=n, restored=restored,
        seconds=round(time.time() - t0, 1))

    import jax
    import jax.numpy as jnp

    from distributed_matvec_tpu.parallel.engine import LocalEngine

    op = cfg.hamiltonian
    T = op.off_diag_table.x.shape[0]
    # Packed-ELL estimate: (i32 idx + f64 coeff) · N · T0, with the typical
    # ~0.55 fill after the two-level split.  The two-pass low-memory build
    # (LocalEngine._build_ell_lowmem) keeps the build peak at packed size,
    # so the packed estimate — not the full-width one — gates ELL.  Beyond
    # that, "compact" (4 B/entry sign-tagged indices, isotropic sectors
    # only) stretches ~3× further; fused is the unbounded fallback.
    est_gb = n * T * 12 * 0.65 / 1e9
    # standard packed ELL must leave headroom for matvec temporaries —
    # an 8.5 GB table built fine but the apply ResourceExhausted'd at
    # runtime on the 16 GB chip; beyond ~6 GB prefer compact (4 B/entry)
    mode = args.mode or ("ell" if est_gb < 6.0 else "compact")
    log("engine_select", num_terms=T, est_packed_ell_gb=round(est_gb, 2),
        mode=mode)

    t0 = time.time()
    try:
        # the structure is checkpointed alongside the representatives, so a
        # rerun restores it in I/O time instead of minutes of build
        eng = LocalEngine(op, mode=mode, structure_cache=args.out)
    except (ValueError, RuntimeError) as e:
        # compact refuses up front (ValueError) or after full build-time
        # ratio validation (RuntimeError) — fall back to fused either way
        if mode != "compact":
            raise
        log("engine_fallback", reason=str(e)[:120])
        mode = "fused"
        eng = LocalEngine(op, mode=mode)
    log("engine_build", seconds=round(time.time() - t0, 1),
        ell_gb=round(eng.ell_nbytes / 1e9, 2),
        structure_restored=getattr(eng, "structure_restored", False),
        backend=jax.default_backend())

    x = jnp.asarray(np.random.default_rng(42).standard_normal(n))
    x = x / jnp.linalg.norm(x)
    t0 = time.time()
    y = jax.block_until_ready(eng.matvec(x))
    log("matvec_compile", seconds=round(time.time() - t0, 1))
    reps = 5
    t0 = time.perf_counter()
    for _ in range(reps):
        y = eng._matvec(x)[0]
    jax.block_until_ready(y)
    ms = (time.perf_counter() - t0) / reps * 1e3
    log("matvec", ms_per_apply=round(ms, 1),
        reference_openmp_36_site_chain_s=38.9)

    if args.solver_iters:
        from distributed_matvec_tpu.solve import lanczos
        t0 = time.time()
        res = lanczos(eng.matvec, n, k=1, max_iters=args.solver_iters,
                      seed=42)
        log("lanczos", iters=res.num_iters,
            seconds=round(time.time() - t0, 1),
            steady_iters_per_s=round(res.steady_iters_per_s, 3),
            e0_estimate=float(res.eigenvalues[0]))


if __name__ == "__main__":
    main()
