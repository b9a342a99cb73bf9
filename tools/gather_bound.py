#!/usr/bin/env python
"""Measure the TPU gather roofline that bounds the ELL matvec.

The symmetry-adapted SpMV is index-rate-bound: each ELL table slot (one a
non-zero in the staircase levels, ``LocalEngine._ell_counts``) costs one
row gather of a [., 3] triple-f32 row (the exact f64
split, ops/split_gather.py).  This script measures, on the current backend:

  1. the raw row-gather rate vs table size, index locality, and row width;
  2. the engine's realized rate on a real basis (gathers-only variant vs
     the full matvec).

Round-2 builder findings on a TPU v5e (2026-07, other hardware than the
attached chip; leads to re-measure, not current results — ROADMAP S3):

  * rate is FLAT in index locality (random / sorted / banded / identity all
    ~160-185 M rows/s at a 4.7M-row table) — a bandwidth-minimizing basis
    reordering (RCM) cannot help, the cost is per-row, not per-page;
  * width 3 (the triple-f32 split row) is the sweet spot: ~255 M rows/s at
    2M rows; width 6 ≈ 0.8× the row rate (so pairing two vectors per gather
    is a ~1.6× per-vector win for *block* solvers); width ≥ 12 collapses;
  * Mosaic/Pallas cannot beat this: `tpu.dynamic_gather` only supports a
    single-vreg (8×128) source ("Multiple source vregs along gather
    dimension" is unimplemented), so no VMEM-blocked gather kernel exists
    on this generation;
  * chain_32_symm (N=4 707 969, then a width-20 table + tail): gathers
    alone are ~593 ms of the ~660 ms apply — the engine runs at ≈93% of the gather roofline;
    coefficient streams + f64 multiply-accumulate add only ~20 ms.

Usage: python tools/gather_bound.py [--full]   (--full includes the
4.7M-row chain_32_symm engine breakdown; several minutes of build time)

Every run also PERSISTS its measured rates as a content-addressed
calibration sidecar (``calibration/<fp>.json`` under the artifact root,
keyed by backend + device kind — ``obs/roofline.py``), consumed by
``tools/capacity.py`` (per-mode apply-time estimates) and
``tools/obs_report.py roofline`` (achieved-vs-bound fractions) instead of
the print-and-discard the script used to be.  ``--no-save`` skips the
sidecar; ``--calibration-out PATH`` writes an explicit copy.
"""

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_matvec_tpu.utils.cache import enable_compilation_cache

enable_compilation_cache()

import jax                                             # noqa: E402
import jax.numpy as jnp                                # noqa: E402

CH = 10        # chained applications per jitted program (amortize dispatch)
REPS = 3


def _time_chain(ch, *args):
    """Seconds per application: host clock around work that ends in
    ``block_until_ready`` (the first call compiles and is not timed)."""
    jax.block_until_ready(ch(*args))
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = ch(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / REPS / CH


def gather_rate(n_rows: int, width: int, pattern: str = "random") -> float:
    """M rows/s for a [n_rows, width] f32 table under the index pattern."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.random((n_rows, width), dtype=np.float32))
    g = n_rows
    if pattern == "random":
        ib = rng.integers(0, n_rows, g)
    elif pattern == "sorted":
        ib = np.sort(rng.integers(0, n_rows, g))
    elif pattern == "identity":
        ib = np.arange(g)
    elif pattern == "banded":
        ib = (np.arange(g) + rng.integers(-100_000, 100_000, g)) % n_rows
    else:
        raise ValueError(pattern)
    ib = jnp.asarray(ib.astype(np.int32))

    def chain(x, i):
        acc = jnp.zeros((g, width), jnp.float32)
        for k in range(CH):
            acc = acc + x[(i + np.int32(k)) % np.int32(n_rows)]
        return acc.sum()

    dt = _time_chain(jax.jit(chain), x, ib)
    return g / dt / 1e6


def h2d_rate(nbytes: int = 1 << 26) -> float:
    """Measured host→device transfer bandwidth (bytes/s): time device_put
    of an ``nbytes`` f32 buffer, each transfer ended by
    ``block_until_ready`` (the plan-stream phase bound `obs/roofline.py`
    divides by)."""
    rng = np.random.default_rng(1)
    a = rng.random(nbytes // 4, dtype=np.float32)
    jax.block_until_ready(jax.device_put(a))      # warm the path
    t0 = time.perf_counter()
    for _ in range(REPS):
        jax.block_until_ready(jax.device_put(a))
    per = (time.perf_counter() - t0) / REPS
    return nbytes / max(per, 1e-9)


def engine_breakdown():
    """Gathers-only vs full matvec on the BASELINE headline basis."""
    from distributed_matvec_tpu.models.basis import SpinBasis
    from distributed_matvec_tpu.models.lattices import (chain_edges,
                                                        heisenberg_from_edges)
    from distributed_matvec_tpu.ops.split_gather import split_parts
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    n = 32
    basis = SpinBasis(n, n // 2, 1,
                      [([*range(1, n), 0], 0), ([*reversed(range(n))], 0)])
    op = heisenberg_from_edges(basis, chain_edges(n))
    print("building chain_32_symm basis + engine (minutes)...", flush=True)
    basis.build()
    eng = LocalEngine(op, mode="ell")
    N, Npad = eng.n_states, eng.n_padded
    counts = eng._ell_counts
    x = jnp.asarray(np.random.default_rng(0).standard_normal(N))
    x = x / jnp.linalg.norm(x)
    apply_fn, operands = eng.bound_matvec()

    def chain_full(x, ops):
        for _ in range(CH):
            x = apply_fn(x, ops)[0]
        return x

    full = _time_chain(jax.jit(chain_full), x, operands)

    def gathers_only(x, ops):
        # every table slot's x-row gather, a row block at a time and piece
        # by piece as the engine walks them (shortest first), and the
        # gather back to basis order, a block's length of rows at a time
        blocks, pos_of, _ = ops
        xs = split_parts(x)
        accs = []
        for pieces in blocks:
            acc = jnp.zeros((0, 3), jnp.float32)
            for idx, _ in reversed(pieces):
                acc = jnp.pad(acc, ((0, idx.shape[1] - acc.shape[0]), (0, 0)))
                for t in range(idx.shape[0]):
                    acc = acc + xs[idx[t]]
            accs.append(acc)
        acc = jnp.concatenate(accs)
        acc = jnp.pad(acc, ((0, max(Npad - acc.shape[0], 0)), (0, 0)))
        if pos_of is not None:
            B = accs[0].shape[0] if len(accs) > 1 else Npad
            acc = jnp.concatenate([acc[pos_of[r0:r0 + B]]
                                   for r0 in range(0, Npad, B)])
        return acc.sum(axis=-1).astype(jnp.float64)

    def chain_g(x, ops):
        for _ in range(CH):
            x = gathers_only(x, ops)[:N]
        return x

    g_only = _time_chain(jax.jit(chain_g), x, operands)
    n_gathers = counts["gather_slots"]
    out = {"config": "chain_32_symm", "n_states": int(N), **counts,
           "full_ms": round(full * 1e3, 3),
           "gathers_only_ms": round(g_only * 1e3, 3),
           "engine_rows_per_s": n_gathers / g_only,
           "gather_share": g_only / full}
    print(f"chain_32_symm: N={N} slots={n_gathers} "
          f"levels={counts['levels']}  full {full*1e3:.0f} ms, "
          f"gathers-only {g_only*1e3:.0f} ms "
          f"({n_gathers/g_only/1e6:.0f} M rows/s; engine at "
          f"{100*g_only/full:.0f}% gather share)")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="include the chain_32_symm engine breakdown")
    ap.add_argument("--no-save", action="store_true",
                    help="do not persist the calibration sidecar")
    ap.add_argument("--calibration-out", default=None, metavar="PATH",
                    help="also write the calibration JSON here")
    ap.add_argument("--quick", action="store_true",
                    help="small tables only (CI-speed calibration: the "
                         "rates are slightly optimistic vs the 4.7M-row "
                         "truth, but measured beats default)")
    args = ap.parse_args()
    backend = jax.default_backend()
    device_kind = jax.devices()[0].device_kind
    print(f"backend: {backend} ({device_kind})")

    big = 1 << 18 if args.quick else 4_718_592
    print(f"\n-- locality ({big}-row [.,3] f32 table) --")
    rates = {}
    for pat in ("random", "sorted", "banded", "identity"):
        rates[pat] = gather_rate(big, 3, pat)
        print(f"  {pat:>9}: {rates[pat]:6.0f} M rows/s")

    wtab = 1 << 16 if args.quick else 1 << 21
    print(f"\n-- row width ({wtab}-row table, random) --")
    widths = {}
    for w in (3, 6, 12):
        r = gather_rate(wtab, w)
        widths[w] = r
        print(f"  width {w:>2}: {r:6.0f} M rows/s = {r*w/1e3:5.1f} G elem/s")

    h2d = h2d_rate(1 << 22 if args.quick else 1 << 26)
    print(f"\n-- h2d bandwidth: {h2d/1e9:.2f} GB/s --")

    breakdown = None
    if args.full:
        print()
        breakdown = engine_breakdown()

    # persist what the roofline model and capacity planner consume: the
    # width-3 random-index rate IS the engines' split-row gather bound
    from distributed_matvec_tpu.obs import roofline as _roofline

    cal = dict(_roofline.default_calibration(backend),
               backend=str(backend), device_kind=str(device_kind),
               gather_rows_per_s=rates["random"] * 1e6,
               h2d_bytes_per_s=h2d,
               gather_table_rows=int(big),
               width_rates_m_rows_per_s={str(w): round(r, 1)
                                         for w, r in widths.items()})
    if breakdown:
        cal["engine_breakdown"] = breakdown
    if args.calibration_out:
        _roofline.save_calibration(cal, args.calibration_out)
        print(f"calibration written to {args.calibration_out}")
    if not args.no_save:
        path = _roofline.save_calibration(cal)
        print(f"calibration sidecar: {path or 'artifact layer off'}")


if __name__ == "__main__":
    main()
