#!/usr/bin/env python
"""How good is f64 on this device?  The probe behind PERF.md's f64 findings.

The TPU has no f64 unit; XLA emulates the type.  This prints, for whatever
device JAX finds (run it through the chip tool for the TPU, plainly for the
CPU's exact-IEEE baseline), every figure the Lanczos repair of PR 22 rests on:

  dot        ``jit(jnp.vdot)`` against ``jit(sum(a*b))`` standalone, n = 12,870
             and 4,707,969, relative to a long-double reference;
  elementwise  max error of mul, sqrt, div, add and ``p*q + r`` over 2^20
             standard-normal values, relative to the RESULT and relative to
             the largest OPERAND.  Read the first for mul, sqrt and div
             (their results can exceed their operands) and both for add and
             ``p*q + r``, where a cancelling result is small against them;
  combine    ``solve.lanczos._combine_rows`` against ``jnp.tensordot`` for
             S[96, 4], V[104, 100000]: max abs error and the values' scale;
  in_solver  the 16-site ring (``data/heisenberg_chain_16.yaml``) solved with
             the solver's elementwise ``_vdot`` and with ``jnp.vdot`` swapped
             back in — same engine, same solver, E0 against the exact value;
  restart    the thick restart (``_make_restart``) at the default Krylov cap
             (96 rows kept to 24) on a 4,707,969-wide buffer: seconds (median
             of 3 after the compile) and the device's peak bytes.

One JSON object per section on stdout, and all of them in
``chiprun_out/f64_probe.json``.
"""

import importlib
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ANCHOR_E0 = 4 * -7.1422963606          # 16-site ring, verify skill's table
M_CAP, L_KEEP = 96, 24                 # the single-vector solver's defaults
FULL_N = 4_707_969                     # chain_32_symm's representatives


def probe_dot(n, rng):
    import jax
    import jax.numpy as jnp

    a = rng.standard_normal(n)
    b = rng.standard_normal(n) + 0.5 * a
    ref = float(np.dot(a.astype(np.longdouble), b.astype(np.longdouble)))
    aj, bj = jnp.asarray(a), jnp.asarray(b)
    got = {"vdot": float(jax.jit(jnp.vdot)(aj, bj)),
           "sum_of_products": float(
               jax.jit(lambda x, y: jnp.sum(x * y))(aj, bj)),
           "numpy_f64": float(np.dot(a, b))}
    return {"n": n, "reference": ref,
            **{f"{k}_rel_err": abs(v - ref) / abs(ref)
               for k, v in got.items()}}


def probe_elementwise(rng, count=1 << 20):
    import jax
    import jax.numpy as jnp

    x = np.abs(rng.standard_normal(count)) + 1e-3
    y, z = rng.standard_normal(count), rng.standard_normal(count)
    xj, yj, zj = map(jnp.asarray, (x, y, z))
    cases = {
        "mul": (jnp.multiply(yj, zj), y * z, np.maximum(abs(y), abs(z))),
        "sqrt": (jnp.sqrt(xj), np.sqrt(x), x),
        "div": (jnp.divide(yj, xj), y / x, np.maximum(abs(y), x)),
        "add": (jnp.add(yj, zj), y + z, np.maximum(abs(y), abs(z))),
        "mul_add": (jax.jit(lambda p, q, r: p * q + r)(yj, zj, xj),
                    y * z + x, np.maximum(abs(y * z), x)),
    }
    out = {"count": count, "values": "standard normal (x: |normal| + 1e-3)"}
    for name, (got, ref, operand) in cases.items():
        err = np.abs(np.asarray(got) - ref)
        out[name] = {
            "max_err_rel_to_result": float(np.max(
                err / np.maximum(np.abs(ref), 1e-300))),
            "max_err_rel_to_operand": float(np.max(err / operand))}
    return out


def probe_combine(rng, r=M_CAP, l=4, n=100_000):
    import jax
    import jax.numpy as jnp

    lz = importlib.import_module("distributed_matvec_tpu.solve.lanczos")
    S, V = rng.standard_normal((r, l)), rng.standard_normal((r + 8, n))
    ref = S.T @ V[:r]
    Sj, Vj = jnp.asarray(S), jnp.asarray(V)
    got = {"combine_rows": lz._combine_rows(Sj, Vj),
           "tensordot": jax.jit(lambda s, v: jnp.tensordot(
               s, v[:r], axes=[[0], [0]]))(Sj, Vj)}
    return {"S": [r, l], "V": [r + 8, n],
            "max_abs_value": float(np.max(np.abs(ref))),
            **{f"{k}_max_abs_err": float(np.max(np.abs(np.asarray(v) - ref)))
               for k, v in got.items()}}


def probe_in_solver():
    import jax.numpy as jnp

    from distributed_matvec_tpu.models.yaml_io import (
        DATA_DIR, load_config_from_yaml)
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    lz = importlib.import_module("distributed_matvec_tpu.solve.lanczos")
    cfg = load_config_from_yaml(
        os.path.join(DATA_DIR, "heisenberg_chain_16.yaml"))
    cfg.basis.build()
    os.environ["DMT_ARTIFACT_CACHE"] = "off"     # build here, write nothing
    eng = LocalEngine(cfg.hamiltonian, mode="ell")
    n = cfg.basis.number_states
    out = {"n_states": int(n), "exact_E0": ANCHOR_E0}
    elementwise = lz._vdot
    try:
        for name, dot in (("elementwise_vdot", elementwise),
                          ("jnp_vdot_in_program", jnp.vdot)):
            lz._vdot = dot
            res = lz.lanczos(eng.matvec, n, k=1, tol=1e-10)
            out[name] = {"E0": float(res.eigenvalues[0]),
                         "E0_minus_exact": float(res.eigenvalues[0])
                         - ANCHOR_E0,
                         "iterations": int(res.num_iters)}
    finally:
        lz._vdot = elementwise
    return out


def probe_restart(n, rng):
    import jax
    import jax.numpy as jnp

    lz = importlib.import_module("distributed_matvec_tpu.solve.lanczos")
    rows = lz._buffer_rows(M_CAP)
    restart = lz._make_restart(M_CAP, (n,), jnp.float64, L_KEEP)
    S = jnp.asarray(np.linalg.qr(rng.standard_normal((M_CAP, M_CAP)))[0]
                    [:, :L_KEEP])
    # one random row scaled per row: the restart's cost does not depend on
    # the values, and no random generator has to fit beside the buffer
    V = jnp.asarray(rng.standard_normal(n))[None, :] \
        * jnp.linspace(0.5, 1.5, rows)[:, None]
    t0 = time.perf_counter()
    V = jax.block_until_ready(restart(V, S))
    first = time.perf_counter() - t0
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        V = jax.block_until_ready(restart(V, S))
        times.append(time.perf_counter() - t0)
    stats = jax.devices()[0].memory_stats() or {}
    return {"n": n, "rows_in": M_CAP, "rows_kept": L_KEEP,
            "buffer_bytes": int(rows * n * 8),
            "first_call_s_with_compile": first, "seconds": sorted(times),
            "median_s": float(np.median(times)),
            "finite": bool(jnp.all(jnp.isfinite(V))),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use",
                                           "not reported")}


def run(n=FULL_N, out_dir=os.path.join(REPO, "chiprun_out")):
    """Every section at vector length ``n`` (a function argument: the
    CPU test calls this at a small size, the program has no options)."""
    import jax

    import distributed_matvec_tpu  # noqa: F401  (enables x64)

    dev = jax.devices()[0]
    rng = np.random.default_rng(0)
    report = {"device": {"platform": dev.platform, "kind": dev.device_kind}}
    print(json.dumps(report), flush=True)
    for name, fn in (
            ("dot_small", lambda: probe_dot(12_870, rng)),
            ("dot_large", lambda: probe_dot(n, rng)),
            ("elementwise", lambda: probe_elementwise(rng)),
            ("combine", lambda: probe_combine(rng)),
            ("in_solver", probe_in_solver),
            ("restart", lambda: probe_restart(n, rng))):
        report[name] = fn()
        print(json.dumps({name: report[name]}), flush=True)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "f64_probe.json"), "w") as f:
        json.dump(report, f, indent=1)
    return report


if __name__ == "__main__":
    run()
