#!/usr/bin/env python
"""Independence anchors above 20 sites (VERDICT r4 'missing' #5 / next #8).

Pins the package's ground-state energies against a solver chain that shares
NOTHING with ``models/expression.py``: ``tests/independent_ref.py`` builds
H·x from the textbook σ-Heisenberg definition (pure NumPy bit ops, no
expression parsing, no term tables, no hashing), and scipy's ``eigsh``
(ARPACK) — a third-party eigensolver — drives it on the full fixed-hw
sector.  The package side solves the SAME physics through its own stack
(expression compiler → engine → thick-restart Lanczos), symmetry-adapted
where the config is (chain_24_symm: the k=0/R=+1/I=+1 sector contains the
ring's ground state).

Anchors:
* chain_24  — full sector C(24,12) = 2,704,156 vs chain_24_symm (28,968
  representatives).  Independent of the symmetry machinery END TO END.
* square_5x5 — full sector C(25,12) = 5,200,300, both sides unsymmetrized
  (25 sites, 50 periodic bonds): pins the expression compiler + engine at
  5.2M states.

    python tools/independent_e0.py --which chain_24 square_5x5
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests"))

# a host-only independence anchor: hold JAX to the CPU whatever the
# environment says
os.environ["JAX_PLATFORMS"] = "cpu"


def log(phase, **kv):
    print(json.dumps({"phase": phase, **kv}), flush=True)


def independent_e0(n, hw, edges, tol=1e-10):
    """Ground energy of Σ_bonds σ·σ on the full fixed-hw sector, computed
    outside the package (independent_ref matvec + scipy ARPACK)."""
    import numpy as np
    from scipy.sparse.linalg import LinearOperator, eigsh

    from independent_ref import enumerate_fixed_hw, heisenberg_apply

    states = enumerate_fixed_hw(n, hw)
    N = states.size

    def mv(x):
        return heisenberg_apply(states, edges, x.astype(np.float64))

    t0 = time.time()
    vals = eigsh(LinearOperator((N, N), matvec=mv), k=1, which="SA",
                 tol=tol, return_eigenvectors=False)
    return float(vals[0]), N, time.time() - t0


def package_e0(op, tol=1e-11):
    from distributed_matvec_tpu.parallel.engine import LocalEngine
    from distributed_matvec_tpu.solve import lanczos

    op.basis.build()
    t0 = time.time()
    eng = LocalEngine(op, mode="ell")
    r = lanczos(eng.matvec, op.basis.number_states, k=1, tol=tol,
                max_iters=600)
    return (float(r.eigenvalues[0]), op.basis.number_states,
            time.time() - t0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--which", nargs="+",
                    default=["chain_24", "square_5x5"],
                    choices=("chain_24", "square_5x5"))
    args = ap.parse_args()

    from distributed_matvec_tpu.models.basis import SpinBasis
    from distributed_matvec_tpu.models.lattices import (
        chain_edges, heisenberg_from_edges, square_edges)

    failures = 0
    for which in args.which:
        if which == "chain_24":
            n, hw, edges = 24, 12, chain_edges(24)
            syms = [([*range(1, 24), 0], 0), ([*reversed(range(24))], 0)]
            basis = SpinBasis(24, 12, 1, syms)
        else:
            n, hw, edges = 25, 12, square_edges(5, 5)
            basis = SpinBasis(25, 12)
        log("independent_start", which=which, loadavg=list(os.getloadavg()))
        e_ind, n_full, t_ind = independent_e0(n, hw, edges)
        log("independent", which=which, e0=e_ind, n_states=n_full,
            seconds=round(t_ind, 1))
        op = heisenberg_from_edges(basis, edges)
        e_pkg, n_pkg, t_pkg = package_e0(op)
        log("package", which=which, e0=e_pkg, n_states=n_pkg,
            seconds=round(t_pkg, 1))
        diff = abs(e_ind - e_pkg)
        agree = diff < 1e-8
        failures += not agree
        log("anchor", which=which, e0_independent=e_ind, e0_package=e_pkg,
            abs_diff=diff, agree_1e8=bool(agree),
            loadavg=list(os.getloadavg()))
    if failures:                      # the one condition this tool exists
        raise SystemExit(1)           # to catch must fail the exit code


if __name__ == "__main__":
    main()
