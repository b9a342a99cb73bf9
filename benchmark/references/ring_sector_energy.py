"""Plain reference: the lowest energy of a ring's momentum sector.

``ring_heisenberg.py`` applies H to sampled rows and has no closed form for
a complex sector's lowest energy.  This file builds the sector's whole
sparse Hermitian matrix from the same definition, bond by bond, with that
file's own ``orbit_minimum`` and the amplitudes of its ``apply_rows``:

    H[r', r'] = sum over bonds of +1 (parallel) or -1 (antiparallel)
    H[r', rep(t)] += 2 chi(h_t) sqrt(|Stab rep(t)| / |Stab r'|)

for every target ``t`` of an antiparallel bond of ``r'`` (two bonds that
reach one representative add up to one element), and returns its lowest
eigenvalue from ARPACK (``scipy.sparse.linalg.eigsh``, smallest algebraic).
It shares nothing with ``distributed_matvec_tpu``: NumPy, SciPy, and
``ring_heisenberg.py`` loaded from beside this file.

At the benchmark's size the value is computed once and stored in the
configuration's file (``"ground_energy"``, beside ``"ground_energy_from"``):

    python3 benchmark/references/ring_sector_energy.py <model.yaml>

prints the sector's size, its stored elements and the lowest eigenvalue.
"""

import importlib.util
import os
import sys
import time

import numpy as np
import scipy.sparse as sp
from scipy.sparse.linalg import eigsh

_HERE = os.path.dirname(os.path.abspath(__file__))


def _load_ring():
    spec = importlib.util.spec_from_file_location(
        "ring_heisenberg_for_sector_energy",
        os.path.join(_HERE, "ring_heisenberg.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ring = _load_ring()
#: rows of the matrix built at a time: 32 bonds x rows x about 40 B
CHUNK = 1 << 17


def matrix_rows(spec, reps, start, stop):
    """Rows ``start:stop`` of the sector's matrix as CSR (duplicates added
    up), ``stop - start`` by ``reps.size``."""
    reps32 = reps.astype(np.uint32)
    s = reps32[start:stop]
    _, stab_s, _ = ring.orbit_minimum(s, spec)
    rows, targets, owners = np.arange(s.size), [], []
    diagonal = np.zeros(s.size)
    for i, j in spec.bonds:
        differ = (((s >> np.uint32(i)) ^ (s >> np.uint32(j)))
                  & np.uint32(1)).astype(bool)
        diagonal += np.where(differ, -1.0, 1.0)
        targets.append(s[differ] ^ np.uint32((1 << i) | (1 << j)))
        owners.append(rows[differ])
    t, owner = np.concatenate(targets), np.concatenate(owners)
    rep, stab_t, phase = ring.orbit_minimum(t, spec)
    col = np.minimum(np.searchsorted(reps32, rep), reps32.size - 1)
    if not np.array_equal(reps32[col] == rep, stab_t > 0):
        raise AssertionError("a coupled state left the basis")
    value = 2.0 * np.sqrt(stab_t / stab_s[owner])
    if phase is not None:
        value = value * phase
    live = stab_t > 0
    data = np.concatenate([diagonal, value[live]]).astype(np.complex128)
    r = np.concatenate([rows, owner[live]])
    c = np.concatenate([start + rows, col[live]])
    return sp.csr_matrix((data, (r, c)), shape=(s.size, reps.size))


def sector_matrix(spec, reps):
    """The sector's whole matrix (CSR, complex128; real values where the
    sector is real), built ``CHUNK`` rows at a time."""
    return sp.vstack([matrix_rows(spec, reps, a, min(a + CHUNK, reps.size))
                      for a in range(0, reps.size, CHUNK)], format="csr")


def lowest_eigenvalue(H, tol=1e-13):
    """ARPACK's smallest algebraic eigenvalue of Hermitian ``H``; a dense
    ``eigvalsh`` below a few hundred states, where ARPACK has nothing to
    iterate on."""
    if H.shape[0] <= 400:
        return float(np.linalg.eigvalsh(H.toarray())[0])
    rng = np.random.default_rng(0)
    v0 = rng.standard_normal(H.shape[0]).astype(H.dtype)
    if np.iscomplexobj(v0):
        v0 = v0 + 1j * rng.standard_normal(H.shape[0])
    vals = eigsh(H, k=1, which="SA", tol=tol, v0=v0,
                 ncv=min(32, H.shape[0] - 1), return_eigenvectors=False)
    return float(np.real(vals[0]))


def ground_energy(path, tol=1e-13):
    """The lowest eigenvalue of the sector the YAML at ``path`` states."""
    spec = ring.Spec(path)
    reps = ring.enumerate_representatives(spec)
    return lowest_eigenvalue(sector_matrix(spec, reps), tol)


def main(argv):
    t0 = time.perf_counter()
    spec = ring.Spec(argv[0])
    reps = ring.enumerate_representatives(spec)
    t1 = time.perf_counter()
    H = sector_matrix(spec, reps)
    t2 = time.perf_counter()
    print(f"states {reps.size}  stored elements {H.nnz}  "
          f"enumeration {t1 - t0:.1f} s  matrix {t2 - t1:.1f} s", flush=True)
    e0 = lowest_eigenvalue(H)
    print(f"eigsh {time.perf_counter() - t2:.1f} s", flush=True)
    print(f"ground_energy {e0!r}")


if __name__ == "__main__":
    main(sys.argv[1:])
