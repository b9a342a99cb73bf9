"""Plain reference: the Heisenberg model on any bond list, without symmetries.

Shares nothing with ``distributed_matvec_tpu``: NumPy bit operations on the
textbook definition, read from the same YAML the program is given (the
problem *specification* is shared, everything derived from it is not).

Covers ``n`` <= 32 sites with H = sum over bonds of sigma^x sigma^x +
sigma^y sigma^y + sigma^z sigma^z (Pauli form, 4x the spin-1/2 S form) on
any list of bonds, a fixed hamming weight, no symmetry group and no spin
inversion: every state of the sector is its own representative.  Any other
specification is refused (``NotImplementedError``), as
``ring_heisenberg.py`` refuses what it does not cover.

A bond (i, j) gives a state +1 on the diagonal where its two spins are
parallel and -1 where they are not, and couples an antiparallel pair to the
state with both flipped with amplitude 2.

There is no Bethe ansatz off the ring, so the ground energy is this file's
own: ARPACK on its own sparse H (:func:`solve_ground_energy`).  At the
benchmark's size that is minutes of host time, so the value is computed
once, kept in :data:`STORED_E0` beside the command that reproduces it, and
looked up by the specification's digest (:func:`ground_energy`); a
specification without a stored value is solved on the fly.

``benchmark/check.py`` asks every reference for ``Spec(path)`` and
``ground_energy(spec)``: ``Spec`` is :class:`LatticeSpec`.

    python3 benchmark/references/lattice_heisenberg.py <model.yaml>

prints the ground energy of a YAML and its digest.
"""

import hashlib
import sys
from math import comb

import numpy as np
import yaml

_XX, _YY, _ZZ = "σˣ₀ σˣ₁", "σʸ₀ σʸ₁", "σᶻ₀ σᶻ₁"

#: Ground energies computed once by :func:`solve_ground_energy`, by
#: ``LatticeSpec.digest``.
STORED_E0 = {
    # 5x5 torus, hamming weight 13, 5,200,300 states: the line printed by
    #   python3 benchmark/references/lattice_heisenberg.py \
    #       benchmark/configs/square_5x5.yaml
    # (PR 28, CPU, 115 s: CSR build 47 s, then ARPACK)
    "76702fbdccc5271f": -60.143081768240215,
}


class LatticeSpec:
    """What the YAML says, after checking that this reference covers it."""

    def __init__(self, path):
        with open(path, encoding="utf-8") as f:
            doc = yaml.safe_load(f)
        basis, terms = doc["basis"], doc["hamiltonian"]["terms"]
        n = self.n = int(basis["number_spins"])
        if not 2 <= n <= 32:
            raise NotImplementedError(f"lattice reference: {n} sites")
        if basis.get("hamming_weight") is None:
            raise NotImplementedError("no fixed hamming weight")
        self.hw = int(basis["hamming_weight"])
        if not 0 <= self.hw <= n:
            raise NotImplementedError(f"hamming weight {self.hw}")
        if basis.get("spin_inversion") not in (None, 0):
            raise NotImplementedError("a spin-inversion sector")
        if basis.get("symmetries"):
            raise NotImplementedError("a symmetry group")
        if sorted(t["expression"] for t in terms) != sorted((_XX, _YY, _ZZ)):
            raise NotImplementedError("not the Heisenberg coupling")
        lists = [[tuple(int(s) for s in b) for b in t["sites"]]
                 for t in terms]
        if any(sorted(map(sorted, b)) != sorted(map(sorted, lists[0]))
               for b in lists[1:]):
            raise NotImplementedError("the three terms' bonds differ")
        for b in lists[0]:
            if len(b) != 2 or b[0] == b[1] or not all(0 <= s < n for s in b):
                raise NotImplementedError(f"bond {b}")
        self.bonds = lists[0]
        self.group_order = 1

    @property
    def digest(self):
        """Names the Hamiltonian: sites, weight and the bonds as a sorted
        list of sorted pairs (a bond listed twice counts twice)."""
        what = (self.n, self.hw, sorted(tuple(sorted(b)) for b in self.bonds))
        return hashlib.sha256(repr(what).encode()).hexdigest()[:16]


Spec = LatticeSpec  # the name ``benchmark/check.py`` asks every reference for


def enumerate_representatives(spec):
    """Sorted uint64 states of the sector: every ``n``-bit word of the
    sector's weight, listed as (high part) x (low part) by the parts'
    popcounts."""
    n, hw = spec.n, spec.hw
    lo_bits = min(n, 13)
    hi_bits = n - lo_bits
    pop = np.array([bin(i).count("1")
                    for i in range(1 << max(lo_bits, hi_bits))], np.uint8)
    lows = np.arange(1 << lo_bits, dtype=np.uint64)
    highs = np.arange(1 << hi_bits, dtype=np.uint64)
    blocks = []
    for p in range(hi_bits + 1):
        if not 0 <= hw - p <= lo_bits:
            continue
        high = highs[pop[:highs.size] == p] << np.uint64(lo_bits)
        low = lows[pop[:lows.size] == hw - p]
        blocks.append((high[:, None] | low[None, :]).ravel())
    states = np.sort(np.concatenate(blocks))
    assert states.size == comb(n, hw)
    return states


def _antiparallel(s, i, j):
    return (((s >> np.uint64(i)) ^ (s >> np.uint64(j)))
            & np.uint64(1)).astype(bool)


def _flip(i, j):
    return np.uint64((1 << i) | (1 << j))


def _lookup(reps, t):
    idx = np.searchsorted(reps, t)
    if not np.array_equal(reps[np.minimum(idx, reps.size - 1)], t):
        raise AssertionError("a coupled state left the basis")
    return idx


def apply_rows(spec, reps, x, rows, dtype=np.float64):
    """(H x)[rows] from the definition, in ``dtype`` arithmetic (float64 is
    the reference; float32 is the control put in the program's place)."""
    reps = np.asarray(reps, np.uint64)
    s = reps[rows]
    xv = np.asarray(x).astype(dtype)
    y = np.zeros(rows.size, dtype)
    two = dtype(2.0)
    for i, j in spec.bonds:
        differ = _antiparallel(s, i, j)
        y += np.where(differ, dtype(-1.0), dtype(1.0)) * xv[rows]
        y[differ] += two * xv[_lookup(reps, s[differ] ^ _flip(i, j))]
    return y


def count_offdiagonal(spec, reps, rows):
    """Non-zero off-diagonal elements of the matrix in ``rows``: the
    distinct states each row's antiparallel bonds couple it to (a bond
    listed twice reaches the same state twice and makes one element)."""
    reps = np.asarray(reps, np.uint64)
    s = reps[rows]
    targets = np.empty((len(spec.bonds), rows.size), np.uint64)
    for b, (i, j) in enumerate(spec.bonds):
        targets[b] = np.where(_antiparallel(s, i, j), s ^ _flip(i, j), s)
    targets.sort(axis=0)
    fresh = np.ones(targets.shape, bool)
    fresh[1:] = targets[1:] != targets[:-1]
    return int(np.count_nonzero(fresh & (targets != s[None, :])))


def sparse_matrix(spec, reps):
    """H on the sector as a SciPy CSR matrix: a row's entries are its
    diagonal and one 2 an antiparallel bond (a bond listed twice gives two
    entries of one column, which CSR arithmetic adds up)."""
    from scipy.sparse import csr_matrix

    reps = np.asarray(reps, np.uint64)
    n, nb = reps.size, len(spec.bonds)
    cols = np.empty((n, nb + 1), np.int32)
    vals = np.full((n, nb + 1), 2.0)
    cols[:, nb] = np.arange(n)
    vals[:, nb] = 0.0
    for b, (i, j) in enumerate(spec.bonds):
        differ = _antiparallel(reps, i, j)
        vals[:, nb] += np.where(differ, -1.0, 1.0)
        cols[:, b] = -1
        cols[differ, b] = _lookup(reps, reps[differ] ^ _flip(i, j))
    live = cols >= 0
    ptr = np.concatenate([[0], np.cumsum(live.sum(axis=1))])
    return csr_matrix((vals[live], cols[live], ptr), shape=(n, n))


def solve_ground_energy(spec, tol=1e-13):
    """Lowest eigenvalue of H on the sector: ARPACK (``eigsh``, smallest
    algebraic) on this file's own sparse matrix; a dense ``eigvalsh`` below
    a few hundred states, where ARPACK has nothing to iterate on."""
    reps = enumerate_representatives(spec)
    H = sparse_matrix(spec, reps)
    if reps.size <= 400:
        return float(np.linalg.eigvalsh(H.toarray())[0])
    from scipy.sparse.linalg import eigsh

    v0 = np.random.default_rng(0).standard_normal(reps.size)
    vals = eigsh(H, k=1, which="SA", tol=tol, v0=v0, ncv=48,
                 return_eigenvectors=False)
    return float(vals[0])


def ground_energy(spec):
    """The ground energy ``check.py`` compares the Ritz value with: the
    stored value where there is one, else :func:`solve_ground_energy`,
    once."""
    stored = STORED_E0.get(spec.digest)
    if stored is None:
        stored = STORED_E0[spec.digest] = solve_ground_energy(spec)
    return stored


if __name__ == "__main__":
    _spec = LatticeSpec(sys.argv[1])
    print(f"digest {_spec.digest}  n {_spec.n}  hamming_weight {_spec.hw}  "
          f"bonds {len(_spec.bonds)}  states {comb(_spec.n, _spec.hw)}")
    print(f"ground_energy {solve_ground_energy(_spec)!r}")
