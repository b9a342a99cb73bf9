"""Plain reference: the Fermi-Hubbard model of spinful fermions, no symmetries.

Shares nothing with ``distributed_matvec_tpu``: NumPy bit operations on the
textbook definition, read from the same YAML the program is given (the
problem *specification* is shared, everything derived from it is not).

Covers ``particle: spinful_fermion`` on ``L`` <= 32 sites with fixed numbers
of up and down electrons and no symmetry group, and a Hamiltonian of exactly
two terms: a hopping ``c (c†₀ c₁ + c†₁ c₀)`` on a list of mode pairs, each
pair inside one spin species, and an on-site repulsion ``U n₀ n₁`` on pairs
``[i, L + i]``.  Mode ``k`` is bit ``k`` of a state: up modes are bits
0 .. L-1, down modes bits L .. 2L-1.  Any other specification is refused
(``NotImplementedError``), as the other references refuse what they do not
cover.

The basis is the product of the two species' fixed-weight sectors, listed
as sorted uint64 words: ``(down << L) | up``.  A hop of one electron between
modes ``i`` and ``j`` couples a state in which exactly one of them is
occupied to the state with both flipped; in the Jordan-Wigner ordering of
the modes by bit its amplitude is ``c`` times ``(-1)`` to the number of
occupied modes strictly between ``i`` and ``j`` (the modes between are the
same before and after the hop, so the matrix is symmetric).  The repulsion
is diagonal: ``U`` for every listed pair with both modes occupied.

There is no closed form for the ground energy: :func:`solve_ground_energy`
diagonalises this file's own sparse matrix (dense below a few hundred
states, ARPACK above).  At the benchmark's size that is a job for the chip,
so :func:`ground_energy` looks the value up in :data:`STORED_E0` by the
specification's digest where the basis is too large to solve here, and
raises where there is none.

    python3 benchmark/references/hubbard.py <model.yaml> [--count]

prints the specification's digest and sizes, the ground energy where the
basis is small enough to solve here, and with ``--count`` the off-diagonal
non-zeros over every row (in row chunks, on every core).
"""

import hashlib
import re
import sys
from math import comb

import numpy as np
import yaml

_HOP = re.compile(r"^\s*([-+]?[0-9.]+(?:[eE][-+]?[0-9]+)?)\s*[×*]?\s*"
                  r"\(\s*c†₀\s*c₁\s*\+\s*c†₁\s*c₀\s*\)\s*$")
_ONSITE = re.compile(r"^\s*([-+]?[0-9.]+(?:[eE][-+]?[0-9]+)?)\s*[×*]?\s*"
                     r"n₀\s*n₁\s*$")

#: the largest basis :func:`ground_energy` solves on the spot
SOLVE_LIMIT = 2_000_000

#: Ground energies too costly to solve here, by ``HubbardSpec.digest``.
STORED_E0 = {}


class HubbardSpec:
    """What the YAML says, after checking that this reference covers it."""

    def __init__(self, path):
        with open(path, encoding="utf-8") as f:
            doc = yaml.safe_load(f)
        basis, terms = doc["basis"], doc["hamiltonian"]["terms"]
        particle = str(basis.get("particle", "spin")).replace("-", "_")
        if particle != "spinful_fermion":
            raise NotImplementedError(f"particle {particle!r}")
        n = self.n = int(basis["number_sites"])
        if not 1 <= n <= 32:
            raise NotImplementedError(f"hubbard reference: {n} sites")
        if basis.get("number_up") is None or basis.get("number_down") is None:
            raise NotImplementedError("no fixed number of electrons a spin")
        self.n_up = int(basis["number_up"])
        self.n_down = int(basis["number_down"])
        if not (0 <= self.n_up <= n and 0 <= self.n_down <= n):
            raise NotImplementedError(f"{self.n_up} up, {self.n_down} down "
                                      f"on {n} sites")
        if basis.get("symmetries"):
            raise NotImplementedError("a symmetry group")
        hop = onsite = None
        for t in terms:
            rows = [tuple(int(s) for s in r) for r in t["sites"]]
            m_hop, m_on = _HOP.match(t["expression"]), \
                _ONSITE.match(t["expression"])
            if m_hop and hop is None:
                hop = (float(m_hop.group(1)), rows)
            elif m_on and onsite is None:
                onsite = (float(m_on.group(1)), rows)
            else:
                raise NotImplementedError(
                    f"term {t['expression']!r}: not one hopping and one "
                    "on-site repulsion")
        if hop is None or onsite is None:
            raise NotImplementedError("not the Hubbard model's two terms")
        self.c, self.bonds = hop
        self.U, pairs = onsite
        for i, j in self.bonds:
            if i == j or not (0 <= i < 2 * n and 0 <= j < 2 * n) \
                    or (i < n) != (j < n):
                raise NotImplementedError(f"hop {(i, j)}: not two modes of "
                                          "one species")
        for i, j in pairs:
            if (i, j) != (i, i + n) or not 0 <= i < n:
                raise NotImplementedError(f"repulsion {(i, j)}: not on site")
        self.sites = [i for i, _ in pairs]

    @property
    def number_states(self):
        return comb(self.n, self.n_up) * comb(self.n, self.n_down)

    @property
    def digest(self):
        """Names the Hamiltonian: sites, electrons, the couplings and the
        mode pairs as sorted lists of sorted pairs."""
        what = (self.n, self.n_up, self.n_down, self.c, self.U,
                sorted(tuple(sorted(b)) for b in self.bonds),
                sorted(self.sites))
        return hashlib.sha256(repr(what).encode()).hexdigest()[:16]


Spec = HubbardSpec  # the name ``benchmark/check.py`` asks every reference for


def _sector(bits, weight):
    """Sorted ``bits``-bit words of popcount ``weight``."""
    words = np.arange(1 << bits, dtype=np.uint64)
    return words[np.bitwise_count(words) == weight]


def enumerate_representatives(spec):
    """Sorted uint64 states of the product basis: ``(down << L) | up``, the
    down part the slower index."""
    up, down = _sector(spec.n, spec.n_up), _sector(spec.n, spec.n_down)
    states = ((down[:, None] << np.uint64(spec.n)) | up[None, :]).ravel()
    assert states.size == spec.number_states
    assert states.size < 2 or bool(np.all(states[1:] > states[:-1]))
    return states


def _bit(s, k):
    return ((s >> np.uint64(k)) & np.uint64(1)).astype(bool)


def _between(i, j):
    """The mask of the modes strictly between ``i`` and ``j``."""
    lo, hi = min(i, j), max(i, j)
    return np.uint64(((1 << hi) - 1) ^ ((1 << (lo + 1)) - 1))


def _lookup(reps, t):
    idx = np.searchsorted(reps, t)
    if not np.array_equal(reps[np.minimum(idx, reps.size - 1)], t):
        raise AssertionError("a coupled state left the basis")
    return idx


def hop_sign(s, i, j):
    """+1 or -1 a state: the Jordan-Wigner string of a hop between modes
    ``i`` and ``j``, the parity of the occupied modes strictly between."""
    return 1 - 2 * (np.bitwise_count(s & _between(i, j)) & 1).astype(np.int64)


def apply_rows(spec, reps, x, rows, dtype=np.float64, signs=True):
    """(H x)[rows] from the definition, in ``dtype`` arithmetic (float64 is
    the reference; float32 is the control put in the program's place).
    ``signs=False`` drops the Jordan-Wigner strings: every hop then has
    amplitude ``c``, which is not this Hamiltonian (a control for a check
    that has to see the signs)."""
    reps = np.asarray(reps, np.uint64)
    s = reps[rows]
    xv = np.asarray(x).astype(dtype)
    y = np.zeros(rows.size, dtype)
    for i in spec.sites:
        both = _bit(s, i) & _bit(s, i + spec.n)
        y[both] += dtype(spec.U) * xv[rows[both]]
    for i, j in spec.bonds:
        one = _bit(s, i) ^ _bit(s, j)
        t = s[one]
        amp = np.full(t.size, dtype(spec.c), dtype)
        if signs:
            amp = amp * hop_sign(t, i, j).astype(dtype)
        y[one] += amp * xv[_lookup(reps, t ^ np.uint64((1 << i) | (1 << j)))]
    return y


def count_offdiagonal(spec, reps, rows):
    """Non-zero off-diagonal elements of the matrix in ``rows``: the
    distinct states each row's hops couple it to.  Every hop's amplitude is
    ``c`` up to its sign, and a state reached twice is reached by one mode
    pair listed twice (a hop flips exactly its two modes), whose amplitudes
    are equal and add: no element cancels."""
    reps = np.asarray(reps, np.uint64)
    s = reps[rows]
    targets = np.empty((len(spec.bonds), rows.size), np.uint64)
    for b, (i, j) in enumerate(spec.bonds):
        one = _bit(s, i) ^ _bit(s, j)
        targets[b] = np.where(one, s ^ np.uint64((1 << i) | (1 << j)), s)
    targets.sort(axis=0)
    fresh = np.ones(targets.shape, bool)
    fresh[1:] = targets[1:] != targets[:-1]
    return int(np.count_nonzero(fresh & (targets != s[None, :])))


def closed_form_offdiagonal(spec):
    """The count :func:`count_offdiagonal` gives over every row where no
    mode pair is listed twice: a hop of a species' pair in one direction
    needs one mode full and one empty, ``C(L - 2, N - 1)`` ways, beside any
    state of the other species."""
    n, nu, nd = spec.n, spec.n_up, spec.n_down
    up = sum(1 for i, _ in spec.bonds if i < n)
    down = len(spec.bonds) - up

    def hops(bonds, k):
        return 2 * bonds * comb(n - 2, k - 1) if k >= 1 else 0
    return hops(up, nu) * comb(n, nd) + hops(down, nd) * comb(n, nu)


def sparse_matrix(spec, reps, signs=True):
    """H on the basis as a SciPy CSR matrix."""
    from scipy.sparse import coo_matrix

    reps = np.asarray(reps, np.uint64)
    n = reps.size
    rows, cols, vals = [np.arange(n)], [np.arange(n)], [np.zeros(n)]
    for i in spec.sites:
        vals[0] += spec.U * (_bit(reps, i) & _bit(reps, i + spec.n))
    for i, j in spec.bonds:
        one = np.flatnonzero(_bit(reps, i) ^ _bit(reps, j))
        t = reps[one]
        rows.append(one)
        cols.append(_lookup(reps, t ^ np.uint64((1 << i) | (1 << j))))
        vals.append(spec.c * (hop_sign(t, i, j) if signs else 1.0))
    return coo_matrix((np.concatenate(vals), (np.concatenate(rows),
                                             np.concatenate(cols))),
                      shape=(n, n)).tocsr()


def solve_ground_energy(spec, tol=1e-13):
    """Lowest eigenvalue of H on the basis: dense ``eigvalsh`` below a few
    hundred states, ARPACK (``eigsh``, smallest algebraic) on this file's
    own sparse matrix above."""
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
    """The ground energy ``check.py`` compares a Ritz value with: the stored
    value where there is one; else, up to :data:`SOLVE_LIMIT` states,
    :func:`solve_ground_energy`, once; else it raises."""
    stored = STORED_E0.get(spec.digest)
    if stored is None:
        if spec.number_states > SOLVE_LIMIT:
            raise NotImplementedError(
                f"no stored ground energy for {spec.digest} "
                f"({spec.number_states} states)")
        stored = STORED_E0[spec.digest] = solve_ground_energy(spec)
    return stored


_WORKER = {}


def _start_worker(path):
    spec = _WORKER["spec"] = HubbardSpec(path)
    _WORKER["reps"] = enumerate_representatives(spec)


def _count_chunk(span):
    return count_offdiagonal(_WORKER["spec"], _WORKER["reps"],
                             np.arange(*span))


def count_every_row(path, chunk=1 << 18, processes=None):
    """:func:`count_offdiagonal` over every row, in row chunks on a pool
    whose every process lists the basis once."""
    from multiprocessing import Pool

    n = HubbardSpec(path).number_states
    spans = [(lo, min(lo + chunk, n)) for lo in range(0, n, chunk)]
    with Pool(processes, initializer=_start_worker,
              initargs=(path,)) as pool:
        return sum(pool.imap_unordered(_count_chunk, spans))


if __name__ == "__main__":
    _spec = HubbardSpec(sys.argv[1])
    print(f"digest {_spec.digest}  sites {_spec.n}  up {_spec.n_up}  "
          f"down {_spec.n_down}  hops {len(_spec.bonds)}  "
          f"states {_spec.number_states}  "
          f"closed_form_offdiagonal {closed_form_offdiagonal(_spec)}",
          flush=True)
    if "--count" in sys.argv[2:]:
        print(f"offdiag_nonzeros {count_every_row(sys.argv[1])}", flush=True)
    if _spec.number_states <= SOLVE_LIMIT:
        print(f"ground_energy {solve_ground_energy(_spec)!r}")
