"""Plain reference: the Heisenberg ring in its fully symmetric sector.

Shares nothing with ``distributed_matvec_tpu``: NumPy bit operations on the
textbook definition, read from the same YAML the program is given (the
problem *specification* is shared, everything derived from it is not).

Covers rings of ``n`` <= 32 sites with H = sum over bonds of
sigma^x sigma^x + sigma^y sigma^y + sigma^z sigma^z (Pauli form, 4x the
spin-1/2 S form), a fixed hamming weight, and the symmetry group generated
by the translation by one site, optionally the reflection and optionally
the global spin flip, every character +1.  Any other specification is
refused (``NotImplementedError``): a configuration of another lattice
brings a reference of its own beside this file.

In that sector the basis state of a representative ``r`` (the smallest
member of its orbit) is ``|r~> = sqrt(|Orb r|) P |r>`` with ``P`` the
projector on the trivial character, so for the real symmetric H

    (H x)[r] = sum over t in H|r> of a_t sqrt(|Stab rep(t)| / |Stab r|)
               x[index of rep(t)]

which :func:`apply_rows` evaluates for sampled rows.  The ground energy of
the ring comes from the Bethe ansatz (:func:`bethe_e0`), a third witness
that shares nothing with either Lanczos or the enumeration.
"""

import numpy as np
import yaml

_XX, _YY, _ZZ = "σˣ₀ σˣ₁", "σʸ₀ σʸ₁", "σᶻ₀ σᶻ₁"


class RingSpec:
    """What the YAML says, after checking that this reference covers it."""

    def __init__(self, path):
        with open(path, encoding="utf-8") as f:
            doc = yaml.safe_load(f)
        basis, terms = doc["basis"], doc["hamiltonian"]["terms"]
        n = self.n = int(basis["number_spins"])
        self.hw = int(basis["hamming_weight"])
        if not 4 <= n <= 32 or n % 2:
            raise NotImplementedError(f"ring reference: {n} sites")
        inv = basis.get("spin_inversion")
        if inv not in (None, 0, 1):
            raise NotImplementedError(f"spin_inversion {inv!r}")
        self.inversion = inv == 1
        if self.inversion and 2 * self.hw != n:
            raise NotImplementedError("spin flip off half filling")
        self.translation = self.reflection = False
        for sym in basis.get("symmetries") or []:
            perm = [int(p) for p in sym["permutation"]]
            if int(sym["sector"]) != 0:
                raise NotImplementedError("a non-trivial character")
            if perm == [*range(1, n), 0]:
                self.translation = True
            elif perm == [*reversed(range(n))]:
                self.reflection = True
            else:
                raise NotImplementedError(f"permutation {perm}")
        if self.reflection and not self.translation:
            raise NotImplementedError("reflection without translation")
        ring = sorted((i, (i + 1) % n) for i in range(n))
        if sorted(t["expression"] for t in terms) != sorted((_XX, _YY, _ZZ)):
            raise NotImplementedError("not the Heisenberg coupling")
        for t in terms:
            if sorted(tuple(int(s) for s in b) for b in t["sites"]) != ring:
                raise NotImplementedError("bonds are not the ring's")
        self.bonds = [(i, (i + 1) % n) for i in range(n)]
        self.group_order = ((n if self.translation else 1)
                            * (2 if self.reflection else 1)
                            * (2 if self.inversion else 1))


def _reverse_bits(s, n):
    """Reverse the low ``n`` bits of uint32 ``s`` (site i -> n - 1 - i)."""
    s = s.astype(np.uint32)
    for shift, mask in ((1, 0x55555555), (2, 0x33333333), (4, 0x0F0F0F0F),
                        (8, 0x00FF00FF), (16, 0x0000FFFF)):
        m = np.uint32(mask)
        s = ((s >> np.uint32(shift)) & m) | ((s & m) << np.uint32(shift))
    return s >> np.uint32(32 - n)


def _variant_maps(spec):
    """One map per coset of the translation subgroup: identity, reflection,
    spin flip, and both."""
    full = np.uint32((1 << spec.n) - 1)
    maps = [lambda s: s]
    if spec.reflection:
        maps.append(lambda s: _reverse_bits(s, spec.n))
    if spec.inversion:
        maps += [lambda s, f=f: f(s) ^ full for f in list(maps)]
    return maps


def _images(s, spec):
    """Every group element's image of uint32 ``s``, the identity first."""
    n, full = spec.n, np.uint32((1 << spec.n) - 1)
    for f in _variant_maps(spec):
        v = f(s)
        yield v
        if spec.translation:
            for k in range(1, n):
                yield ((v << np.uint32(k)) | (v >> np.uint32(n - k))) & full


def _keep_smallest(s, spec):
    """The members of ascending ``s`` that no group element maps lower."""
    n, full = spec.n, np.uint32((1 << spec.n) - 1)
    for f in _variant_maps(spec):
        v = f(s)
        for k in range(n if spec.translation else 1):
            image = v if k == 0 else \
                ((v << np.uint32(k)) | (v >> np.uint32(n - k))) & full
            keep = image >= s
            if not keep.all():
                s, v = s[keep], v[keep]
    return s


def _candidates(spec):
    """Blocks of ascending uint32 states of the sector's weight that can be
    the smallest of their orbit.  With the translations and half the sites
    up, the smallest rotation starts with the longest run of zeros: its two
    top bits are 0 and its lowest bit is 1.  The one exception, the
    alternating state, is a block of its own."""
    n, hw = spec.n, spec.hw
    if spec.translation and 2 * hw == n and n >= 6:
        yield np.array([int("01" * (n // 2), 2)], np.uint32)
        free, fixed, ones, shift = n - 3, 1, hw - 1, 1   # bits 1 .. n-3
    else:
        free, fixed, ones, shift = n, 0, hw, 0
    lo_bits = min(free, 15)
    hi_bits = free - lo_bits
    pop = np.array([bin(i).count("1") for i in range(1 << 17)], np.uint8)
    lows = np.arange(1 << lo_bits, dtype=np.uint32)
    highs = np.arange(1 << hi_bits, dtype=np.uint32)
    for p in range(hi_bits + 1):
        q = ones - p
        if not 0 <= q <= lo_bits:
            continue
        high = highs[pop[:highs.size] == p] << np.uint32(lo_bits + shift)
        low = (lows[pop[:lows.size] == q] << np.uint32(shift)) \
            | np.uint32(fixed)
        rows = max(1, (1 << 22) // max(low.size, 1))
        for a in range(0, high.size, rows):
            yield (high[a:a + rows, None] | low[None, :]).ravel()


def enumerate_representatives(spec):
    """Sorted uint64 representatives: the states of the sector's weight
    that no group element maps to a smaller one."""
    kept = [_keep_smallest(block, spec) for block in _candidates(spec)]
    return np.sort(np.concatenate(kept)).astype(np.uint64)


def orbit_minimum(t, spec):
    """(smallest image, number of group elements that reach it) of each
    uint32 state in ``t``; the count is the order of its stabiliser."""
    rep = t.copy()
    for image in _images(t, spec):
        np.minimum(rep, image, out=rep)
    stab = np.zeros(t.shape, np.int32)
    for image in _images(t, spec):
        stab += image == rep
    return rep, stab


def apply_rows(spec, reps, x, rows, dtype=np.float64):
    """(H x)[rows] from the definition, in ``dtype`` arithmetic (float64 is
    the reference; float32 is the control put in the program's place)."""
    reps32 = reps.astype(np.uint32)
    s = reps32[rows]
    _, stab_s = orbit_minimum(s, spec)
    xv = np.asarray(x).astype(dtype)
    y = np.zeros(rows.size, dtype)
    two = dtype(2.0)
    for i, j in spec.bonds:
        differ = ((s >> np.uint32(i)) ^ (s >> np.uint32(j))) & np.uint32(1)
        differ = differ.astype(bool)
        y += np.where(differ, dtype(-1.0), dtype(1.0)) * xv[rows]
        t = s[differ] ^ np.uint32((1 << i) | (1 << j))
        rep, stab_t = orbit_minimum(t, spec)
        idx = np.searchsorted(reps32, rep)
        if not np.array_equal(reps32[np.minimum(idx, reps32.size - 1)], rep):
            raise AssertionError("a coupled state left the basis")
        ratio = (stab_t / stab_s[differ]).astype(dtype)
        y[differ] += two * np.sqrt(ratio) * xv[idx]
    return y


def bethe_e0(n):
    """Ground energy of the ``n``-site ring (n even) in Pauli form, from
    the Bethe ansatz: ``n`` arctan(2 l_j) = pi I_j + sum_k arctan(l_j - l_k)
    with I_j = -(M - 1)/2 .. (M - 1)/2, M = n / 2, solved by Newton's
    method, and E = n - 8 sum_j 1 / (4 l_j^2 + 1)."""
    m = n // 2
    quantum = np.arange(m) - (m - 1) / 2.0
    lam = 0.5 * np.tan(np.pi * quantum / n)
    for _ in range(200):
        d = lam[:, None] - lam[None, :]
        f = n * np.arctan(2 * lam) - np.pi * quantum - np.arctan(d).sum(1)
        jac = 1.0 / (1.0 + d * d)
        jac[np.diag_indices(m)] = 2.0 * n / (1.0 + 4.0 * lam ** 2) \
            - (jac.sum(1) - 1.0)
        step = np.linalg.solve(jac, f)
        lam = lam - step
        if np.max(np.abs(step)) < 1e-15:
            break
    return float(n - 8.0 * np.sum(1.0 / (4.0 * lam ** 2 + 1.0)))


def count_offdiagonal(spec, reps, rows):
    """Non-zero off-diagonal elements of the symmetry-reduced matrix in
    ``rows``: distinct representatives other than the row's own that its
    bonds couple it to (bonds that reach the same one add up to one
    element, exact cancellations apart: every amplitude here is positive)."""
    reps32 = reps.astype(np.uint32)
    s = reps32[rows]
    targets = np.empty((len(spec.bonds), rows.size), np.uint32)
    for b, (i, j) in enumerate(spec.bonds):
        differ = (((s >> np.uint32(i)) ^ (s >> np.uint32(j)))
                  & np.uint32(1)).astype(bool)
        t = np.where(differ, s ^ np.uint32((1 << i) | (1 << j)), s)
        targets[b], _ = orbit_minimum(t, spec)
    targets.sort(axis=0)
    fresh = np.ones(targets.shape, bool)
    fresh[1:] = targets[1:] != targets[:-1]
    return int(np.count_nonzero(fresh & (targets != s[None, :])))
