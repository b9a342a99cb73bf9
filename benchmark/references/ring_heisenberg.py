"""Plain reference: the Heisenberg ring in its fully symmetric sector, or
in a momentum sector with a complex character.

Shares nothing with ``distributed_matvec_tpu``: NumPy bit operations on the
textbook definition, read from the same YAML the program is given (the
problem *specification* is shared, everything derived from it is not).

Covers rings of ``n`` <= 32 sites with H = sum over bonds of
sigma^x sigma^x + sigma^y sigma^y + sigma^z sigma^z (Pauli form, 4x the
spin-1/2 S form), a fixed hamming weight, and the symmetry group generated
by the translation by one site, optionally the reflection and optionally
the global spin flip.  Either every character is +1, or the translation
carries ``sector: k`` with 0 < k < n, k != n/2 (a complex character; no
reflection then, which maps k to -k; the spin flip may stay, with character
+1 or -1).  Any other specification is refused (``NotImplementedError``): a
configuration of another lattice brings a reference of its own beside this
file.

With every character +1 the basis state of a representative ``r`` (the
smallest member of its orbit) is ``|r~> = sqrt(|Orb r|) P |r>`` with ``P``
the projector on the trivial character, so for the real symmetric H

    (H x)[r] = sum over t in H|r> of a_t sqrt(|Stab rep(t)| / |Stab r|)
               x[index of rep(t)]

which :func:`apply_rows` evaluates for sampled rows.

**A complex character**, from the same definition.  The YAML's conventions
are the specification's (upstream's ``lattice-symmetries``): a permutation
``p`` sends site ``i`` to site ``p[i]`` (so ``[1, 2, ..., 0]`` rotates a
state's bits left by one), and a generator of ``sector: k`` and period
``n`` has the character ``chi = exp(-2 pi i k / n)``.  The projector is
``P_k = (1/|G|) sum_g chi(g)^* g`` and the basis state of a representative
is ``|r~> = P_k |r> / ||P_k |r>||`` with

    ||P_k |r>||^2 = <r|P_k|r> = (1/|G|) sum over g in Stab r of chi(g)

which is ``|Stab r| / |G|`` where the character is trivial on the
stabiliser and 0 where it is not: such a representative is no basis state
(at k = 1 every orbit shorter than ``n`` goes) and
:func:`enumerate_representatives` leaves it out.  ``H`` commutes with
``P_k``, so ``<r'~|H|r~> = <r'|H P_k|r> / (||P_k r'|| ||P_k r||)``, and with
``<t|P_k|r> = (1/|G|) sum over g with g r = t of chi(g)^*
= chi(h) ||P_k r||^2`` for the ``h`` that takes ``t`` to its
representative (``h t = r``; ``g = h^-1 s`` with ``s`` in ``Stab r``, and
``chi(h^-1)^* = chi(h)``):

    (H x)[r'] = sum over t in H|r'> of a_t chi(h_t)
                sqrt(|Stab rep(t)| / |Stab r'|) x[index of rep(t)]

``chi(h_t)`` and not its conjugate, because the row ``r'`` is the *bra*:
the target ``t`` stands in the ket ``P_k|r>``, where ``t = h^-1 r`` comes
with ``chi(h^-1)^* = chi(h)``.  (Read by columns, ``H|r~>`` spread over the
``|r'~>``, the same element carries ``chi^*`` of the element that takes
the *column's* target home; the two are one Hermitian matrix.)  A target
whose representative has norm 0 contributes nothing.  :func:`orbit_minimum`
returns what the sum needs: the representative, the order of its stabiliser
(0 where the characters cancel on it) and ``chi(h_t)``.

The ground energy of the ring comes from the Bethe ansatz
(:func:`bethe_e0`), a third witness that shares nothing with either Lanczos
or the enumeration; it is the fully symmetric sector's where ``n`` is a
multiple of 4.  A complex sector has no closed form here: a configuration
with a ``ground_state`` cell brings its stored energy
(``"ground_energy"`` in its file, beside how it was computed).
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
        if inv not in (None, 0, 1, -1):
            raise NotImplementedError(f"spin_inversion {inv!r}")
        self.inversion = inv in (1, -1)
        self.inversion_character = -1 if inv == -1 else 1
        if self.inversion and 2 * self.hw != n:
            raise NotImplementedError("spin flip off half filling")
        self.translation = self.reflection = False
        self.k = 0
        for sym in basis.get("symmetries") or []:
            perm = [int(p) for p in sym["permutation"]]
            sector = int(sym["sector"])
            if perm == [*range(1, n), 0]:
                self.translation = True
                if not 0 <= sector < n or 2 * sector == n:
                    raise NotImplementedError(
                        f"translation sector {sector} of {n} sites")
                self.k = sector
            elif perm == [*reversed(range(n))]:
                self.reflection = True
                if sector != 0:
                    raise NotImplementedError("an odd reflection")
            else:
                raise NotImplementedError(f"permutation {perm}")
        if self.reflection and not self.translation:
            raise NotImplementedError("reflection without translation")
        #: whether the sector's character is complex (0 < k < n, k != n/2)
        self.complex = self.k != 0
        if self.complex and self.reflection:
            raise NotImplementedError(
                "a reflection beside a complex translation character: it "
                "maps k to -k")
        if self.inversion_character == -1 and not self.complex:
            raise NotImplementedError("spin_inversion -1 in a real sector")
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


Spec = RingSpec     # the name ``benchmark/check.py`` asks every reference for


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


def _characters(spec):
    """``chi(g)`` of every group element, in the order of :func:`_images`:
    ``exp(-2 pi i k j / n)`` for the rotation by ``j``, times the spin
    flip's character where the element flips."""
    cosets = [1.0] * (2 if spec.reflection else 1)
    if spec.inversion:
        cosets += [float(spec.inversion_character)] * len(cosets)
    turns = np.arange(spec.n if spec.translation else 1)
    omega = np.exp(-2j * np.pi * ((spec.k * turns) % spec.n) / spec.n)
    return np.concatenate([c * omega for c in cosets])


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
    that no group element maps to a smaller one and, under a complex
    character, whose stabiliser's characters do not cancel."""
    kept = [_keep_smallest(block, spec) for block in _candidates(spec)]
    if spec.complex:
        kept = [s[orbit_minimum(s, spec)[1] > 0] for s in kept]
    return np.sort(np.concatenate(kept)).astype(np.uint64)


def orbit_minimum(t, spec):
    """(smallest image, order of its stabiliser, character of a group
    element that reaches it) of each uint32 state in ``t``.

    With every character +1 the order is the number of group elements that
    reach the smallest image, and the third value is ``None``.  Under a
    complex character the characters of those elements add up to
    ``chi(h) sum over s in Stab of chi(s)``: of modulus ``|Stab|``, or 0
    where the character is not trivial on the stabiliser; the order is that
    modulus (so 0 marks an orbit that is no basis state) and the third
    value its phase ``chi(h)``, 0 beside an order of 0."""
    rep = t.copy()
    for image in _images(t, spec):
        np.minimum(rep, image, out=rep)
    if not spec.complex:
        stab = np.zeros(t.shape, np.int32)
        for image in _images(t, spec):
            stab += image == rep
        return rep, stab, None
    total = np.zeros(t.shape, np.complex128)
    for image, chi in zip(_images(t, spec), _characters(spec)):
        total += chi * (image == rep)
    stab = np.rint(np.abs(total)).astype(np.int32)
    return rep, stab, total / np.maximum(stab, 1)


def apply_rows(spec, reps, x, rows, dtype=np.float64):
    """(H x)[rows] from the definition, in ``dtype`` arithmetic (float64 is
    the reference; float32 is the control put in the program's place).  A
    complex sector's is complex: complex128 for float64 or complex128,
    complex64 for float32 or complex64."""
    if spec.complex:
        dtype = np.result_type(dtype, np.complex64).type
    reps32 = reps.astype(np.uint32)
    s = reps32[rows]
    _, stab_s, _ = orbit_minimum(s, spec)
    xv = np.asarray(x).astype(dtype)
    y = np.zeros(rows.size, dtype)
    two = dtype(2.0)
    for i, j in spec.bonds:
        differ = ((s >> np.uint32(i)) ^ (s >> np.uint32(j))) & np.uint32(1)
        differ = differ.astype(bool)
        y += np.where(differ, dtype(-1.0), dtype(1.0)) * xv[rows]
        t = s[differ] ^ np.uint32((1 << i) | (1 << j))
        rep, stab_t, phase = orbit_minimum(t, spec)
        idx = np.minimum(np.searchsorted(reps32, rep), reps32.size - 1)
        # a representative is in the basis exactly where its norm is not 0
        if not np.array_equal(reps32[idx] == rep, stab_t > 0):
            raise AssertionError("a coupled state left the basis")
        ratio = (stab_t / stab_s[differ]).astype(dtype)
        amplitude = two * np.sqrt(ratio)
        if phase is not None:
            amplitude = amplitude * phase.astype(dtype)
        y[differ] += amplitude * xv[idx]
    return y


def ground_energy(spec):
    """The ground energy ``benchmark/check.py`` compares a Ritz value with:
    the Bethe ansatz's where the sector holds the ring's ground state."""
    if spec.complex:
        raise NotImplementedError(
            "the ring reference has no closed form for a complex sector's "
            "lowest energy: a configuration with a ground_state cell brings "
            "its stored energy (\"ground_energy\" in its file, beside how "
            "it was computed)")
    if spec.translation and spec.n % 4:
        raise NotImplementedError(
            f"the ground state of a ring of {spec.n} sites has momentum pi: "
            "it is not in the k = 0 sector")
    return bethe_e0(spec.n)


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
    element).  With every character +1 every amplitude is positive and none
    cancels; under a complex character the amplitudes of one element carry
    phases, so they are added up and an element counts where the sum is not
    0 (a representative of norm 0 adds nothing)."""
    reps32 = reps.astype(np.uint32)
    s = reps32[rows]
    targets = np.empty((len(spec.bonds), rows.size), np.uint32)
    phases = np.zeros(targets.shape, np.complex128) if spec.complex else None
    for b, (i, j) in enumerate(spec.bonds):
        differ = (((s >> np.uint32(i)) ^ (s >> np.uint32(j)))
                  & np.uint32(1)).astype(bool)
        t = np.where(differ, s ^ np.uint32((1 << i) | (1 << j)), s)
        targets[b], _, phase = orbit_minimum(t, spec)
        if phases is not None:
            phases[b] = np.where(differ, phase, 0.0)
    if phases is None:
        targets.sort(axis=0)
        fresh = np.ones(targets.shape, bool)
        fresh[1:] = targets[1:] != targets[:-1]
        return int(np.count_nonzero(fresh & (targets != s[None, :])))
    order = np.argsort(targets, axis=0, kind="stable")
    targets = np.take_along_axis(targets, order, axis=0)
    # an element's sum: the running sum at its last bond less the running
    # sum before its first
    run = np.cumsum(np.take_along_axis(phases, order, axis=0), axis=0)
    last = np.ones(targets.shape, bool)
    last[:-1] = targets[1:] != targets[:-1]
    before = np.zeros(targets.shape, np.complex128)
    for b in range(1, len(spec.bonds)):
        before[b] = np.where(targets[b] != targets[b - 1], run[b - 1],
                             before[b - 1])
    element = np.abs(run - before) > 1e-9
    return int(np.count_nonzero(last & element & (targets != s[None, :])))
