"""The plain reference of lattices without symmetries
(``benchmark/references/lattice_heisenberg.py``) against a construction that
shares nothing with it: H as a sum of Kronecker products of Pauli matrices
on the full 2^n space, cut to the sector.  Its refusals, its count of
off-diagonal elements against the closed form, and the names
``benchmark/check.py`` calls it by."""

import os
from math import comb

import numpy as np
import pytest
import scipy.sparse as sp

from benchmark import check
from conftest import ROOT

PAULI = {"x": sp.csr_matrix([[0, 1], [1, 0]], dtype=complex),
         "y": sp.csr_matrix([[0, -1j], [1j, 0]]),
         "z": sp.csr_matrix([[1, 0], [0, -1]], dtype=complex)}


def torus_bonds(nx, ny):
    """Site y*nx + x; a bond to the right and one up from every site."""
    at = lambda x, y: (y % ny) * nx + (x % nx)            # noqa: E731
    return [[at(x, y), at(*there)] for y in range(ny) for x in range(nx)
            for there in ((x + 1, y), (x, y + 1))]


def lattice_yaml(path, n, hw, bonds, extra_basis="", terms="ˣʸᶻ",
                 bonds_z=None):
    lines = [f"basis:\n  number_spins: {n}\n  hamming_weight: {hw}\n",
             extra_basis, "hamiltonian:\n  name: Heisenberg\n  terms:\n"]
    for axis in terms:
        sites = bonds_z if axis == "ᶻ" and bonds_z is not None else bonds
        lines.append(f"    - {{expression: \"σ{axis}₀ σ{axis}₁\", "
                     f"sites: {sites}}}\n")
    with open(path, "w", encoding="utf-8") as f:
        f.writelines(lines)
    return str(path)


def kron_hamiltonian(n, bonds):
    """sum over bonds of XX + YY + ZZ on the full space, site i the bit i
    of a state's index."""
    def on(site_ops):
        out = sp.identity(1, dtype=complex, format="csr")
        for site in reversed(range(n)):
            out = sp.kron(out, site_ops.get(site, sp.identity(2)),
                          format="csr")
        return out
    H = sp.csr_matrix((1 << n, 1 << n), dtype=complex)
    for i, j in bonds:
        for p in PAULI.values():
            H = H + on({i: p, j: p})
    assert abs(H.imag).max() == 0
    return H.real.tocsr()


@pytest.fixture(scope="module")
def ref():
    mod, _ = check.load_reference({
        "reference": "lattice_heisenberg",
        "model": "benchmark/configs/square_5x5.yaml"})
    return mod


@pytest.fixture(scope="module", params=[(3, 3, 4), (3, 3, 5), (4, 4, 4),
                                        (4, 4, 6)],
                ids=["3x3_hw4", "3x3_hw5", "4x4_hw4", "4x4_hw6"])
def case(request, tmp_path_factory, ref):
    """(spec, its states, the sector's dense H from Kronecker products)."""
    nx, ny, hw = request.param
    bonds = torus_bonds(nx, ny)
    spec = ref.LatticeSpec(lattice_yaml(
        tmp_path_factory.mktemp("lattice") / "model.yaml", nx * ny, hw,
        bonds))
    states = ref.enumerate_representatives(spec)
    full = kron_hamiltonian(nx * ny, bonds)
    rows = states.astype(np.int64)
    return spec, states, full[rows][:, rows].toarray()


def test_enumeration_is_the_sector_ascending(case):
    spec, states, _ = case
    want = [s for s in range(1 << spec.n) if bin(s).count("1") == spec.hw]
    assert states.dtype == np.uint64
    np.testing.assert_array_equal(states, want)


def test_apply_rows_against_the_dense_matrix(case, ref):
    spec, states, dense = case
    x = np.random.default_rng(7).standard_normal(states.size)
    rows = np.sort(np.random.default_rng(8).choice(
        states.size, size=states.size // 2, replace=False))
    got = ref.apply_rows(spec, states, x, rows)
    np.testing.assert_allclose(got, (dense @ x)[rows], atol=1e-13, rtol=1e-13)
    low = ref.apply_rows(spec, states, x, rows, np.float32)
    assert low.dtype == np.float32
    assert 1e-8 < np.max(np.abs(low - got)) < 1e-3


def test_sparse_matrix_and_ground_energy_against_dense(case, ref):
    spec, states, dense = case
    np.testing.assert_array_equal(
        ref.sparse_matrix(spec, states).toarray(), dense)
    want = float(np.linalg.eigvalsh(dense)[0])
    assert ref.solve_ground_energy(spec) == pytest.approx(want, rel=1e-12)
    # the name check.py calls, given the specification: solved once, then
    # kept by the specification's digest
    assert ref.Spec is ref.LatticeSpec
    assert ref.ground_energy(spec) == pytest.approx(want, rel=1e-12)
    assert ref.STORED_E0[spec.digest] == ref.ground_energy(spec)


def test_count_offdiagonal_against_the_closed_form(case, ref):
    """A bond couples the states in which its two spins differ: 2 x
    C(n - 2, hw - 1) of them, and on these tori no two bonds join the same
    pair of sites."""
    spec, states, dense = case
    want = 2 * len(spec.bonds) * comb(spec.n - 2, spec.hw - 1)
    rows = np.arange(states.size)
    assert ref.count_offdiagonal(spec, states, rows) == want
    assert np.count_nonzero(dense - np.diag(np.diag(dense))) == want
    half = states.size // 2
    assert (ref.count_offdiagonal(spec, states, rows[:half])
            + ref.count_offdiagonal(spec, states, rows[half:])) == want


def test_a_bond_listed_twice_counts_twice_and_is_one_element(tmp_path, ref):
    """The 2 x 4 torus: the wrap doubles every vertical bond."""
    bonds = torus_bonds(4, 2)
    spec = ref.LatticeSpec(lattice_yaml(tmp_path / "m.yaml", 8, 4, bonds))
    states = ref.enumerate_representatives(spec)
    rows = states.astype(np.int64)
    dense = kron_hamiltonian(8, bonds)[rows][:, rows].toarray()
    x = np.random.default_rng(1).standard_normal(states.size)
    np.testing.assert_allclose(
        ref.apply_rows(spec, states, x, np.arange(states.size)), dense @ x,
        atol=1e-13, rtol=1e-13)
    assert ref.count_offdiagonal(spec, states, np.arange(states.size)) \
        == np.count_nonzero(dense - np.diag(np.diag(dense)))
    assert ref.ground_energy(spec) == pytest.approx(
        float(np.linalg.eigvalsh(dense)[0]), rel=1e-12)


@pytest.mark.parametrize("what, build", [
    ("a symmetry group", lambda p: lattice_yaml(
        p, 8, 4, torus_bonds(4, 2), extra_basis="  symmetries:\n    - "
        "{permutation: [1, 2, 3, 0, 5, 6, 7, 4], sector: 0}\n")),
    ("a spin-inversion sector", lambda p: lattice_yaml(
        p, 8, 4, torus_bonds(4, 2), extra_basis="  spin_inversion: 1\n")),
    ("no fixed hamming weight", lambda p: lattice_yaml(
        p, 8, "null", torus_bonds(4, 2))),
    ("not the Heisenberg coupling", lambda p: lattice_yaml(
        p, 8, 4, torus_bonds(4, 2), terms="ˣʸ")),
    ("the three terms' bonds differ", lambda p: lattice_yaml(
        p, 8, 4, torus_bonds(4, 2), bonds_z=torus_bonds(4, 2)[:-1])),
    ("bond", lambda p: lattice_yaml(p, 8, 4, [[0, 1], [3, 8]])),
    ("sites", lambda p: lattice_yaml(p, 33, 16, [[0, 1]])),
])
def test_what_the_reference_does_not_cover_is_refused(tmp_path, ref, what,
                                                      build):
    with pytest.raises(NotImplementedError, match=what):
        ref.LatticeSpec(build(tmp_path / "model.yaml"))


def test_the_benchmarks_configuration_is_what_the_reference_counts(ref):
    """``square_5x5.json`` against the reference's own numbers that need no
    enumeration, and the stored energy's key against the YAML's digest."""
    import json

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "square_5x5.json")) as f:
        config = json.load(f)
    mod, spec = check.load_reference(config)
    assert (spec.n, spec.hw, len(spec.bonds), spec.group_order) == (
        config["number_spins"], config["hamming_weight"], config["bonds"],
        config["group_order"]) == (25, 13, 50, 1)
    assert sorted(map(sorted, spec.bonds)) == sorted(
        map(sorted, torus_bonds(5, 5)))
    assert config["number_states"] == comb(25, 13)
    assert config["offdiag_nonzeros"] == 2 * 50 * comb(23, 12)
    assert config["reduced"] == [] and len(config["assumed"]) == 2
    assert spec.digest in mod.STORED_E0
    # the stored value is what the run compares with, without a solve
    assert mod.ground_energy(spec) == mod.STORED_E0[spec.digest] == \
        pytest.approx(-60.14308176824, abs=1e-10)
