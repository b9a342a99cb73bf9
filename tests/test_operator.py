"""Operator tables + host matvec vs the independent dense projected matrix.

This is the heart of the correctness story: the production pipeline
(nonbranching masks → state_info canonicalization → χ·norm-ratio rescale,
mirroring BatchedOperator.chpl:82-213) must reproduce B†·H_full·B computed by
explicit Kronecker/projector algebra, to the reference tolerances
(atol 1e-14 / rtol 1e-12, TestMatrixVectorProduct.chpl:15-16).
"""

import numpy as np
import pytest

from distributed_matvec_tpu.models.basis import SpinBasis
from distributed_matvec_tpu.models.lattices import (
    chain_edges,
    heisenberg_from_edges,
    kagome_12_edges,
)
from distributed_matvec_tpu.models.expression import parse_expression
from distributed_matvec_tpu.models.operator import Operator

import dense_ref

ATOL, RTOL = 1e-14, 1e-12


def dense_expr_pairs(op):
    """Re-parse the operator's defining expressions for the dense path."""
    return op._dense_exprs  # attached by helpers below


def build_heisenberg(n, hw=None, inv=None, syms=(), edges=None):
    basis = SpinBasis(n, hw, inv, syms)
    edges = edges if edges is not None else chain_edges(n)
    op = heisenberg_from_edges(basis, edges)
    sites = [list(e) for e in edges]
    op._dense_exprs = [
        (parse_expression("σˣ₀ σˣ₁"), sites),
        (parse_expression("σʸ₀ σʸ₁"), sites),
        (parse_expression("σᶻ₀ σᶻ₁"), sites),
    ]
    return op


def dense_effective_matrix(op):
    basis = op.basis
    h_full = dense_ref.operator_matrix_full(basis.number_spins, op._dense_exprs)
    reps, norms = dense_ref.brute_force_representatives(
        basis.number_spins, basis.representatives, basis.group
    )
    np.testing.assert_array_equal(reps, basis.representatives)
    return dense_ref.projected_matrix(
        basis.number_spins, h_full, basis.representatives, basis.norms, basis.group
    )


CONFIGS = [
    # (n, hw, inv, syms) — mirroring the reference's config matrix shapes
    (4, 2, None, ()),
    (6, 3, None, ()),
    (8, 4, None, ()),
    (10, 5, -1, ()),  # heisenberg_chain_10.yaml sector
    (8, 4, 1, ()),
    (8, None, None, ()),
    (8, 4, None, [([1, 2, 3, 4, 5, 6, 7, 0], 0)]),
    (8, 4, 1, [([1, 2, 3, 4, 5, 6, 7, 0], 0), ([7, 6, 5, 4, 3, 2, 1, 0], 0)]),
    (10, 5, None, [([1, 2, 3, 4, 5, 6, 7, 8, 9, 0], 1)]),  # complex characters
    (12, 6, 1, [([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 0], 0),
                ([11, 10, 9, 8, 7, 6, 5, 4, 3, 2, 1, 0], 0)]),  # chain_24_symm shape
]


@pytest.mark.parametrize("n,hw,inv,syms", CONFIGS)
def test_matvec_host_matches_dense(n, hw, inv, syms, rng):
    op = build_heisenberg(n, hw, inv, syms)
    op.basis.build()
    h_eff = dense_effective_matrix(op)
    # Hermiticity of the projected matrix (sanity of the dense path itself)
    np.testing.assert_allclose(h_eff, h_eff.conj().T, atol=1e-12)
    x = rng.random(op.basis.number_states) - 0.5
    y_ref = h_eff @ x
    y = op.matvec_host(x, batch_size=257)  # odd batch to exercise chunk edges
    if op.effective_is_real:
        assert np.abs(y_ref.imag).max() < 1e-12
        y_ref = y_ref.real
    else:
        x = x.astype(np.complex128)
        y = op.matvec_host(x, batch_size=257)
    np.testing.assert_allclose(y, y_ref, atol=ATOL * max(1, n), rtol=RTOL)


@pytest.mark.parametrize("n,hw,inv,syms", CONFIGS)
def test_matvec_host_rows_matches_dense(n, hw, inv, syms, rng):
    """The row (gather) form used as the large-basis reference — sampled
    rows and a slice — against the independent dense matrix."""
    op = build_heisenberg(n, hw, inv, syms)
    op.basis.build()
    N = op.basis.number_states
    h_eff = dense_effective_matrix(op)
    x = rng.random(N) - 0.5
    if not op.effective_is_real:
        x = x + 1j * (rng.random(N) - 0.5)
    y_ref = h_eff @ x
    if op.effective_is_real:
        y_ref = y_ref.real
    rows = np.sort(rng.choice(N, size=min(N, 17), replace=False))
    for sel in (rows, slice(0, min(N, 9))):
        y = op.matvec_host_rows(x, sel)
        assert y.dtype == y_ref.dtype
        np.testing.assert_allclose(y, y_ref[sel], atol=ATOL * max(1, n),
                                   rtol=RTOL)


@pytest.mark.parametrize("n,hw,inv,syms", CONFIGS)
def test_to_sparse_matches_dense(n, hw, inv, syms):
    # covers projected bases and complex-character sectors too — the
    # off-diagonal source indexing relies on amps keeping [B, T] order
    op = build_heisenberg(n, hw, inv, syms)
    op.basis.build()
    h_eff = dense_effective_matrix(op)
    ours = np.asarray(op.to_sparse().todense())
    np.testing.assert_allclose(ours, h_eff, atol=1e-12)


def test_issue_01_regression(rng):
    """data/issue_01.yaml: kagome-12 with a period-2 permutation, sector 1,
    and two couplings (1.0 and 0.8)."""
    perm = [2, 10, 0, 4, 3, 7, 11, 5, 9, 8, 1, 6]
    basis = SpinBasis(12, 6, None, [(perm, 1)])
    lattice_1 = [[0, 1], [1, 2], [0, 3], [3, 5], [5, 6], [6, 7], [4, 7], [2, 4],
                 [5, 8], [8, 0], [9, 2], [7, 9], [2, 10], [10, 0], [7, 11], [11, 5]]
    lattice_2 = [[1, 3], [6, 4], [6, 8], [1, 9], [10, 4], [11, 3], [11, 9], [10, 8]]
    from distributed_matvec_tpu.models.operator import Operator

    exprs = []
    dense_exprs = []
    for e in ["σˣ₀ σˣ₁", "σʸ₀ σʸ₁", "σᶻ₀ σᶻ₁"]:
        exprs.append((e, lattice_1))
        dense_exprs.append((parse_expression(e), lattice_1))
    for e in ["0.8 × σˣ₀ σˣ₁", "0.8 × σʸ₀ σʸ₁", "0.8 × σᶻ₀ σᶻ₁"]:
        exprs.append((e, lattice_2))
        dense_exprs.append((parse_expression(e), lattice_2))
    op = Operator.from_expressions(basis, exprs)
    op._dense_exprs = dense_exprs
    basis.build()
    assert op.is_hermitian
    h_eff = dense_effective_matrix(op)
    x = rng.random(basis.number_states) - 0.5
    y = op.matvec_host(x)
    y_ref = h_eff @ x
    if op.effective_is_real:
        y_ref = y_ref.real
    np.testing.assert_allclose(y, y_ref, atol=1e-13, rtol=RTOL)


def test_hermiticity_and_reality_flags():
    op = build_heisenberg(6, 3)
    assert op.is_hermitian and op.is_real
    # number_off_diag_terms counts flip-mask groups = number of bonds
    assert op.number_off_diag_terms == 6


def test_heisenberg_ground_energy_chain_8():
    """E₀ of the σ-Heisenberg 8-ring (hw sector), a published exact value:
    E₀/J = 4·Σ S·S eigen — cross-check against dense eigendecomposition."""
    op = build_heisenberg(8, 4)
    op.basis.build()
    import scipy.sparse.linalg as sla

    h = op.to_sparse()
    e0 = sla.eigsh(h, k=1, which="SA")[0][0]
    h_eff = dense_effective_matrix(op)
    e0_ref = np.linalg.eigvalsh(h_eff)[0]
    np.testing.assert_allclose(e0, e0_ref, atol=1e-10)


def test_operator_algebra(rng):
    """H = a*op1 + op2 - op3 front-end parity with the reference's
    expression algebra: matvec of the combination equals the combination of
    matvecs, and engines accept the result."""
    basis = SpinBasis(8)   # unconstrained: each piece is sector-valid alone
    sites = [[i, (i + 1) % 8] for i in range(8)]
    xx = Operator.from_expressions(basis, [("σˣ₀ σˣ₁", sites)], name="xx")
    yy = Operator.from_expressions(basis, [("σʸ₀ σʸ₁", sites)], name="yy")
    zz = Operator.from_expressions(basis, [("σᶻ₀ σᶻ₁", sites)], name="zz")
    basis.build()
    H = xx + yy + 0.5 * zz - 0.25 * zz
    x = rng.random(basis.number_states) - 0.5
    want = (xx.matvec_host(x) + yy.matvec_host(x)
            + 0.25 * zz.matvec_host(x))
    np.testing.assert_allclose(H.matvec_host(x), want, atol=1e-13)
    # scalar mul alone, negation, and same-basis enforcement
    np.testing.assert_allclose((2.0 * zz).matvec_host(x),
                               2 * zz.matvec_host(x), atol=1e-13)
    np.testing.assert_allclose((-zz).matvec_host(x), -zz.matvec_host(x),
                               atol=1e-13)
    other = SpinBasis(8)
    foreign = Operator.from_expressions(other, [("σᶻ₀ σᶻ₁", sites)])
    with pytest.raises(ValueError, match="different bases"):
        _ = zz + foreign
    # the combined operator runs through the jitted engine
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    eng = LocalEngine(H)
    np.testing.assert_allclose(np.asarray(eng.matvec(x)), want,
                               atol=1e-13, rtol=1e-12)


def test_operator_algebra_names():
    basis = SpinBasis(4)
    s = [[0, 1]]
    a = Operator.from_expressions(basis, [("σᶻ₀ σᶻ₁", s)], name="a")
    b = Operator.from_expressions(basis, [("σˣ₀ σˣ₁", s)], name="b")
    assert (a + b).name == "a + b"
    assert (a - b).name == "a - b"
    assert (2.0 * a).name == "2.0·a"
    assert (-a).name == "-a"


def test_state_info_coset_loop_paths_agree(monkeypatch, rng):
    """The unrolled (J ≤ _COSET_UNROLL_MAX) and dynamic-fori coset-scan paths
    of the device state_info must agree bit-for-bit — the dynamic path is
    what large 2-D groups (square_6x6: J=48) compile in reasonable time."""
    import jax
    import jax.numpy as jnp

    from distributed_matvec_tpu.ops import kernels as K

    op = build_heisenberg(
        12, 6, 1, [([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 0], 0),
                   ([11, 10, 9, 8, 7, 6, 5, 4, 3, 2, 1, 0], 0)])
    op.basis.build()
    t = K.device_tables(op)
    J = t.group.elem.shape[0]
    assert J > 1, "need a multi-coset group for this test"
    states = jnp.asarray(
        rng.integers(0, 1 << 12, 4096, dtype=np.uint64) | np.uint64(0))

    rep_u, char_u, norm_u = jax.jit(K.state_info)(t.group, states)
    monkeypatch.setattr(K, "_COSET_UNROLL_MAX", 0)   # force the fori path
    rep_d, char_d, norm_d = jax.jit(
        lambda g, s: K.state_info(g, s))(t.group, states)
    np.testing.assert_array_equal(np.asarray(rep_u), np.asarray(rep_d))
    np.testing.assert_array_equal(np.asarray(char_u), np.asarray(char_d))
    np.testing.assert_array_equal(np.asarray(norm_u), np.asarray(norm_d))
