"""Eigensolvers vs dense eigh — the Diagonalize driver contract.

The reference validates its solver through PRIMME's own residuals and the
golden HDF5 eigenvalues (Diagonalize.chpl:248-256); here the ground truth is
dense diagonalization of the symmetry-adapted matrix at 1e-10.
"""

import numpy as np
import pytest

from distributed_matvec_tpu.parallel.engine import LocalEngine
from distributed_matvec_tpu.solve import lanczos, lanczos_block, lobpcg

from test_operator import build_heisenberg, dense_effective_matrix

TOL = 1e-9


def _dense_evals(op, k):
    h = dense_effective_matrix(op)
    w = np.linalg.eigvalsh(h)
    return w[:k]


@pytest.mark.parametrize("n,hw,inv,syms", [
    (10, 5, None, ()),
    (12, 6, 1, [([*range(1, 12), 0], 0)]),
    (8, 4, None, [([*range(1, 8), 0], 1)]),   # complex sector
])
def test_lanczos_ground_state(n, hw, inv, syms):
    op = build_heisenberg(n, hw, inv, syms)
    op.basis.build()
    eng = LocalEngine(op)
    want = _dense_evals(op, 2)
    res = lanczos(eng.matvec, op.basis.number_states, k=2, tol=1e-11,
                  compute_eigenvectors=True, seed=5)
    assert res.converged
    np.testing.assert_allclose(res.eigenvalues, want, atol=1e-9)
    # eigenvector residual ‖Hv − λv‖
    v = res.eigenvectors[0]
    hv = np.asarray(eng.matvec(v))
    r = np.linalg.norm(hv - res.eigenvalues[0] * np.asarray(v))
    assert r < 1e-7


@pytest.mark.parametrize("n,hw,inv,syms,k,p", [
    (12, 6, None, (), 4, 4),                   # real sector, k == block
    (12, 6, 1, [([*range(1, 12), 0], 0)], 3, 2),  # symmetry-reduced, k > p
    (8, 4, None, [([*range(1, 8), 0], 1)], 2, 2),   # complex sector (c128)
])
def test_lanczos_block_ground_states(n, hw, inv, syms, k, p):
    """Block Lanczos over the engine's batched [N, p] matvec reproduces the
    dense lowest-k spectrum (including near-degenerate clusters a
    single-vector recurrence resolves only sequentially)."""
    op = build_heisenberg(n, hw, inv, syms)
    op.basis.build()
    eng = LocalEngine(op)
    want = _dense_evals(op, k)
    res = lanczos_block(eng.matvec, op.basis.number_states, k=k,
                        block_size=p, tol=1e-11, max_iters=400,
                        compute_eigenvectors=True, seed=7)
    assert res.converged
    np.testing.assert_allclose(res.eigenvalues, want, atol=1e-8)
    for lam, v in zip(res.eigenvalues, res.eigenvectors):
        hv = np.asarray(eng.matvec(np.asarray(v)))
        assert np.linalg.norm(hv - lam * np.asarray(v)) < 1e-6


def test_lanczos_block_rejects_pair_engines():
    from distributed_matvec_tpu.utils.config import get_config, update_config
    op = build_heisenberg(8, 4, None, [([*range(1, 8), 0], 1)])
    op.basis.build()
    prev = get_config().complex_pair
    update_config(complex_pair="on")
    try:
        eng = LocalEngine(op)
        assert eng.pair
        with pytest.raises(ValueError, match="pair-mode"):
            lanczos_block(eng.matvec, op.basis.number_states, k=1)
    finally:
        update_config(complex_pair=prev)


def test_lanczos_distributed(rng):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine

    op = build_heisenberg(12, 6)
    op.basis.build()
    eng = DistributedEngine(op, n_devices=4)
    want = _dense_evals(op, 1)
    v0 = eng.random_hashed(seed=11)
    res = lanczos(eng.matvec, v0=v0, k=1, tol=1e-11)
    assert res.converged
    np.testing.assert_allclose(res.eigenvalues[:1], want, atol=1e-9)


@pytest.mark.parametrize("mcap", [24, 51])   # 51: not a multiple of the GS
def test_lanczos_thick_restart(mcap):        # row-block — clamp regression
    rng = np.random.default_rng(0)
    A = rng.standard_normal((400, 400))
    A = (A + A.T) / 2
    import jax.numpy as jnp

    Aj = jnp.asarray(A)
    res = lanczos(lambda x: Aj @ x, 400, k=2, max_basis_size=mcap,
                  min_restart_size=8, tol=1e-10, max_iters=400,
                  compute_eigenvectors=True)
    want = np.linalg.eigvalsh(A)[:2]
    assert res.converged and res.restarts > 0
    np.testing.assert_allclose(res.eigenvalues, want, atol=1e-8)
    v = np.asarray(res.eigenvectors[0])
    assert np.linalg.norm(A @ v - res.eigenvalues[0] * v) < 1e-7


def test_lanczos_wrapped_method_not_hijacked():
    """A bound method other than engine.matvec must keep its own semantics
    (the bound_matvec substitution only applies to the stock matvec)."""
    import jax.numpy as jnp

    op = build_heisenberg(10, 5)
    op.basis.build()
    sigma = 7.0

    class Shifted(LocalEngine):
        def shifted(self, x):
            return self.matvec(x) - sigma * jnp.asarray(x)

    sh = Shifted(op)
    plain = lanczos(LocalEngine(op).matvec, op.basis.number_states, k=1,
                    tol=1e-10)
    res = lanczos(sh.shifted, op.basis.number_states, k=1, tol=1e-10)
    np.testing.assert_allclose(res.eigenvalues[0],
                               plain.eigenvalues[0] - sigma, atol=1e-8)


def test_lobpcg_ground_state():
    op = build_heisenberg(10, 5)
    op.basis.build()
    eng = LocalEngine(op)
    want = _dense_evals(op, 2)
    evals, evecs, iters = lobpcg(eng.matvec, op.basis.number_states, k=2,
                                 tol=1e-10, seed=2)
    np.testing.assert_allclose(evals, want, atol=1e-7)


def test_lobpcg_distributed_real():
    """LOBPCG over a DistributedEngine runs in the hashed flat space (one
    all_to_all per block apply) and returns block-order eigenvectors."""
    import jax as _jax

    if len(_jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine

    op = build_heisenberg(10, 5)
    op.basis.build()
    n = op.basis.number_states
    eng = DistributedEngine(op, n_devices=8)
    want = _dense_evals(op, 2)
    evals, V, iters = lobpcg(eng.matvec, n, k=2, tol=1e-10, seed=2)
    np.testing.assert_allclose(evals, want, atol=1e-7)
    # block-order eigenvectors: H v = E v via the host matvec.  This pins
    # the hashed→block unshuffle (a layout bug gives an O(1) residual);
    # the threshold is solver-noise-tolerant, eigenvalue accuracy above
    # carries the precision check.
    for i in range(2):
        r = np.linalg.norm(op.matvec_host(V[:, i]) - evals[i] * V[:, i])
        assert r < 1e-3, r


def test_lobpcg_distributed_pair():
    """Distributed pair-form complex sector (previously an explicit
    refusal): LOBPCG in the hashed (re, im) flat space vs dense truth."""
    import jax as _jax

    if len(_jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine
    from distributed_matvec_tpu.utils.config import update_config

    op = build_heisenberg(12, 6, None, [([*range(1, 12), 0], 2)])
    op.basis.build()
    assert not op.effective_is_real
    n = op.basis.number_states
    Hd = op.to_sparse().toarray()
    want = np.linalg.eigvalsh(Hd)[:2]
    update_config(complex_pair="on")
    try:
        eng = DistributedEngine(op, n_devices=8)
        assert eng.pair
        evals, V, iters = lobpcg(eng.matvec, n, k=2, tol=1e-10, seed=4)
    finally:
        update_config(complex_pair="auto")
    np.testing.assert_allclose(evals, want, atol=1e-6)
    assert np.iscomplexobj(V) and V.shape == (n, 2)
    for i in range(2):
        r = np.linalg.norm(Hd @ V[:, i] - evals[i] * V[:, i])
        assert r < 1e-5, r


def test_lanczos_checkpoint_resume(tmp_path):
    """Mid-solve checkpoint/resume (beyond the reference: PRIMME state is
    never saved there).  A truncated run checkpoints its Krylov state; the
    rerun resumes — cumulative iteration count, same converged result as
    an uninterrupted solve."""
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    A = rng.standard_normal((400, 400))
    A = (A + A.T) / 2
    Aj = jnp.asarray(A)
    mv = lambda x: Aj @ x                       # noqa: E731
    want = np.linalg.eigvalsh(A)[0]
    ck = str(tmp_path / "lz.h5")

    partial_res = lanczos(mv, 400, k=1, tol=1e-11, max_iters=24,
                          check_every=8, checkpoint_path=ck,
                          checkpoint_every=1)
    assert not partial_res.converged
    import os
    assert os.path.exists(ck + ".structure.h5") or os.path.exists(ck)

    # an exhausted-budget resume still returns the checkpointed estimates
    # instead of empty arrays (loop body never runs)
    stuck = lanczos(mv, 400, k=1, tol=1e-11, max_iters=24,
                    check_every=8, checkpoint_path=ck)
    assert stuck.resumed_from == 24 and stuck.eigenvalues.size == 1

    resumed = lanczos(mv, 400, k=1, tol=1e-11, max_iters=300,
                      check_every=8, checkpoint_path=ck)
    assert resumed.resumed_from == 24           # genuinely resumed
    assert resumed.converged
    assert resumed.num_iters > 24               # cumulative, not restarted
    np.testing.assert_allclose(resumed.eigenvalues[0], want, atol=1e-9)

    # a different vector space must MISS the checkpoint, not crash
    B = A[:300, :300]
    Bj = jnp.asarray(B)
    fresh = lanczos(lambda x: Bj @ x, 300, k=1, tol=1e-10, max_iters=300,
                    check_every=8, checkpoint_path=ck)
    assert fresh.resumed_from == 0 and fresh.converged
    np.testing.assert_allclose(fresh.eigenvalues[0],
                               np.linalg.eigvalsh(B)[0], atol=1e-8)


def test_lanczos_checkpoint_keyed_by_operator(tmp_path):
    """An engine-backed solve keys its checkpoint by the operator: a rerun
    against an EDITED Hamiltonian with the same lattice (same vector shape)
    must MISS the foreign Krylov state and converge to the new operator's
    ground state, not silently restore the old one (ADVICE r3)."""
    from test_operator import build_heisenberg
    from distributed_matvec_tpu.models.yaml_io import operator_from_dict
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    op1 = build_heisenberg(10, 5)
    op1.basis.build()
    eng1 = LocalEngine(op1)
    ck = str(tmp_path / "lz.h5")
    r1 = lanczos(eng1.matvec, op1.basis.number_states, k=1, tol=1e-11,
                 max_iters=24, check_every=8, checkpoint_path=ck,
                 checkpoint_every=1)
    assert not r1.converged

    # the SAME operator rebuilt from scratch resumes (fingerprint is a pure
    # function of the problem, not the object identity)
    op1b = build_heisenberg(10, 5)
    op1b.basis.build()
    r3 = lanczos(LocalEngine(op1b).matvec, op1b.basis.number_states, k=1,
                 tol=1e-11, max_iters=24, check_every=8, checkpoint_path=ck)
    assert r3.resumed_from == 24

    # same basis, different couplings → same shape, different operator
    ham2 = {"terms": [{"expression": "2.5 σᶻ₀ σᶻ₁ + σˣ₀ σˣ₁ + σʸ₀ σʸ₁",
                       "sites": [[i, (i + 1) % 10] for i in range(10)]}]}
    b2 = type(op1.basis)(number_spins=10, hamming_weight=5)
    op2 = operator_from_dict(ham2, b2)
    op2.basis.build()
    eng2 = LocalEngine(op2)
    r2 = lanczos(eng2.matvec, op2.basis.number_states, k=1, tol=1e-10,
                 max_iters=300, check_every=8, checkpoint_path=ck)
    assert r2.resumed_from == 0              # foreign state refused
    want2 = np.linalg.eigvalsh(op2.to_sparse().toarray())[0]
    np.testing.assert_allclose(r2.eigenvalues[0], want2, atol=1e-8)


def test_lanczos_checkpoint_resume_restart_boundary(tmp_path):
    """Resume across a thick-restart boundary: the checkpoint written after
    a restart carries the arrowhead (lock) state and still converges to
    the truth."""
    import jax.numpy as jnp

    rng = np.random.default_rng(4)
    A = rng.standard_normal((300, 300))
    A = (A + A.T) / 2
    Aj = jnp.asarray(A)
    mv = lambda x: Aj @ x                       # noqa: E731
    ck = str(tmp_path / "lz.h5")
    partial_res = lanczos(mv, 300, k=1, tol=1e-12, max_iters=40,
                          max_basis_size=24, min_restart_size=8,
                          check_every=8, checkpoint_path=ck,
                          checkpoint_every=1)
    assert not partial_res.converged
    resumed = lanczos(mv, 300, k=1, tol=1e-12, max_iters=400,
                      max_basis_size=24, min_restart_size=8,
                      check_every=8, checkpoint_path=ck)
    assert resumed.resumed_from == 40
    assert resumed.converged and resumed.num_iters > 40
    np.testing.assert_allclose(resumed.eigenvalues[0],
                               np.linalg.eigvalsh(A)[0], atol=1e-9)


def test_lobpcg_private_api_present():
    """Multi-process LOBPCG runs jax's UNJITTED lobpcg body under its own
    jit (solve/lobpcg.py:100-107); that body is reached through the
    private ``_lobpcg_standard_callable.__wrapped__``.  Pin the dependency
    here so a jax upgrade that removes it fails CI loudly instead of
    silently degrading the advertised capability to 'use lanczos'."""
    from jax.experimental.sparse.linalg import _lobpcg_standard_callable

    assert callable(getattr(_lobpcg_standard_callable, "__wrapped__", None))


@pytest.mark.parametrize("rows,keep", [(5, 2), (13, 3), (96, 24)],
                         ids=["below_one_block", "ragged", "default_cap"])
def test_combine_rows_matches_matmul(rows, keep):
    """The blocked elementwise Sᵀ·V of the thick restart and the Ritz-vector
    assembly, for row counts below, across and at multiples of the block."""
    import importlib

    import jax.numpy as jnp

    lz = importlib.import_module("distributed_matvec_tpu.solve.lanczos")
    rng = np.random.default_rng(rows)
    S = rng.standard_normal((rows, keep))
    V = rng.standard_normal((lz._buffer_rows(rows), 257))
    got = np.asarray(lz._combine_rows(jnp.asarray(S), jnp.asarray(V)))
    np.testing.assert_allclose(got, S.T @ V[:rows], atol=1e-13, rtol=0)
