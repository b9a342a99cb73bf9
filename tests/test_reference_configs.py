"""Run the reference's own YAML config matrix end-to-end.

The reference's test matrix drives 13 matvec + 14 enumeration configs from
``data/*.yaml`` (``Makefile:88-126``).  The golden HDF5 archives are not
available offline, so ground truth is layered:

  * every config ≤ 24 sites: YAML → basis build → jitted engine matvec vs the
    independent host (NumPy) matvec at the golden tolerances,
  * configs ≤ 12 sites additionally: dense Kronecker/projector matrix
    (tests/dense_ref.py — fully independent of the production term compiler).

``issue_01.yaml`` is the reference's regression input (Makefile:111-125).
"""

import os

import numpy as np
import pytest
import yaml as pyyaml

import dense_ref
from distributed_matvec_tpu.models.expression import parse_expression
from distributed_matvec_tpu.models.yaml_io import load_config_from_yaml
from distributed_matvec_tpu.parallel.engine import LocalEngine

DATA = "/root/reference/data"
# the configurations the repo keeps a copy of (upstream's schema)
REPO_DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "data")
ATOL, RTOL = 1e-13, 1e-12

SMALL = [  # dense-verified
    "heisenberg_chain_4.yaml",
    "heisenberg_chain_6.yaml",
    "heisenberg_chain_8.yaml",
    "heisenberg_chain_10.yaml",
    "heisenberg_chain_12.yaml",
    "heisenberg_kagome_12.yaml",
    "heisenberg_kagome_12_symm.yaml",
    "issue_01.yaml",
]
MEDIUM = [  # engine vs host matvec
    "heisenberg_chain_16.yaml",
    "heisenberg_chain_20.yaml",
    "heisenberg_square_4x4.yaml",
    "heisenberg_kagome_16.yaml",
]
LARGE = [  # symmetry-projected or multi-million-state, slow-marked
    "heisenberg_chain_24.yaml",
    "heisenberg_chain_24_symm.yaml",
]

require_data = pytest.mark.skipif(
    not os.path.isdir(DATA), reason="reference data not mounted"
)


def _load(name):
    """Upstream's file where it is mounted, else the repo's copy."""
    path = os.path.join(DATA, name)
    if not os.path.exists(path):
        path = os.path.join(REPO_DATA, name)
    cfg = load_config_from_yaml(path)
    assert cfg.hamiltonian is not None
    cfg.basis.build()
    return cfg


def _random_x(cfg, rng):
    x = rng.random(cfg.basis.number_states) - 0.5
    if not cfg.hamiltonian.effective_is_real:
        x = x.astype(np.complex128)
    return x


@require_data
@pytest.mark.parametrize("name", SMALL)
def test_small_configs_vs_dense(name, rng):
    cfg = _load(name)
    raw = pyyaml.safe_load(open(os.path.join(DATA, name)))
    pairs = [(parse_expression(t["expression"]), t["sites"])
             for t in raw["hamiltonian"]["terms"]]
    basis = cfg.basis
    h_full = dense_ref.operator_matrix_full(basis.number_spins, pairs)
    h_eff = dense_ref.projected_matrix(
        basis.number_spins, h_full, basis.representatives, basis.norms,
        basis.group)
    x = _random_x(cfg, rng)
    y_ref = h_eff @ x
    if cfg.hamiltonian.effective_is_real:
        y_ref = y_ref.real
    np.testing.assert_allclose(
        cfg.hamiltonian.matvec_host(x), y_ref, atol=ATOL, rtol=RTOL)
    eng = LocalEngine(cfg.hamiltonian, batch_size=97)
    np.testing.assert_allclose(
        np.asarray(eng.matvec(x)), y_ref, atol=ATOL, rtol=RTOL)


@require_data
@pytest.mark.parametrize("name", MEDIUM)
def test_medium_configs_engine_vs_host(name, rng):
    cfg = _load(name)
    x = _random_x(cfg, rng)
    eng = LocalEngine(cfg.hamiltonian)
    np.testing.assert_allclose(
        np.asarray(eng.matvec(x)), cfg.hamiltonian.matvec_host(x),
        atol=ATOL, rtol=RTOL)


@require_data
@pytest.mark.slow
@pytest.mark.parametrize("name", LARGE)
def test_large_symm_configs(name, rng):
    cfg = _load(name)
    x = _random_x(cfg, rng)
    eng = LocalEngine(cfg.hamiltonian)
    np.testing.assert_allclose(
        np.asarray(eng.matvec(x)), cfg.hamiltonian.matvec_host(x),
        atol=ATOL, rtol=RTOL)


@require_data
def test_enumeration_counts_match_sector_dimensions():
    """Enumeration sanity across the matrix: sector sizes obey the
    character-sum dimension formula (dense_ref projector ranks for the
    smallest, plain binomials for the unprojected)."""
    from math import comb

    for name, n, hw in [("heisenberg_chain_10.yaml", 10, 5),
                        ("heisenberg_chain_16.yaml", 16, 8),
                        ("heisenberg_kagome_16.yaml", 16, 8)]:
        cfg = load_config_from_yaml(os.path.join(DATA, name))
        cfg.basis.build()
        if not cfg.basis.requires_projection:
            assert cfg.basis.number_states == comb(n, hw)


@require_data
def test_full_yaml_matrix_loads():
    """Every in-tree YAML ≤ 40 sites parses through the schema loader
    (loadConfigFromYaml parity, ForeignTypes.chpl:261-288) — no build."""
    import glob

    for path in sorted(glob.glob(os.path.join(DATA, "*.yaml"))):
        cfg = load_config_from_yaml(path)
        assert cfg.basis.number_spins >= 4
        assert cfg.hamiltonian is not None
        assert cfg.hamiltonian.number_off_diag_terms > 0


REPO_COPIES = {  # name: (spins, hamming weight, off-diagonal terms)
    "heisenberg_chain_16.yaml": (16, 8, 16),
    "heisenberg_chain_28.yaml": (28, 14, 28),
    "heisenberg_chain_32_symm.yaml": (32, 16, 32),
    "heisenberg_square_5x5.yaml": (25, 13, 50),
}


@pytest.mark.parametrize("name", sorted(REPO_COPIES))
def test_repo_yaml_copies_load(name):
    """The configurations the repo keeps a copy of (``data/``, the
    benchmark's deployments among them) through the schema loader, as
    ``test_full_yaml_matrix_loads`` takes upstream's — no build."""
    import glob

    assert sorted(map(os.path.basename, glob.glob(
        os.path.join(REPO_DATA, "*.yaml")))) == sorted(REPO_COPIES)
    cfg = load_config_from_yaml(os.path.join(REPO_DATA, name))
    assert not cfg.basis.is_built
    assert (cfg.basis.number_spins, cfg.basis.hamming_weight,
            cfg.hamiltonian.number_off_diag_terms) == REPO_COPIES[name]


@pytest.mark.slow
def test_square_5x5_engine_vs_host(rng):
    """square_5x5 (N=5.2M, 50 bonds) — the largest config whose host
    matvec is still test-tractable; with this the automated matrix covers
    every `make check` config (Makefile:111-125) plus two sizes beyond."""
    cfg = _load("heisenberg_square_5x5.yaml")
    x = _random_x(cfg, rng)
    eng = LocalEngine(cfg.hamiltonian)
    np.testing.assert_allclose(
        np.asarray(eng.matvec(x)), cfg.hamiltonian.matvec_host(x),
        atol=ATOL, rtol=RTOL)


@pytest.mark.slow
def test_chain_28_fused_vs_independent(rng):
    """chain_28 (N=40.1M) — fused (recompute-on-the-fly) engine against
    the term-compiler-independent bit-op apply; host matvec_host is too
    slow at this size, the independent ring apply is not.  (``_load``
    finds the repo's copy, ``data/heisenberg_chain_28.yaml``, where
    upstream's is not mounted.)"""
    from independent_ref import heisenberg_ring_apply

    cfg = _load("heisenberg_chain_28.yaml")
    x = _random_x(cfg, rng)
    eng = LocalEngine(cfg.hamiltonian, mode="fused")
    y_ref = heisenberg_ring_apply(cfg.basis.representatives, 28, x)
    np.testing.assert_allclose(
        np.asarray(eng.matvec(x)), y_ref, atol=ATOL, rtol=RTOL)
