"""The square lattice without symmetries (upstream's
``data/heisenberg_square_5x5.yaml``, the benchmark's ``square_5x5``) on the
normal path: YAML -> ``load_config_from_yaml`` -> ``LocalEngine`` ->
``solve.lanczos``, at 4x4 and 4x5 against the benchmark's plain reference
(``benchmark/references/lattice_heisenberg.py``, which imports nothing of
the program), both forms of the term loop, and the 5x5 numbers that need no
build: the staircase its row-nnz histogram gives, the term loop's form at
those counts, and the YAML in ``data/``.
"""

import importlib.util
import os
from math import comb
from types import SimpleNamespace

import numpy as np
import pytest

from distributed_matvec_tpu.models.lattices import square_edges
from distributed_matvec_tpu.models.yaml_io import load_config_from_yaml
from distributed_matvec_tpu.parallel.engine import (
    LocalEngine, ell_term_loop, staircase_levels)
from distributed_matvec_tpu.solve import lanczos
from distributed_matvec_tpu.utils.config import update_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
YAML_5X5 = os.path.join(ROOT, "data", "heisenberg_square_5x5.yaml")
TORI = {"4x4": (4, 4, 8), "4x5": (4, 5, 10)}

# square_5x5's row-nnz histogram (twice the antiparallel bonds of a state;
# counted by the plain reference over all 5,200,300 rows, PR 28) and what
# ``staircase_levels`` makes of it at n_pad = 80 x 65,536
HIST_5X5 = {12: 100, 14: 1_200, 16: 14_275, 18: 65_300, 20: 246_500,
            22: 606_000, 24: 1_068_600, 26: 1_249_600, 28: 1_027_975,
            30: 572_200, 32: 240_800, 34: 79_200, 36: 23_275, 38: 4_300,
            40: 975}
N_5X5, N_PAD_5X5 = 5_200_300, 5_242_880
LEVELS_5X5 = ((0, 14, 5_200_896), (14, 2, 5_199_872), (16, 2, 5_185_536),
              (18, 2, 5_120_000), (20, 2, 4_873_216), (22, 2, 4_267_008),
              (24, 2, 3_198_976), (26, 2, 1_949_696), (28, 2, 921_600),
              (30, 2, 349_184), (32, 2, 108_544), (34, 2, 28_672),
              (36, 2, 6_144), (38, 2, 1_024))


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "lattice_heisenberg", os.path.join(
            ROOT, "benchmark", "references", "lattice_heisenberg.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def torus_yaml(path, nx, ny, hw):
    """The periodic nx x ny Heisenberg model in upstream's YAML schema, as
    ``data/heisenberg_square_5x5.yaml`` states the 5x5 one."""
    bonds = [list(e) for e in square_edges(nx, ny)]
    lines = [f"basis:\n  number_spins: {nx * ny}\n  hamming_weight: {hw}\n",
             "hamiltonian:\n  name: Heisenberg\n  terms:\n"]
    for axis in "ˣʸᶻ":
        lines.append(f"    - {{expression: \"σ{axis}₀ σ{axis}₁\", "
                     f"sites: {bonds}}}\n")
    with open(path, "w", encoding="utf-8") as f:
        f.writelines(lines)
    return str(path)


@pytest.fixture(scope="module")
def torus(tmp_path_factory, reference):
    """``torus(name)``: (operator with its basis built, the reference's
    spec and states) of one of :data:`TORI`, once a module."""
    made = {}

    def get(name):
        if name not in made:
            nx, ny, hw = TORI[name]
            path = torus_yaml(
                tmp_path_factory.mktemp("torus") / f"{name}.yaml", nx, ny, hw)
            cfg = load_config_from_yaml(path, hamiltonian=True)
            cfg.basis.build()
            spec = reference.LatticeSpec(path)
            made[name] = (cfg.hamiltonian, spec,
                          reference.enumerate_representatives(spec))
        return made[name]
    return get


@pytest.fixture
def term_loop():
    """``term_loop(form)`` sets the test hook; ``auto`` comes back after."""
    yield lambda form: update_config(term_loop=form)
    update_config(term_loop="auto")


def _close(got, want):
    np.testing.assert_allclose(got, want, atol=1e-14, rtol=1e-12)


@pytest.mark.parametrize("name", list(TORI))
def test_apply_matches_the_plain_reference(name, torus, reference):
    """Every row of one apply at the configuration's own contract."""
    op, spec, states = torus(name)
    np.testing.assert_array_equal(op.basis.representatives, states)
    eng = LocalEngine(op)
    assert eng.mode == "ell" and eng._ell_counts["levels"] > 1
    x = np.random.default_rng(28).standard_normal(states.size)
    x /= np.linalg.norm(x)
    want = reference.apply_rows(spec, states, x, np.arange(states.size))
    _close(np.asarray(eng.matvec(x)), want)


@pytest.mark.parametrize("name", list(TORI))
def test_ground_energy_matches_the_plain_reference(name, torus, reference):
    op, spec, states = torus(name)
    res = lanczos(LocalEngine(op).matvec, n=states.size, k=1, tol=1e-12)
    want = reference.ground_energy(spec)
    assert abs(float(res.eigenvalues[0]) - want) <= 1e-10 * abs(want)


@pytest.mark.parametrize("name", list(TORI))
def test_both_term_loop_forms_agree(name, torus, reference, term_loop):
    """``scan`` and ``unroll`` through the test hook: the same result, the
    staircase engaged under both, and the form on the span the apply was
    traced under and in the ``engine_init`` event."""
    from distributed_matvec_tpu import obs

    op, spec, states = torus(name)
    x = np.random.default_rng(5).standard_normal(states.size)
    want = reference.apply_rows(spec, states, x, np.arange(states.size))
    got = {}
    for form in ("scan", "unroll"):
        term_loop(form)
        eng = LocalEngine(op)
        counts = eng._ell_counts
        assert counts["levels"] > 1 and eng._ell_pos_of is not None
        assert counts["terms"] == len(spec.bonds)
        assert counts["widest_row"] == sum(
            idx.shape[0] for idx, _ in eng._ell_levels) <= counts["terms"]
        got[form] = np.asarray(eng.matvec(x))
        _close(got[form], want)
        wide = counts["widest_row"]
        said = {"unrolled_columns": wide if form == "unroll" else 0,
                "scanned_columns": wide if form == "scan" else 0}
        init = obs.events("engine_init")[-1]
        assert {k: init[k] for k in said} == said
        traced = [e for e in obs.events("span") if e["name"] == "apply"
                  and "scanned_columns" in e]
        assert {k: traced[-1][k] for k in said} == said
    _close(got["scan"], got["unroll"])


def test_staircase_of_the_5x5_histogram():
    """``staircase_levels`` on square_5x5's histogram, no build: 14 levels,
    135,231,488 table slots, 140,474,368 gathered slots for 135,207,800
    non-zeros (fill 96.25%)."""
    hist = np.zeros(51, np.int64)
    for width, rows in HIST_5X5.items():
        hist[width] = rows
    hist[0] = N_PAD_5X5 - N_5X5                 # the padded rows
    assert sum(HIST_5X5.values()) == N_5X5 == comb(25, 13)
    live = int(np.dot(np.arange(51), hist))
    assert live == 2 * 50 * comb(23, 12) == 135_207_800
    stair, levels = staircase_levels(hist, N_PAD_5X5)
    assert stair and levels == LEVELS_5X5
    slots = sum(k * L for _, k, L in levels)
    assert slots == 135_231_488
    assert 100.0 * live / (slots + N_PAD_5X5) == pytest.approx(96.25, abs=0.01)


@pytest.mark.parametrize("form, unrolled", [("auto", False),
                                            ("scan", False),
                                            ("unroll", True)])
@pytest.mark.parametrize("cell, levels", [
    ("square_5x5", [(k, L) for _, k, L in LEVELS_5X5]),
    ("chain_32_symm", [(6, 4_708_352), (2, 4_707_328), (2, 4_694_016),
                       (2, 4_600_832), (2, 4_224_000), (2, 3_326_976),
                       (2, 2_030_592), (2, 878_592), (2, 249_856),
                       (2, 44_032), (2, 5_120), (6, 1_024)]),
])
def test_term_loop_form_at_the_benchmarks_counts(cell, levels, form, unrolled,
                                                 term_loop):
    """The form of the term loop at the benchmark's two Hamiltonians, from
    the levels' shapes alone: the scan under ``auto``, the form the chip
    runs chose at both (no slower on the device, 4.3% faster through a
    chain_32_symm solve, lighter: PERF.md §6, PR 28); one gather a column
    only under the ``unroll`` hook."""
    term_loop(form)
    shaped = [(SimpleNamespace(shape=(k, L)), None) for k, L in levels]
    unroll, counts = ell_term_loop(shaped)
    width = sum(k for k, _ in levels)
    assert unroll is unrolled and width == {"square_5x5": 40,
                                            "chain_32_symm": 32}[cell]
    assert counts == {"unrolled_columns": width if unrolled else 0,
                      "scanned_columns": 0 if unrolled else width}


def test_the_5x5_basis_is_listed_not_searched():
    """The sector's 5,200,300 states from the YAML, ascending, every one
    its own representative with norm 1.  (A second: the listing is built a
    bit at a time; the recursion it replaces took 18 s on the chip's host.)"""
    basis = load_config_from_yaml(YAML_5X5).basis
    basis.build()
    states = basis.representatives
    assert states.dtype == np.uint64 and states.size == N_5X5
    assert basis.number_states == N_5X5
    assert (np.diff(states.astype(np.int64)) > 0).all()
    bits = np.zeros(states.size, np.int64)
    for site in range(25):
        bits += ((states >> np.uint64(site)) & np.uint64(1)).astype(np.int64)
    assert (bits == 13).all() and int(states[-1]) < 1 << 25
    assert (basis.norms == 1.0).all()


def test_the_5x5_yaml_describes_upstreams_sector():
    """``data/heisenberg_square_5x5.yaml`` through the schema loader, no
    build: 25 spins, weight 13 (5,200,300 states), no group, 50 bonds."""
    cfg = load_config_from_yaml(YAML_5X5, hamiltonian=True)
    basis = cfg.basis
    assert not basis.is_built
    assert (basis.number_spins, basis.hamming_weight) == (25, 13)
    assert basis.spin_inversion is None and not basis.requires_projection
    assert comb(basis.number_spins, basis.hamming_weight) == N_5X5
    assert cfg.hamiltonian.number_off_diag_terms == 50
    with open(YAML_5X5, encoding="utf-8") as f, open(os.path.join(
            ROOT, "benchmark", "configs", "square_5x5.yaml"),
            encoding="utf-8") as g:
        assert f.read() == g.read()      # the benchmark's copy is a copy
