"""The Fermi-Hubbard model on the periodic square lattice (the benchmark's
``hubbard_4x4``: the 4x4 torus at U/t = 4 with 5 up and 5 down electrons,
19,079,424 states) on the normal path — ``load_config_from_yaml`` ->
``LocalEngine`` / ``DistributedEngine`` — at sizes the CPU builds, against
the benchmark's plain reference (``benchmark/references/hubbard.py``, which
imports nothing of the program) and, under it, against dense Jordan-Wigner
matrices built from ``dense_ref.fermion_site_operator_matrix``; the
table cut steered on as ``chain_28`` takes it on the chip, and the near and
far slots of the cut read off the product basis's index arithmetic alone;
the counts the build span and the ``engine_init`` event carry; and the full
size's numbers that need no build.
"""

import importlib.util
import json
import os
from math import comb

import numpy as np
import pytest
import scipy.sparse as sp

from distributed_matvec_tpu.models.lattices import square_edges
from distributed_matvec_tpu.models.yaml_io import load_config_from_yaml
from distributed_matvec_tpu.obs.events import events
from distributed_matvec_tpu.parallel import engine
from distributed_matvec_tpu.parallel.engine import (
    LocalEngine, gather_table_ranges, staircase_levels)

from dense_ref import fermion_site_operator_matrix
# the table cut steered on through the rules' VMEM number, as there
from test_chain_28_config import table_outside_vmem  # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA_YAML = os.path.join(ROOT, "data", "hubbard_square_4x4.yaml")
BENCH_YAML = os.path.join(ROOT, "benchmark", "configs", "hubbard_4x4.yaml")
BENCH_JSON = os.path.join(ROOT, "benchmark", "configs", "hubbard_4x4.json")
ATOL, RTOL = 1e-14, 1e-12
CHUNK = 4096                    # several chunks a toy basis


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "hubbard_reference", os.path.join(
            ROOT, "benchmark", "references", "hubbard.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def hubbard_yaml(path, nx, ny, n_up, n_down, U=4.0):
    """The ``nx`` x ``ny`` torus in the configuration's own layout: up
    modes 0 .. L-1, down modes L .. 2L-1, the bonds of ``square_edges``
    once a species, the repulsion on ``[i, L + i]``."""
    L = nx * ny
    bonds = square_edges(nx, ny)
    hops = [[i, j] for i, j in bonds] + [[L + i, L + j] for i, j in bonds]
    onsite = [[i, L + i] for i in range(L)]
    with open(path, "w", encoding="utf-8") as f:
        f.write(f"basis: {{particle: spinful_fermion, number_sites: {L}, "
                f"number_up: {n_up}, number_down: {n_down}}}\n"
                "hamiltonian:\n  terms:\n"
                f"    - {{expression: \"-1.0 (c†₀ c₁ + c†₁ c₀)\", "
                f"sites: {hops}}}\n"
                f"    - {{expression: \"{U} n₀ n₁\", sites: {onsite}}}\n")
    return str(path)


@pytest.fixture(scope="module")
def cluster(tmp_path_factory, reference):
    """``cluster(nx, ny, n_up, n_down)``: (operator with its basis built,
    the reference's spec and states), once a module."""
    made = {}

    def get(*key):
        if key not in made:
            path = hubbard_yaml(tmp_path_factory.mktemp("hubbard")
                                / "h.yaml", *key)
            cfg = load_config_from_yaml(path, hamiltonian=True)
            cfg.basis.build()
            spec = reference.Spec(path)
            made[key] = (cfg.hamiltonian, spec,
                         reference.enumerate_representatives(spec))
        return made[key]
    return get


def _unit(n, seed):
    x = np.random.default_rng(seed).standard_normal(n)
    return x / np.linalg.norm(x)


# ---------------------------------------------------------------------------
# The reference against the program's basis and against dense matrices
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("key", [(4, 4, 1, 1), (4, 4, 2, 2), (2, 3, 3, 3),
                                 (3, 4, 6, 6)])
def test_reference_enumerates_the_program_basis(key, cluster):
    op, spec, states = cluster(*key)
    nx, ny, n_up, n_down = key
    L = nx * ny
    assert states.size == comb(L, n_up) * comb(L, n_down) \
        == spec.number_states
    np.testing.assert_array_equal(op.basis.representatives, states)


def _dense_hubbard(spec, states):
    """H on ``states`` from Kronecker products of the 2 x 2 mode matrices
    (``c_k`` carries a Z on every mode below ``k``), no shared algebra."""
    nb = 2 * spec.n
    h = sp.csr_matrix((1 << nb, 1 << nb), dtype=np.complex128)
    for i, j in spec.bonds:
        hop = (fermion_site_operator_matrix(nb, "c+", i)
               @ fermion_site_operator_matrix(nb, "c", j))
        h = h + spec.c * (hop + hop.getH())
    for i in spec.sites:
        h = h + spec.U * (fermion_site_operator_matrix(nb, "n", i)
                          @ fermion_site_operator_matrix(nb, "n", spec.n + i))
    idx = states.astype(np.int64)
    return h[idx][:, idx].toarray()


@pytest.mark.parametrize("key", [(2, 3, 2, 1), (2, 3, 3, 3), (3, 3, 2, 2)])
def test_reference_rows_match_dense_jordan_wigner(key, cluster, reference):
    """Every row of the reference's apply against the dense matrix; the
    2x3 torus lists its wrap bonds twice (two bonds of one pair add up)."""
    _, spec, states = cluster(*key)
    h = _dense_hubbard(spec, states)
    assert np.abs(h.imag).max() == 0 and np.allclose(h, h.T)
    x = _unit(states.size, 3)
    rows = np.arange(states.size)
    np.testing.assert_allclose(
        reference.apply_rows(spec, states, x, rows), h.real @ x,
        atol=ATOL, rtol=RTOL)
    np.testing.assert_allclose(
        reference.sparse_matrix(spec, states).toarray(), h.real,
        atol=ATOL)


@pytest.mark.parametrize("key,closed", [((3, 3, 2, 2), 18_144),
                                        ((3, 4, 3, 3), 950_400),
                                        ((4, 4, 2, 2), 215_040)])
def test_count_offdiagonal_is_the_closed_form(key, closed, cluster,
                                              reference):
    """Over every row, on tori with no bond listed twice:
    2 spins x (2 |bonds| C(L-2, N-1)) x C(L, N)."""
    nx, ny, n, _ = key
    _, spec, states = cluster(*key)
    L = nx * ny
    assert closed == 2 * (2 * len(square_edges(nx, ny))
                          * comb(L - 2, n - 1)) * comb(L, n)
    assert reference.closed_form_offdiagonal(spec) == closed
    assert reference.count_offdiagonal(
        spec, states, np.arange(states.size)) == closed


def test_reference_refuses_what_it_does_not_cover(tmp_path, reference):
    hop = '"-1.0 (c†₀ c₁ + c†₁ c₀)"'
    basis = ("basis: {particle: spinful_fermion, number_sites: 2, "
             "number_up: 1, number_down: 1}\n")

    def doc(basis=basis, hops="[[0, 1], [2, 3]]", onsite="[[0, 2], [1, 3]]",
            other="n₀ n₁"):
        return (basis + "hamiltonian:\n  terms:\n"
                f"    - {{expression: {hop}, sites: {hops}}}\n"
                f"    - {{expression: \"4.0 {other}\", sites: {onsite}}}\n")
    good = tmp_path / "good.yaml"
    good.write_text(doc(), encoding="utf-8")
    assert reference.Spec(str(good)).number_states == 4
    cases = {
        "spins": doc(basis="basis: {number_spins: 4, hamming_weight: 2}\n"),
        "no number": doc(basis=basis.replace(", number_down: 1", "")),
        "a hop between species": doc(hops="[[0, 3], [2, 3]]"),
        "repulsion off site": doc(onsite="[[0, 3], [1, 3]]"),
        "another term": doc(other="c†₀ c₁"),
    }
    for what, text in cases.items():
        p = tmp_path / "bad.yaml"
        p.write_text(text, encoding="utf-8")
        with pytest.raises(NotImplementedError):
            reference.Spec(str(p))


# ---------------------------------------------------------------------------
# The program's apply against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("how", ["one_pass", "table_cut"])
def test_local_engine_matches_the_reference(how, cluster, reference,
                                            table_outside_vmem):
    op, spec, states = cluster(4, 4, 2, 2)
    if how == "table_cut":
        R, _ = table_outside_vmem(engine.pad_to_multiple(states.size, CHUNK))
    eng = LocalEngine(op, batch_size=CHUNK)
    counts = eng._ell_counts
    assert counts["table_ranges"] == (R if how == "table_cut" else 1)
    assert counts["live_entries"] == reference.closed_form_offdiagonal(spec)
    x = _unit(states.size, 7)
    np.testing.assert_allclose(
        np.asarray(eng.matvec(x)),
        reference.apply_rows(spec, states, x, np.arange(states.size)),
        atol=ATOL, rtol=RTOL)


def test_distributed_engine_matches_the_reference(cluster, reference):
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine

    op, spec, states = cluster(4, 4, 2, 2)
    eng = DistributedEngine(op, n_devices=4)
    x = _unit(states.size, 11)
    np.testing.assert_allclose(
        eng.matvec_global(x),
        reference.apply_rows(spec, states, x, np.arange(states.size)),
        atol=ATOL, rtol=RTOL)
    inits = [e for e in events() if e.get("kind") == "engine_init"
             and e.get("engine") == "distributed"]
    assert inits[-1]["particle"] == "spinful_fermion"
    assert inits[-1]["string_terms"] == 40


def test_the_check_sees_the_signs(cluster, reference):
    """A control: the reference with its Jordan-Wigner strings dropped is
    another matrix, and the apply check's numbers say so by far."""
    op, spec, states = cluster(4, 4, 2, 2)
    eng = LocalEngine(op, batch_size=CHUNK)
    x = _unit(states.size, 13)
    rows = np.sort(np.random.default_rng(5).choice(states.size, 4096,
                                                   replace=False))
    got = np.asarray(eng.matvec(x))[rows]

    def over_tol(want):
        return float(np.max(np.abs(got - want) / (ATOL + RTOL * np.abs(want))))
    assert over_tol(reference.apply_rows(spec, states, x, rows)) <= 1.0
    assert over_tol(reference.apply_rows(spec, states, x, rows,
                                         signs=False)) > 1e6


def test_ground_energy_of_the_reference_and_lanczos(cluster, reference):
    """4x4 at 2 up 2 down (14,400 states): ARPACK on the reference's own
    matrix and the program's Lanczos on ``LocalEngine``."""
    from distributed_matvec_tpu.solve.lanczos import lanczos

    op, spec, states = cluster(4, 4, 2, 2)
    e_ref = reference.ground_energy(spec)
    eng = LocalEngine(op)
    res = lanczos(eng.matvec, states.size, k=1, tol=1e-12, max_iters=300)
    assert abs(res.eigenvalues[0] - e_ref) <= 1e-10


# ---------------------------------------------------------------------------
# What the build says it ran
# ---------------------------------------------------------------------------

def test_build_span_and_event_carry_particle_and_string_terms(cluster):
    """40 of the 4x4 torus's 64 hops carry a Jordan-Wigner string: each
    species' 16 vertical bonds (3 modes between, 11 on the wrap) and 4
    horizontal wrap bonds (2 modes between); the other 24 join modes
    adjacent in bit order."""
    op, _, _ = cluster(4, 4, 2, 2)
    assert op.string_terms == 40 and op.number_off_diag_terms == 64
    LocalEngine(op, batch_size=CHUNK)
    ring = events()
    builds = [e for e in ring if e.get("kind") == "span"
              and e.get("name") == "engine_init/build_structure"]
    init = [e for e in ring if e.get("kind") == "engine_init"][-1]
    for payload in (builds[-1], init):
        assert payload["particle"] == "spinful_fermion"
        assert payload["string_terms"] == 40
        assert payload["live_entries"] == 215_040 and payload["terms"] == 64
    op.basis.build(force=True)
    product = [e for e in events() if e.get("kind") == "span"
               and e.get("name") == "enumeration/product"][-1]
    assert (product["up_states"], product["down_states"]) == (120, 120)


def test_a_spin_model_has_no_string_terms(tmp_path):
    path = tmp_path / "ring.yaml"
    bonds = [[i, (i + 1) % 8] for i in range(8)]
    path.write_text("basis: {number_spins: 8, hamming_weight: 4}\n"
                    "hamiltonian:\n  terms:\n" + "".join(
                        f"    - {{expression: \"σ{a}₀ σ{a}₁\", "
                        f"sites: {bonds}}}\n" for a in "ˣʸᶻ"),
                    encoding="utf-8")
    cfg = load_config_from_yaml(str(path))
    cfg.basis.build()
    assert cfg.hamiltonian.string_terms == 0
    LocalEngine(cfg.hamiltonian)
    init = [e for e in events() if e.get("kind") == "engine_init"][-1]
    assert (init["particle"], init["string_terms"]) == ("spin", 0)


# ---------------------------------------------------------------------------
# The table cut of a product basis, by its index arithmetic
# ---------------------------------------------------------------------------

def _sector(bits, weight):
    words = np.arange(1 << bits, dtype=np.int64)
    return words[np.bitwise_count(words) == weight]


def product_range_histograms(L, n_up, n_down, bonds, n_padded, W, T):
    """Rows of each table range by their near and by their far entries,
    from the product basis's index arithmetic alone: row = down rank x
    C(L, n_up) + up rank, so an up hop keeps its row's block of C(L, n_up)
    rows and a down hop moves by whole blocks; an entry is near where its
    column lies in its row's range.  Padded rows are empty."""
    up, down = _sector(L, n_up), _sector(L, n_down)
    rank_up = np.full(1 << L, -1, np.int64)
    rank_up[up] = np.arange(up.size)
    rank_down = np.full(1 << L, -1, np.int64)
    rank_down[down] = np.arange(down.size)
    rows = np.arange(up.size * down.size)
    d, u = np.divmod(rows, up.size)
    near = np.zeros(rows.size, np.int64)
    far = np.zeros(rows.size, np.int64)
    for i, j in bonds:
        flip = (1 << i) | (1 << j)
        for word, col_of in (
                (up[u], lambda w: d * up.size + rank_up[w]),
                (down[d], lambda w: rank_down[w] * up.size + u)):
            one = (((word >> i) ^ (word >> j)) & 1).astype(bool)
            is_near = one & (col_of(word ^ flip) // W == rows // W)
            near += is_near
            far += one & ~is_near
    R = -(-n_padded // W)
    hist = np.zeros((2, R, T + 1), np.int64)
    for k, counts in enumerate((near, far)):
        np.add.at(hist[k], (rows // W, counts), 1)
        np.add.at(hist[k], (np.arange(rows.size, n_padded) // W, 0), 1)
    return hist


def test_index_arithmetic_gives_the_engines_near_and_far_slots(
        cluster, table_outside_vmem):
    """The count behind the full size's near share, held to the engine's
    own ``near_slots`` and ``far_slots`` at a size the CPU builds: the
    staircases of the arithmetic's histograms are the engine's slots."""
    op, _, states = cluster(4, 4, 2, 2)
    n_padded = engine.pad_to_multiple(states.size, CHUNK)
    R, W = table_outside_vmem(n_padded)
    counts = LocalEngine(op, batch_size=CHUNK)._ell_counts
    hist = product_range_histograms(16, 2, 2, square_edges(4, 4),
                                    n_padded, W, 64)
    entries, slots = [0, 0], [0, 0]
    for k in range(2):
        for r in range(R):
            entries[k] += int(np.dot(np.arange(65), hist[k, r]))
            _, levels = staircase_levels(hist[k, r], int(hist[k, r].sum()))
            slots[k] += sum(cols * rows for _, cols, rows in levels)
    assert sum(entries) == counts["live_entries"] == 215_040
    assert 0 < entries[1] < entries[0]
    assert slots == [counts["near_slots"], counts["far_slots"]]


# ---------------------------------------------------------------------------
# The full size, no build
# ---------------------------------------------------------------------------

def test_the_full_size_configuration(reference):
    """The user's YAML and the benchmark's copy state one Hamiltonian, the
    configuration's sizes are the closed form's, and the rules cut its
    gather table into six ranges."""
    spec = reference.Spec(BENCH_YAML)
    assert spec.digest == reference.Spec(DATA_YAML).digest
    with open(BENCH_JSON) as f:
        config = json.load(f)
    assert (spec.n, spec.n_up, spec.n_down, spec.c, spec.U) == (
        16, 5, 5, -1.0, 4.0)
    assert sorted(map(tuple, spec.bonds)) == sorted(
        [(i, j) for i, j in square_edges(4, 4)]
        + [(16 + i, 16 + j) for i, j in square_edges(4, 4)])
    assert config["number_states"] == spec.number_states == 4368 ** 2 \
        == 19_079_424
    assert config["offdiag_nonzeros"] \
        == reference.closed_form_offdiagonal(spec) == 559_663_104
    cfg = load_config_from_yaml(DATA_YAML)
    assert cfg.hamiltonian.string_terms == 40
    n_padded = engine.pad_to_multiple(spec.number_states, 65_536)
    assert 16 * n_padded > engine.GATHER_VMEM_BYTES
    assert gather_table_ranges(n_padded, 3) == (6, 3_189_760)
    with pytest.raises(NotImplementedError):
        reference.ground_energy(spec)


def test_product_lookup_is_the_search_of_the_sorted_basis(cluster):
    """The rank tables a spinful-fermion basis's build looks states up in
    give the bucketed search's index wherever a state is in the basis,
    and find nothing where it is not: a part of another weight, a high
    part past the table, the padding's sentinel."""
    import jax.numpy as jnp

    from distributed_matvec_tpu.ops.bits import (
        build_sorted_lookup, state_index_bucketed)
    from distributed_matvec_tpu.parallel.engine import (
        SENTINEL_STATE, basis_lookup)

    op, _, states = cluster(3, 4, 3, 3)
    basis = op.basis
    low_rank, high_offset, shift, probes = basis_lookup(basis)
    assert (shift, probes) == (12, 0) and low_rank.size == 1 << 12
    sorted_lookup = build_sorted_lookup(states, basis.number_bits)
    rng = np.random.default_rng(3)
    queries = np.concatenate([
        states[rng.choice(states.size, 5000)],
        rng.integers(0, 1 << 24, 5000).astype(np.uint64),
        np.array([SENTINEL_STATE, 1 << 40, states[-1] + 1], np.uint64)])
    want_idx, want_found = state_index_bucketed(
        *map(jnp.asarray, sorted_lookup[:2]), jnp.asarray(queries),
        shift=sorted_lookup[2], probes=sorted_lookup[3])
    idx, found = state_index_bucketed(
        jnp.asarray(low_rank), jnp.asarray(high_offset),
        jnp.asarray(queries), shift=shift, probes=probes)
    found = np.asarray(found)
    np.testing.assert_array_equal(found, np.asarray(want_found))
    assert found[:5000].all() and not found[-3:].any()
    np.testing.assert_array_equal(np.asarray(idx)[found],
                                  np.asarray(want_idx)[found])


def test_the_app_solves_the_torus(tmp_path, cluster, reference):
    """``apps/diagonalize.py`` on the configuration's layout at 4x4 with 2
    up 2 down, as a user runs the YAML: its E0 is the reference's."""
    import sys

    import h5py

    _, spec, _ = cluster(4, 4, 2, 2)
    path = hubbard_yaml(tmp_path / "h.yaml", 4, 4, 2, 2)
    sys.path.insert(0, os.path.join(ROOT, "apps"))
    import diagonalize

    out = tmp_path / "h.h5"
    assert diagonalize.main([path, "-o", str(out), "-k", "1",
                             "--tol", "1e-10"]) == 0
    with h5py.File(out, "r") as f:
        e0 = float(f["hamiltonian/eigenvalues"][0])
    assert abs(e0 - reference.ground_energy(spec)) <= 1e-9
