"""The chain without symmetries at the size where the vector leaves VMEM
(upstream's ``data/heisenberg_chain_28.yaml``, the benchmark's ``chain_28``:
40,116,600 states, a 613 MiB gather table, the two-pass structure build) on
the normal path, at rings of 16 and 18 sites pushed into the same two
branches — the low-memory build forced through ``ell_build_budget_gb`` and
``GATHER_VMEM_BYTES`` patched below the table's bytes — against the
benchmark's plain reference (``benchmark/references/lattice_heisenberg.py``,
which imports nothing of the program); and the full size's numbers that
need no build: the staircase of its closed-form histogram, the size rule's
verdict, and the YAML in ``data/``.
"""

import gc
import importlib.util
import os
from math import comb

import jax
import numpy as np
import pytest

from distributed_matvec_tpu import obs
from distributed_matvec_tpu.models.yaml_io import load_config_from_yaml
from distributed_matvec_tpu.parallel import engine
from distributed_matvec_tpu.parallel.engine import (
    LocalEngine, block_pieces, gather_row_blocks, staircase_levels)
from distributed_matvec_tpu.utils.config import get_config, update_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
YAML_28 = os.path.join(ROOT, "data", "heisenberg_chain_28.yaml")
RINGS = {"ring16": 16, "ring18": 18}
CHUNK = 4096                    # several chunks a toy basis, as 613 at 28

N_28, N_PAD_28 = 40_116_600, 613 * 65_536
LEVELS_28 = ((0, 4, 40_117_248), (4, 2, 40_115_200), (6, 2, 40_057_856),
             (8, 2, 39_485_440), (10, 2, 36_622_336), (12, 2, 28_893_184),
             (14, 2, 17_114_112), (16, 2, 6_807_552), (18, 2, 1_654_784),
             (20, 2, 223_232), (22, 2, 15_360), (24, 4, 1_024))


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "lattice_heisenberg", os.path.join(
            ROOT, "benchmark", "references", "lattice_heisenberg.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def ring_yaml(path, n):
    """The periodic ``n``-site chain at half filling with no group, in
    upstream's schema, as ``data/heisenberg_chain_28.yaml`` states it."""
    bonds = [[i, (i + 1) % n] for i in range(n)]
    lines = [f"basis:\n  number_spins: {n}\n  hamming_weight: {n // 2}\n",
             "hamiltonian:\n  name: Heisenberg\n  terms:\n"]
    for axis in "ˣʸᶻ":
        lines.append(f"    - {{expression: \"σ{axis}₀ σ{axis}₁\", "
                     f"sites: {bonds}}}\n")
    with open(path, "w", encoding="utf-8") as f:
        f.writelines(lines)
    return str(path)


@pytest.fixture(scope="module")
def ring(tmp_path_factory, reference):
    """``ring(name)``: (operator with its basis built, the reference's spec
    and states) of one of :data:`RINGS`, once a module."""
    made = {}

    def get(name):
        if name not in made:
            path = ring_yaml(
                tmp_path_factory.mktemp("ring") / f"{name}.yaml", RINGS[name])
            cfg = load_config_from_yaml(path, hamiltonian=True)
            cfg.basis.build()
            spec = reference.LatticeSpec(path)
            made[name] = (cfg.hamiltonian, spec,
                          reference.enumerate_representatives(spec))
        return made[name]
    return get


@pytest.fixture
def two_pass():
    """The size rule sends every build to ``_build_ell_lowmem``."""
    was = get_config().ell_build_budget_gb
    update_config(ell_build_budget_gb=1e-9)
    yield
    update_config(ell_build_budget_gb=was)


@pytest.fixture
def table_outside_vmem(monkeypatch):
    """``table_outside_vmem(n_padded)``: the rule's VMEM number one row
    short of ``x`` as a gather table, so that it takes its no-room branch
    (steered through the rule's input, not through an option)."""
    def patch(n_padded):
        monkeypatch.setattr(engine, "GATHER_VMEM_BYTES", 16 * n_padded - 16)
        assert gather_row_blocks(n_padded, 3) == (
            1, engine.pad_to_multiple(n_padded, engine.INDEX_TILE))
    return patch


def _build(op, n_padded, table_outside_vmem):
    table_outside_vmem(n_padded)
    return LocalEngine(op, batch_size=CHUNK)


@pytest.mark.parametrize("name", list(RINGS))
def test_two_pass_build_with_the_table_in_hbm_matches_the_reference(
        name, ring, reference, two_pass, table_outside_vmem):
    """Every row of one apply at the configuration's own contract, through
    the two branches ``chain_28`` takes on the chip."""
    op, spec, states = ring(name)
    np.testing.assert_array_equal(op.basis.representatives, states)
    n_padded = engine.pad_to_multiple(states.size, CHUNK)
    eng = _build(op, n_padded, table_outside_vmem)
    counts = eng._ell_counts
    assert eng.mode == "ell" and eng.num_chunks > 3
    assert (counts["build_passes"], counts["row_blocks"]) == (2, 1)
    assert counts["table_bytes"] == 16 * n_padded > engine.GATHER_VMEM_BYTES
    assert counts["levels"] > 1 and eng._ell_pos_of is not None
    assert counts["gather_pieces"] == counts["levels"] + 1
    x = np.random.default_rng(32).standard_normal(states.size)
    x /= np.linalg.norm(x)
    want = reference.apply_rows(spec, states, x, np.arange(states.size))
    np.testing.assert_allclose(np.asarray(eng.matvec(x)), want,
                               atol=1e-14, rtol=1e-12)


@pytest.mark.parametrize("name", list(RINGS))
def test_two_pass_build_makes_the_one_pass_builds_arrays(
        name, ring, table_outside_vmem):
    op, _, states = ring(name)
    n_padded = engine.pad_to_multiple(states.size, CHUNK)
    one = _build(op, n_padded, table_outside_vmem)
    was = get_config().ell_build_budget_gb
    update_config(ell_build_budget_gb=1e-9)
    try:
        two = _build(op, n_padded, table_outside_vmem)
    finally:
        update_config(ell_build_budget_gb=was)
    assert two._ell_counts == {**one._ell_counts, "build_passes": 2}
    assert len(two._ell_blocks) == len(one._ell_blocks) == 1
    assert len(two._ell_levels) == len(one._ell_levels)
    for (i2, c2), (i1, c1) in zip(two._ell_levels, one._ell_levels):
        np.testing.assert_array_equal(np.asarray(i2), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(c2), np.asarray(c1))
    np.testing.assert_array_equal(np.asarray(two._ell_pos_of),
                                  np.asarray(one._ell_pos_of))


def _table_bytes():
    """Bytes of the live arrays shaped like a level's table: columns by at
    least a tile of rows (buffers and pieces; not the lookup's ``[N, 2]``)."""
    return sum(a.nbytes for a in jax.live_arrays()
               if a.ndim >= 2 and a.shape[1] >= engine.INDEX_TILE)


def test_two_pass_build_holds_its_levels_once(ring, two_pass, monkeypatch):
    """While the pieces are cut the build holds at most one table of one
    level twice (a buffer goes before the next one's pieces are made), and
    when it ends the tables alive are the engine's levels, once: not
    beside the chunk-padded buffers they were packed into."""
    op, _, _ = ring("ring18")
    gc.collect()
    assert _table_bytes() == 0
    seen = []
    wait = jax.block_until_ready

    def watching(tree):
        out = wait(tree)
        seen.append(_table_bytes())
        return out

    monkeypatch.setattr(jax, "block_until_ready", watching)
    eng = LocalEngine(op, batch_size=CHUNK)
    monkeypatch.undo()
    assert eng._ell_counts["build_passes"] == 2
    gc.collect()
    levels = sum(i.nbytes + c.nbytes for i, c in eng._ell_levels)
    assert levels == eng.ell_nbytes - eng._ell_pos_of.nbytes
    assert _table_bytes() == levels
    # the pack's wait sees the chunk-padded buffers alone; ``ell/cut`` then
    # waits once for each table that was packed into a longer buffer (a
    # level a whole number of chunks long is its buffer)
    long = [t for p in eng._ell_levels for t in p if t.shape[1] % CHUNK]
    assert len(long) >= eng._ell_counts["levels"]
    packed, cuts = seen[-len(long) - 1], seen[-len(long):]
    widest = max(t.nbytes for t in long)
    assert levels < packed and 2 * widest < levels
    # cut all at once, as the build's last statement did before it was
    # repaired, the peak was ``packed + levels``
    assert max(cuts) <= packed + widest
    k, rows = long[-1].shape
    assert cuts[-1] == levels + 8 * k * engine.pad_to_multiple(rows, CHUNK)


def _histogram_28():
    """Rows of ``chain_28`` by width, in closed form: a state with ``2m``
    domain walls has ``2m`` off-diagonal entries, and the ring of 28 at
    weight 14 has ``(28/m) C(13, m-1)^2`` of them."""
    hist = np.zeros(29, np.int64)
    for m in range(1, 15):
        hist[2 * m] = 28 * comb(13, m - 1) ** 2 // m
    assert hist.sum() == N_28 == comb(28, 14)
    hist[0] = N_PAD_28 - N_28               # the padded rows
    return hist


def test_staircase_of_the_chain_28_histogram():
    """``staircase_levels`` on the closed-form histogram, no build: 12
    levels, 582,451,200 table slots and 40,173,568 un-permute rows for
    582,433,600 non-zeros (fill 93.545%), one row block of whole levels
    (the 613 MiB table cannot fit VMEM), 13 gathers an apply."""
    hist = _histogram_28()
    live = int(np.dot(np.arange(29), hist))
    assert live == 2 * 28 * comb(26, 13) == 582_433_600
    stair, levels = staircase_levels(hist, N_PAD_28)
    assert stair and levels == LEVELS_28
    slots = sum(k * L for _, k, L in levels)
    assert slots == 582_451_200 and slots + N_PAD_28 == 622_624_768
    assert 100.0 * live / (slots + N_PAD_28) == pytest.approx(93.545,
                                                              abs=0.001)
    assert 16 * N_PAD_28 == 642_777_088 > engine.GATHER_VMEM_BYTES
    nb, B = gather_row_blocks(N_PAD_28, 3)
    assert (nb, B) == (1, N_PAD_28)
    plan = block_pieces(levels, B)
    assert len(plan) == 1 and len(plan[0]) + nb == 13
    # 12 B a slot on the device: indices 2.33 GB, coefficients 4.66 GB
    assert 12 * slots == 6_989_414_400


def test_the_size_rule_sends_chain_28_to_the_two_pass_build():
    """1.6 x the full-width build tables (28 terms x 12 B a padded row)
    against ``ell_build_budget_gb``: 21.6 GB over 12, where the benchmark's
    other two bases stay under it (2.9 and 5.0 GB)."""
    budget = get_config().ell_build_budget_gb * 1e9
    full = {"chain_28": N_PAD_28 * 28 * 12,
            "chain_32_symm": 4_718_592 * 32 * 12,
            "square_5x5": 5_242_880 * 50 * 12}
    assert full["chain_28"] == 13_498_318_848
    assert {k: 1.6 * v > budget for k, v in full.items()} == {
        "chain_28": True, "chain_32_symm": False, "square_5x5": False}


def test_the_chain_28_yaml_describes_upstreams_sector():
    """``data/heisenberg_chain_28.yaml`` through the schema loader, no
    build: 28 spins, weight 14 (40,116,600 states), no group, the 28
    distinct bonds of the ring; the benchmark's copy is a copy."""
    import yaml

    cfg = load_config_from_yaml(YAML_28, hamiltonian=True)
    basis = cfg.basis
    assert not basis.is_built
    assert (basis.number_spins, basis.hamming_weight) == (28, 14)
    assert basis.spin_inversion is None and not basis.requires_projection
    assert comb(basis.number_spins, basis.hamming_weight) == N_28
    assert cfg.hamiltonian.number_off_diag_terms == 28
    with open(YAML_28, encoding="utf-8") as f:
        text = f.read()
    for term in yaml.safe_load(text)["hamiltonian"]["terms"]:
        bonds = {tuple(sorted(b)) for b in term["sites"]}
        assert bonds == {tuple(sorted((i, (i + 1) % 28)))
                         for i in range(28)} and len(term["sites"]) == 28
    with open(os.path.join(ROOT, "benchmark", "configs", "chain_28.yaml"),
              encoding="utf-8") as g:
        assert text == g.read()


def test_the_two_pass_builds_spans(ring, two_pass):
    """``ell/count_rows``, ``ell/row_order``, ``ell/pack``, ``ell/cut``
    under the build span, each with the ``device_wait`` where the host
    blocks, and ``build_passes`` / ``table_bytes`` on the span and in the
    ``engine_init`` event."""
    op, _, _ = ring("ring16")
    obs.reset_all()
    eng = LocalEngine(op, batch_size=CHUNK)
    spans = obs.events("span")
    (build,) = [e for e in spans
                if e["name"] == "engine_init/build_structure"]
    passes = [e for e in spans if e["parent_span_id"] == build["span_id"]]
    assert [e["name"] for e in passes] == [
        "ell/count_rows", "ell/row_order", "ell/pack", "ell/cut"]
    assert all(e["cat"] == "phase" for e in passes)
    cut = sum(t.shape[1] % CHUNK > 0 for p in eng._ell_levels for t in p)
    assert cut >= 2 * (eng._ell_counts["levels"] - 1)
    for e, at, n in zip(passes, ["ell_count_rows", "ell_row_order",
                                 "ell_pack", "ell_cut"], [1, 1, 1, cut]):
        waits = [w for w in spans if w["name"] == "device_wait"
                 and w["parent_span_id"] == e["span_id"]]
        assert [w["at"] for w in waits] == [at] * n
        assert sum(w["dur_ms"] for w in waits) <= e["dur_ms"]
    assert sum(e["dur_ms"] for e in passes) <= build["dur_ms"]
    assert (build["build_passes"], build["table_bytes"]) == \
        (2, 16 * eng.n_padded)
    init = obs.events("engine_init")[-1]
    assert (init["build_passes"], init["table_bytes"]) == \
        (2, 16 * eng.n_padded)
    obs.reset_all()
