"""The chain without symmetries at the size where the vector leaves VMEM
(upstream's ``data/heisenberg_chain_28.yaml``, the benchmark's ``chain_28``:
40,116,600 states, a 613 MiB gather table, cut into 12 ranges since PR 33)
on the normal path, at rings of 16 and 18 sites pushed into the same
branch — ``GATHER_VMEM_BYTES`` patched below the table's bytes, so that the
table is cut and the structure built a range at a time — against the
benchmark's plain reference (``benchmark/references/lattice_heisenberg.py``,
which imports nothing of the program); the two-pass build below that line;
and the full size's numbers that need no build: the staircase of its
closed-form histogram and of its ranges' counted ones, the size rules'
verdicts, and the YAML in ``data/``.
"""

import gc
import importlib.util
import json
import os
from math import comb

import jax
import numpy as np
import pytest

from distributed_matvec_tpu import obs
from distributed_matvec_tpu.models.yaml_io import load_config_from_yaml
from distributed_matvec_tpu.parallel import engine
from distributed_matvec_tpu.parallel.engine import (
    LocalEngine, block_pieces, gather_row_blocks, gather_table_ranges,
    staircase_levels, widest_pieces)
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
    """``table_outside_vmem(n_padded)``: the rules' VMEM number one row
    short of ``x`` as a gather table, so that the row-block rule has no
    room and the table is cut (steered through the rules' input, not
    through an option).  Returns the ranges: 36 B a row of a range against
    16 B a row of the table, so three."""
    def patch(n_padded):
        monkeypatch.setattr(engine, "GATHER_VMEM_BYTES", 16 * n_padded - 16)
        assert gather_row_blocks(n_padded, 3) == (
            1, engine.pad_to_multiple(n_padded, engine.INDEX_TILE))
        R, W = gather_table_ranges(n_padded, 3)
        assert R == 3 and (R - 1) * W < n_padded <= R * W
        assert 36 * W <= engine.GATHER_VMEM_BYTES and W % 1024 == 0
        return R, W
    return patch


def _stored_entries(eng, W):
    """``(row, column, value, near)`` of every live entry the cut
    structure stores, read back from its arrays alone: range ``r``'s
    staircases at ``_ell_blocks[2 r]`` (near: range-local columns) and
    ``[2 r + 1]`` (far: global ones), a piece's position ``p`` the row
    whose ``pos_of`` is ``p``."""
    out = []
    for j, (blk, pos) in enumerate(zip(eng._ell_blocks, eng._ell_pos_of)):
        r, far = divmod(j, 2)
        row_of = np.argsort(np.asarray(pos)) + r * W
        for idx, cf in blk:
            idx, cf = np.asarray(idx), np.asarray(cf)
            t, p = np.nonzero(cf)
            out.append((row_of[p], idx[t, p] + (0 if far else r * W),
                        cf[t, p], np.full(t.size, not far)))
    return [np.concatenate(c) for c in zip(*out)]


@pytest.mark.parametrize("name", list(RINGS))
def test_table_cut_build_matches_the_reference(name, ring, reference,
                                               table_outside_vmem):
    """Every row of one apply at the configuration's own contract, through
    the branch ``chain_28`` takes on the chip: the table cut into ranges,
    one pass of the kernels a range, near entries gathered from the range
    and far ones from whole ``x``; the counts that say so; and the stored
    entries against the reference's own matrix, each once, near exactly
    where its column lies in its row's range."""
    op, spec, states = ring(name)
    np.testing.assert_array_equal(op.basis.representatives, states)
    n_padded = engine.pad_to_multiple(states.size, CHUNK)
    R, W = table_outside_vmem(n_padded)
    eng = LocalEngine(op, batch_size=CHUNK)
    counts = eng._ell_counts
    assert eng.mode == "ell" and eng.num_chunks > 3
    assert (counts["build_passes"], counts["row_blocks"],
            counts["table_ranges"]) == (1, R, R)
    assert counts["table_bytes"] == 16 * n_padded > engine.GATHER_VMEM_BYTES
    assert len(eng._ell_blocks) == len(eng._ell_pos_of) == 2 * R
    assert all(p is not None for p in eng._ell_pos_of)
    assert counts["gather_pieces"] == counts["levels"] + 2 * R
    slots = [sum(i.size for i, _ in blk) for blk in eng._ell_blocks]
    assert (sum(slots[0::2]), sum(slots[1::2])) == \
        (counts["near_slots"], counts["far_slots"])
    assert counts["gather_slots"] == sum(slots) + 2 * n_padded
    rows, cols, vals, near = _stored_entries(eng, W)
    want = reference.sparse_matrix(spec, states).tocoo()
    off = want.row != want.col
    assert counts["live_entries"] == rows.size == int(off.sum()) \
        == reference.count_offdiagonal(spec, states,
                                       np.arange(states.size))
    np.testing.assert_array_equal(near, rows // W == cols // W)
    assert 0 < int(near.sum()) < rows.size          # both kinds occur
    got, ref = np.lexsort((cols, rows)), np.lexsort((want.col[off],
                                                     want.row[off]))
    np.testing.assert_array_equal(rows[got], want.row[off][ref])
    np.testing.assert_array_equal(cols[got], want.col[off][ref])
    np.testing.assert_array_equal(vals[got], want.data[off][ref])
    x = np.random.default_rng(32).standard_normal(states.size)
    x /= np.linalg.norm(x)
    np.testing.assert_allclose(
        np.asarray(eng.matvec(x)),
        reference.apply_rows(spec, states, x, np.arange(states.size)),
        atol=1e-14, rtol=1e-12)


@pytest.mark.parametrize("name", list(RINGS))
def test_table_cut_build_is_one_build_whatever_the_budget(
        name, ring, table_outside_vmem):
    """Above the line ``ell_build_budget_gb`` decides nothing: the build
    that would have gone one-pass and the one that would have gone
    two-pass are the same pass a range, the same arrays piece for piece."""
    op, _, states = ring(name)
    n_padded = engine.pad_to_multiple(states.size, CHUNK)
    table_outside_vmem(n_padded)
    one = LocalEngine(op, batch_size=CHUNK)
    was = get_config().ell_build_budget_gb
    update_config(ell_build_budget_gb=1e-9)
    try:
        two = LocalEngine(op, batch_size=CHUNK)
    finally:
        update_config(ell_build_budget_gb=was)
    assert two._ell_counts == one._ell_counts
    assert one._ell_counts["build_passes"] == 1
    assert [len(b) for b in two._ell_blocks] == \
        [len(b) for b in one._ell_blocks]
    for (i2, c2), (i1, c1) in zip(two._ell_levels, one._ell_levels):
        np.testing.assert_array_equal(np.asarray(i2), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(c2), np.asarray(c1))
    for p2, p1 in zip(two._ell_pos_of, one._ell_pos_of):
        np.testing.assert_array_equal(np.asarray(p2), np.asarray(p1))


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
    """``staircase_levels`` on the closed-form histogram, no build: the 12
    whole levels the basis had before its gather table was cut (582,451,200
    table slots for 582,433,600 non-zeros), which the row-block rule still
    leaves whole (the 613 MiB table cannot fit VMEM) and the table rule
    now cuts into 12 ranges."""
    hist = _histogram_28()
    live = int(np.dot(np.arange(29), hist))
    assert live == 2 * 28 * comb(26, 13) == 582_433_600
    stair, levels = staircase_levels(hist, N_PAD_28)
    assert stair and levels == LEVELS_28
    slots = sum(k * L for _, k, L in levels)
    assert slots == 582_451_200 and slots + N_PAD_28 == 622_624_768
    assert 16 * N_PAD_28 == 642_777_088 > engine.GATHER_VMEM_BYTES
    nb, B = gather_row_blocks(N_PAD_28, 3)
    assert (nb, B) == (1, N_PAD_28)
    assert len(block_pieces(levels, B)) == 1
    # a range is a table, a gather's rows and their indices at once: 36 B
    # a row inside 118 MiB, so 11.7 ranges at the least
    R, W = gather_table_ranges(N_PAD_28, 3)
    assert (R, W) == (12, 3_348_480) and 36 * W <= engine.GATHER_VMEM_BYTES
    assert 36 * engine.pad_to_multiple(-(-N_PAD_28 // 11), 1024) \
        > engine.GATHER_VMEM_BYTES


def test_staircases_of_the_chain_28_ranges():
    """The near and far staircases of ``chain_28``'s 12 ranges, read off
    the histograms counted independently of the engine
    (``tests/data/chain_28_ranges.json``), no build: 84.16% of the
    non-zeros are near; 490.3 M near slots, 92.3 M far ones and two
    un-permute rows a padded row make 663.0 M gathered slots an apply
    (fill 87.85%, where the whole levels' was 93.545: one more row a row
    to put back), in 294 levels and 24 un-permutes."""
    with open(os.path.join(ROOT, "tests", "data",
                           "chain_28_ranges.json")) as f:
        data = json.load(f)
    R, W = gather_table_ranges(N_PAD_28, 3)
    assert (data["n_states"], data["n_padded"], data["ranges"],
            data["range_rows"]) == (N_28, N_PAD_28, R, W)
    entries, slots, levels_n, unpermute = [0, 0], [0, 0], 0, 0
    whole = np.zeros(29, np.int64)
    for r in range(R):
        rows = min(W, N_PAD_28 - r * W)
        for part, kind in enumerate(("near", "far")):
            hist = np.array(data[kind][r], np.int64)
            assert hist.sum() == rows
            entries[part] += int(np.dot(np.arange(29), hist))
            stair, levels = staircase_levels(hist, rows)
            assert stair and levels[0][2] <= W
            slots[part] += sum(k * L for _, k, L in levels)
            levels_n += len(levels)
            unpermute += rows
        # a range's near levels run to 22-23 columns, its far ones to 7-12
        assert 22 <= np.flatnonzero(data["near"][r]).max() <= 23
        assert 7 <= np.flatnonzero(data["far"][r]).max() <= 12
    assert entries == [490_171_298, 92_262_302]
    assert sum(entries) == 582_433_600
    assert round(entries[0] / sum(entries), 4) == 0.8416
    assert slots == [490_305_536, 92_332_032]
    assert (levels_n, unpermute) == (294, 2 * N_PAD_28)
    assert sum(slots) + unpermute == 662_984_704
    assert 100.0 * sum(entries) / 662_984_704 == pytest.approx(87.850,
                                                                abs=0.001)
    # 12 B a slot on the device and two position arrays: 7.31 GB
    assert 12 * sum(slots) + 4 * unpermute == 7_313_039_360


def test_the_size_rule_sends_chain_28_to_the_two_pass_build():
    """1.6 x the full-width build tables (28 terms x 12 B a padded row)
    against ``ell_build_budget_gb``: 21.6 GB over 12, where the benchmark's
    other two bases stay under it (2.9 and 5.0 GB).  (Since PR 33 the
    table rule comes first and ``chain_28`` is built a range at a time;
    the budget rule still has the last word below the VMEM line.)"""
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


def test_the_table_cut_builds_spans(ring, table_outside_vmem):
    """Above the line the build span holds an ``ell/fill`` and an
    ``ell/stair_levels`` pass a range (the chunks' slabs fetched under
    ``device_wait at=ell_fill``), and it and the ``engine_init`` event
    carry the counts that say the cut engaged: ``table_ranges``,
    ``near_slots``, ``far_slots`` beside ``row_blocks``, ``gather_pieces``
    and ``table_bytes``."""
    op, _, states = ring("ring16")
    n_padded = engine.pad_to_multiple(states.size, CHUNK)
    R, W = table_outside_vmem(n_padded)
    obs.reset_all()
    eng = LocalEngine(op, batch_size=CHUNK)
    spans = obs.events("span")
    (build,) = [e for e in spans
                if e["name"] == "engine_init/build_structure"]
    passes = [e for e in spans if e["parent_span_id"] == build["span_id"]]
    assert [e["name"] for e in passes] == \
        ["ell/fill", "ell/stair_levels"] * R
    assert [e["table_range"] for e in passes] == \
        [r for r in range(R) for _ in range(2)]
    assert sum(e["dur_ms"] for e in passes) <= build["dur_ms"]
    fills = {e["span_id"] for e in passes if e["name"] == "ell/fill"}
    waits = [w for w in spans if w["name"] == "device_wait"
             and w["parent_span_id"] in fills]
    assert len(waits) == sum(-(-min(W, n_padded - r * W) // CHUNK)
                             for r in range(R))      # one a chunk's slab
    assert {w["at"] for w in waits} == {"ell_fill"}
    counts = eng._ell_counts
    init = obs.events("engine_init")[-1]
    for event in (build, init):
        assert {k: event[k] for k in counts} == counts
    assert counts["table_ranges"] == R > 1
    assert 0 < counts["far_slots"] < counts["near_slots"]
    share = counts["near_slots"] / (counts["near_slots"]
                                    + counts["far_slots"])
    assert 0.7 < share < 0.95
    obs.reset_all()


FORMS = ("auto", "scan", "unroll")


def _applies_in_every_form(op, xs, **kw):
    """``{form: (the engine_init event's two column counts, one apply of
    each of xs)}``: a ``LocalEngine`` built and its apply traced under each
    value of the ``term_loop`` hook."""
    out = {}
    try:
        for form in FORMS:
            update_config(term_loop=form)
            eng = LocalEngine(op, **kw)
            init = obs.events("engine_init")[-1]
            out[form] = ((init["unrolled_columns"], init["scanned_columns"]),
                         [np.asarray(eng.matvec(x)) for x in xs])
    finally:
        update_config(term_loop="auto")
    return out, eng


@pytest.mark.parametrize("name", list(RINGS))
def test_cut_apply_is_the_same_bits_in_every_form_of_the_term_loop(
        name, ring, table_outside_vmem):
    """Where the table is cut every column is a gather of its own (PR 36:
    the form whose tables the chip's compiler places in VMEM), and the
    ``term_loop`` hook can still ask for the scan: the apply of a real
    vector is the same bits in all three forms (a row's columns are added
    in the same order, column 0 first), and the ``engine_init`` event says
    which form ran.  A two-column batch agrees to the last bit or two: in
    straight-line code XLA's CPU backend contracts a batch's multiply-adds
    where under the scan it does not (as ``test_engine_local.py`` records
    of two unrolled programs).  Where the table is not cut ``auto`` is the
    scan, as since PR 28."""
    op, _, states = ring(name)
    rng = np.random.default_rng(36)
    xs = [rng.standard_normal(states.size),
          rng.standard_normal((states.size, 2))]
    whole, eng = _applies_in_every_form(op, xs, batch_size=CHUNK)
    wide = sum(i.shape[0] for i, _ in eng._ell_levels)
    assert eng._ell_counts["table_ranges"] == 1
    assert [whole[f][0] for f in FORMS] == [(0, wide), (0, wide), (wide, 0)]
    table_outside_vmem(engine.pad_to_multiple(states.size, CHUNK))
    cut, eng = _applies_in_every_form(op, xs, batch_size=CHUNK)
    assert eng._ell_counts["table_ranges"] == 3
    wide = sum(i.shape[0] for i, _ in widest_pieces(eng._ell_blocks, True))
    assert 0 < wide <= 2 * eng._ell_counts["widest_row"]
    assert [cut[f][0] for f in FORMS] == [(wide, 0), (0, wide), (wide, 0)]
    # the same form twice is the same program; the other form of the cut
    # apply is the same bits for the vector
    for y, y_auto in zip(cut["unroll"][1] + whole["scan"][1],
                         cut["auto"][1] + whole["auto"][1]):
        np.testing.assert_array_equal(y, y_auto)
    (y, Y), (y_auto, Y_auto) = cut["scan"][1], cut["auto"][1]
    np.testing.assert_array_equal(y, y_auto)
    np.testing.assert_allclose(Y, Y_auto, atol=1e-14, rtol=1e-13)
    obs.reset_all()
