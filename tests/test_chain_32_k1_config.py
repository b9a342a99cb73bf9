"""The chain in a complex momentum sector (the benchmark's ``chain_32_k1``:
upstream's 32-site chain at translation character k = 1 with spin inversion
+1 and no reflection, 9,390,656 states, a vector of (re, im) pairs whose
gathered row is 32 B, so that ``x`` as a gather table is cut into 6 ranges
of 1,572,864 rows) on the normal path, at rings of 16, 18 and 20 sites
pushed into the same branch: the model is
``benchmark/configs/chain_32_k1.yaml``'s own text with the sizes swapped,
``complex_pair`` is on as it is on a TPU, and ``GATHER_VMEM_BYTES`` is
patched below the pair table's bytes (for the two small rings the rules'
tile too: 392 rows cannot be cut at 1,024 a range), so that the table is cut
and the structure built a range at a time.  One apply of an ``[N, 2]``
array is held against the benchmark's plain reference
(``benchmark/references/ring_heisenberg.py``, which imports nothing of the
program) on every row at the configuration's own contract, and the
reference computed in complex64 fails it.  Beside them the numbers of the
full size that need no build: the closed form of the sector's dimension,
the table rule's verdict, and the staircases of its six ranges read off
histograms counted independently of the engine
(``tests/data/chain_32_k1_ranges.json``).
"""

import importlib.util
import json
import os
import re
from math import comb
from types import SimpleNamespace

import numpy as np
import pytest

from distributed_matvec_tpu import obs
from distributed_matvec_tpu.models.yaml_io import load_config_from_yaml
from distributed_matvec_tpu.ops import kernels as K
from distributed_matvec_tpu.parallel import engine
from distributed_matvec_tpu.parallel.engine import (
    LocalEngine, ell_term_loop, gather_row_blocks, gather_row_bytes,
    gather_table_counts, gather_table_ranges, staircase_levels,
    widest_pieces)
from distributed_matvec_tpu.utils.config import get_config, update_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
YAML_K1 = os.path.join(ROOT, "benchmark", "configs", "chain_32_k1.yaml")
N_K1, N_PAD_K1 = 9_390_656, 144 * 65_536
#: sites -> (chunk rows, the rules' tile, table ranges): several chunks a
#: range; 4 ranges of 128 rows, 3 of 512, and 3 of 2,048 at the real tile
RINGS = {16: (64, 128, 4), 18: (64, 128, 3), 20: (128, 1024, 3)}
ATOL, RTOL = 1e-14, 1e-12       # the configuration's apply contract


def _mobius(d):
    out, p = 1, 2
    while p * p <= d:
        if d % p == 0:
            d //= p
            if d % p == 0:
                return 0
            out = -out
        p += 1
    return -out if d > 1 else out


def sector_dimension(n):
    """States of the ``n``-site ring at half filling in the sector k = 1
    with spin inversion +1, in closed form.  At k = 1 only orbits of full
    period keep a norm (a shorter orbit's stabiliser holds a rotation whose
    character is not 1): ``A`` aperiodic necklaces of ``n`` beads, half of
    them up.  The flip maps an orbit to itself only through the half turn,
    whose character is -1: those ``S`` orbits, of the states ``(a, ~a)``,
    cancel, and the rest pair up."""
    half = n // 2
    A = sum(_mobius(d) * comb(n // d, half // d)
            for d in range(1, half + 1) if half % d == 0) // n
    S = sum(_mobius(d) * 2 ** (half // d)
            for d in range(1, n + 1, 2) if n % d == 0) // n
    return (A - S) // 2


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "ring_heisenberg", os.path.join(
            ROOT, "benchmark", "references", "ring_heisenberg.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def ring_yaml(path, n):
    """``benchmark/configs/chain_32_k1.yaml``'s own text at ``n`` sites:
    the two sizes, the translation and the bond list swapped, nothing
    else."""
    with open(YAML_K1, encoding="utf-8") as f:
        text = f.read()
    swaps = [("number_spins: 32", f"number_spins: {n}"),
             ("hamming_weight: 16", f"hamming_weight: {n // 2}"),
             (str([*range(1, 32), 0]), str([*range(1, n), 0])),
             (str([[i, (i + 1) % 32] for i in range(32)]).replace(" ", "")
              .replace("],[", "], ["),
              str([[i, (i + 1) % n] for i in range(n)]))]
    for old, new in swaps:
        assert text.count(old) == 1, old
        text = text.replace(old, new)
    with open(path, "w", encoding="utf-8") as f:
        f.write(text)
    return str(path)


@pytest.fixture(scope="module")
def ring(tmp_path_factory, reference):
    """``ring(n)``: (operator with its basis built, the reference's spec
    and representatives) of the ``n``-site ring, once a module."""
    made = {}

    def get(n):
        if n not in made:
            path = ring_yaml(
                tmp_path_factory.mktemp("ring") / f"ring_{n}_k1.yaml", n)
            cfg = load_config_from_yaml(path, hamiltonian=True)
            cfg.basis.build()
            spec = reference.Spec(path)
            made[n] = (cfg.hamiltonian, spec,
                       reference.enumerate_representatives(spec))
        return made[n]
    return get


@pytest.fixture
def pair_form():
    """Complex sectors in (re, im)-f64 pair form, as on a TPU."""
    prev = get_config().complex_pair
    update_config(complex_pair="on")
    yield
    update_config(complex_pair=prev)


@pytest.fixture
def pair_table_outside_vmem(monkeypatch):
    """``pair_table_outside_vmem(n_padded, tile, R)``: the rules' VMEM
    number at what ``R`` ranges of a pair-form table take (a range as table,
    gathered rows and indices at once: 68 B a row), which is below the 32 B
    a row of the whole table, so that the row-block rule has no room and the
    table is cut (steered through the rules' inputs, not through an option).
    Returns the range length."""
    def patch(n_padded, tile, R):
        monkeypatch.setattr(engine, "INDEX_TILE", tile)
        W = engine.pad_to_multiple(-(-n_padded // R), tile)
        monkeypatch.setattr(engine, "GATHER_VMEM_BYTES", 68 * W)
        assert 68 * W < 32 * n_padded
        assert gather_row_blocks(n_padded, 6)[0] == 1
        assert gather_table_ranges(n_padded, 6) == (R, W)
        assert (R - 1) * W < n_padded <= R * W
        return W
    return patch


def _pair_vector(n, seed):
    """A unit-norm complex standard-normal vector and its pair form."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal(n) + 1j * rng.standard_normal(n)
    x /= np.linalg.norm(x)
    return x, K.pair_from_complex(x)


def _over_tol(got, want):
    """The largest ``|got - want| / (atol + rtol |want|)``: the modulus of
    complex numbers, as ``benchmark/check.py::compare_apply`` takes it."""
    return float(np.max(np.abs(got - want) / (ATOL + RTOL * np.abs(want))))


@pytest.mark.parametrize("n", list(RINGS))
def test_the_sector_has_the_closed_forms_dimension(n, ring):
    """The program's enumeration, the reference's and the closed form
    agree on the representatives: 392 at 16 sites, 1,336 at 18, 4,587 at
    20 (and 9,390,656 at 32, below)."""
    op, spec, reps = ring(n)
    assert spec.complex and spec.k == 1 and spec.inversion
    assert spec.group_order == 2 * n and not spec.reflection
    np.testing.assert_array_equal(op.basis.representatives, reps)
    assert reps.size == sector_dimension(n) == {16: 392, 18: 1_336,
                                                20: 4_587}[n]
    assert not op.effective_is_real


@pytest.mark.parametrize("n", list(RINGS))
def test_cut_pair_form_apply_matches_the_reference(n, ring, reference,
                                                   pair_form,
                                                   pair_table_outside_vmem):
    """Every row of one apply at the configuration's own contract, through
    the branch ``chain_32_k1`` takes on the chip: pair form, the 32 B-a-row
    table cut into ranges and built a range at a time, the character
    multiplied in by ``cmul_pair``; ``matvec`` takes and returns ``[N, 2]``
    float64.  The complex64 control is not correct by five orders."""
    op, spec, reps = ring(n)
    chunk, tile, R = RINGS[n]
    n_padded = engine.pad_to_multiple(reps.size, chunk)
    W = pair_table_outside_vmem(n_padded, tile, R)
    eng = LocalEngine(op, batch_size=chunk)
    assert eng.mode == "ell" and eng.pair and not eng.real
    assert eng.num_chunks > R and eng._ell_range_rows == W
    counts = eng._ell_counts
    assert (counts["build_passes"], counts["row_blocks"],
            counts["table_ranges"]) == (1, R, R)
    assert counts["table_bytes"] == 32 * n_padded > engine.GATHER_VMEM_BYTES
    assert len(eng._ell_blocks) == len(eng._ell_pos_of) == 2 * R
    assert all(c.shape[-1] == 2 and c.dtype == np.float64
               for _, c in eng._ell_levels)
    assert 0 < counts["far_slots"] < counts["near_slots"]
    rows = np.arange(reps.size)
    # the engine stores a slot a bond: the reference merges the bonds that
    # reach one representative, so it counts at most as many elements
    assert reference.count_offdiagonal(spec, reps, rows) \
        <= counts["live_entries"]
    x, xp = _pair_vector(reps.size, 35 + n)
    assert xp.shape == (reps.size, 2) and xp.dtype == np.float64
    y = np.asarray(eng.matvec(xp))
    assert y.shape == (reps.size, 2) and y.dtype == np.float64
    want = reference.apply_rows(spec, reps, x, rows)
    assert want.dtype == np.complex128
    assert _over_tol(K.complex_from_pair(y), want) <= 1.0
    np.testing.assert_allclose(K.complex_from_pair(y), want,
                               atol=ATOL, rtol=RTOL)
    control = reference.apply_rows(spec, reps, x, rows, np.complex64)
    assert control.dtype == np.complex64
    assert _over_tol(control.astype(np.complex128), want) > 1e5


@pytest.mark.parametrize("n", list(RINGS))
def test_the_span_counts_say_which_gather_is_which(n, ring, pair_form,
                                                   pair_table_outside_vmem):
    """``range_rows``, ``table_rows``, ``unpermute_slots`` and
    ``row_bytes`` on the build span and in the ``engine_init`` event (PR
    35), beside the twelve counts that were there: derived from what the
    engine holds, they read what its arrays say, and they are no key of
    ``_ell_counts`` (the structure artifact's)."""
    op, _, reps = ring(n)
    chunk, tile, R = RINGS[n]
    n_padded = engine.pad_to_multiple(reps.size, chunk)
    W = pair_table_outside_vmem(n_padded, tile, R)
    obs.reset_all()
    eng = LocalEngine(op, batch_size=chunk)
    (build,) = [e for e in obs.events("span")
                if e["name"] == "engine_init/build_structure"]
    init = obs.events("engine_init")[-1]
    new = gather_table_counts(eng)
    assert list(new) == ["range_rows", "table_rows", "unpermute_slots",
                         "row_bytes"]
    assert not set(new) & set(eng._ell_counts)
    for event in (build, init):
        assert {k: event[k] for k in new} == new
        assert {k: event[k] for k in eng._ell_counts} == eng._ell_counts
    assert init["pair"] is True
    # the un-permute gathers' rows: one position a row of every staircase
    # whose rows are ordered by count
    assert new["unpermute_slots"] == sum(
        p.size for p in eng._ell_pos_of if p is not None)
    assert new["unpermute_slots"] > 0 or n == 16    # plain tables there
    assert new["unpermute_slots"] + eng._ell_counts["near_slots"] \
        + eng._ell_counts["far_slots"] == eng._ell_counts["gather_slots"]
    assert new["row_bytes"] == gather_row_bytes(6) == 32
    assert new["row_bytes"] * new["table_rows"] \
        == eng._ell_counts["table_bytes"]
    assert (new["range_rows"], new["table_rows"]) == (W, n_padded)
    assert new["range_rows"] * eng._ell_counts["table_ranges"] \
        >= new["table_rows"]
    # every near table is a range of x, every far one whole x
    assert all(int(np.asarray(i).max()) < W
               for blk in eng._ell_blocks[0::2] for i, _ in blk)
    assert max(int(np.asarray(i).max())
               for blk in eng._ell_blocks[1::2] for i, _ in blk) >= W
    obs.reset_all()


def test_the_span_counts_below_the_line(ring, pair_form):
    """Where the table is not cut ``range_rows`` is 0, every table slot a
    far one by the span's count, and the one un-permute gather a padded row
    long; a real vector's row is 16 B."""
    op, _, reps = ring(16)
    eng = LocalEngine(op, batch_size=64)
    new = gather_table_counts(eng)
    assert eng._ell_counts["table_ranges"] == 1 and eng.pair
    assert new == {"range_rows": 0, "table_rows": eng.n_padded,
                   "unpermute_slots": eng.n_padded
                   if eng._ell_pos_of is not None else 0, "row_bytes": 32}
    assert gather_row_bytes(3) == 16
    assert gather_table_counts(object()) == {}


def test_the_table_rule_cuts_chain_32_k1_into_six_ranges():
    """At full size, no build: 9,390,656 states by the closed form, 144
    chunks of 65,536 padded rows, a pair-form table of 302 MB that leaves
    the row-block rule no room, and the fewest ranges of which one is a
    table, a gather's rows and their indices at once inside 118 MiB (68 B a
    row): 6 of 1,572,864, exactly the padded rows."""
    assert sector_dimension(32) == N_K1
    assert (comb(32, 16) - comb(16, 8)) // 32 == 18_783_360
    assert (18_783_360 - 2 ** 16 // 32) // 2 == N_K1
    assert engine.pad_to_multiple(N_K1, 65_536) == N_PAD_K1 == 9_437_184
    assert 32 * N_PAD_K1 == 301_989_888 > engine.GATHER_VMEM_BYTES
    assert gather_row_blocks(N_PAD_K1, 6) == (1, N_PAD_K1)
    R, W = gather_table_ranges(N_PAD_K1, 6)
    assert (R, W) == (6, 1_572_864) and R * W == N_PAD_K1
    assert 68 * W <= engine.GATHER_VMEM_BYTES
    assert 68 * engine.pad_to_multiple(-(-N_PAD_K1 // 5), 1024) \
        > engine.GATHER_VMEM_BYTES
    # a real vector of the same rows (16 B a row) is cut at twice the rows
    assert gather_table_ranges(N_PAD_K1, 3) == (3, 2 * W)


def test_staircases_of_the_chain_32_k1_ranges():
    """The near and far staircases of ``chain_32_k1``'s 6 ranges, read off
    the histograms counted independently of the engine
    (``tests/data/chain_32_k1_ranges.json``: the plain reference's orbit
    scan and a searchsorted over its own sector), no build: 82.19% of the
    155,077,888 live entries are near; 127.5 M near slots, 27.7 M far ones
    and two un-permute rows a padded row make 174.1 M gathered rows an
    apply (fill 89.10%), in 151 levels and 12 un-permutes: the counts the
    chip's build put on its span (PERF.md §7, PR 34's run).  43 gathers an
    apply are a full range long with a range as their table."""
    with open(os.path.join(ROOT, "tests", "data",
                           "chain_32_k1_ranges.json")) as f:
        data = json.load(f)
    R, W = gather_table_ranges(N_PAD_K1, 6)
    assert (data["n_states"], data["n_padded"], data["ranges"],
            data["range_rows"]) == (N_K1, N_PAD_K1, R, W)
    entries, slots, levels_n, unpermute, widest = [0, 0], [0, 0], 0, 0, 0
    full_length = 0
    for r in range(R):
        width = 0
        for part, kind in enumerate(("near", "far")):
            hist = np.array(data[kind][r], np.int64)
            assert hist.sum() == W
            entries[part] += int(np.dot(np.arange(33), hist))
            stair, levels = staircase_levels(hist, W)
            assert stair and levels[0][2] <= W
            slots[part] += sum(k * L for _, k, L in levels)
            levels_n += len(levels)
            unpermute += W
            width += sum(k for _, k, _ in levels)
            full_length += 1 + (kind == "near") * sum(
                k for _, k, L in levels if L == W)
        widest = max(widest, width)
    assert entries == [127_464_870, 27_613_018]
    assert sum(entries) == 155_077_888
    assert round(entries[0] / sum(entries), 4) == 0.8219
    assert slots == [127_524_864, 27_652_096]
    assert (levels_n, unpermute, widest) == (151, 2 * N_PAD_K1, 42)
    assert sum(slots) + unpermute == 174_051_328
    assert 100.0 * sum(entries) / 174_051_328 == pytest.approx(89.0989,
                                                                abs=1e-4)
    assert round(100.0 * slots[0] / sum(slots), 2) == 82.18
    assert full_length == 43
    # 20 B a slot on the device (an index and an (re, im) pair of f64) and
    # twelve position arrays: 3.18 GB of the 4.79 GB peak
    assert 20 * sum(slots) + 4 * unpermute == 3_179_036_672


def test_the_chain_32_k1_yaml_describes_the_sector():
    """``benchmark/configs/chain_32_k1.yaml`` through the schema loader, no
    build: 32 spins, weight 16, spin inversion +1, one symmetry, the
    translation with ``sector: 1``; the Hamiltonian is
    ``chain_32_symm.yaml``'s to the letter; a complex sector."""
    import yaml

    cfg = load_config_from_yaml(YAML_K1, hamiltonian=True)
    basis = cfg.basis
    assert not basis.is_built
    assert (basis.number_spins, basis.hamming_weight,
            basis.spin_inversion) == (32, 16, 1)
    assert not cfg.hamiltonian.effective_is_real
    assert cfg.hamiltonian.number_off_diag_terms == 32
    with open(YAML_K1, encoding="utf-8") as f:
        text = f.read()
    doc = yaml.safe_load(text)
    assert doc["basis"]["symmetries"] == [
        {"permutation": [*range(1, 32), 0], "sector": 1}]
    with open(YAML_K1.replace("chain_32_k1", "chain_32_symm"),
              encoding="utf-8") as f:
        symm = f.read()
    at = "hamiltonian:"
    assert text[text.index(at):] == symm[symm.index(at):]
    assert re.search(r"sector: 1\n", text) and "sector: 0" not in text


FORMS = ("auto", "scan", "unroll")


@pytest.mark.parametrize("n", list(RINGS))
def test_cut_pair_form_apply_is_the_same_bits_in_every_term_loop_form(
        n, ring, pair_form, pair_table_outside_vmem):
    """The pair-form apply with its table cut, one ``[N, 2]`` vector and a
    two-column batch ``[N, 2, 2]``, under each value of the ``term_loop``
    hook: the same apply to the last bit or two (one gather a column in
    straight-line code, which ``auto`` is where the table is cut since PR
    36, adds a row's columns in the scan's order; XLA's CPU backend
    contracts ``cmul_pair``'s multiply-adds otherwise there than under the
    scan, 10 of 9,174 values an ulp apart at 20 sites: a real vector's
    apply is the same bits, ``test_chain_28_config.py``), ``auto`` and
    ``unroll`` the same bits, and the ``engine_init`` event says which ran:
    ``unrolled_columns`` the widest range's columns and ``scanned_columns``
    0, the reverse under ``scan`` and, under ``auto``, wherever the table
    is not cut (``test_the_span_counts_below_the_line``'s engine)."""
    op, _, reps = ring(n)
    chunk, tile, R = RINGS[n]
    rng = np.random.default_rng(36 + n)
    xs = [rng.standard_normal((reps.size, 2)),
          rng.standard_normal((reps.size, 2, 2))]
    LocalEngine(op, batch_size=chunk)   # not cut: the scan, as since PR 28
    init = obs.events("engine_init")[-1]
    assert init["unrolled_columns"] == 0 < init["scanned_columns"]
    pair_table_outside_vmem(engine.pad_to_multiple(reps.size, chunk), tile, R)
    said, got = {}, {}
    try:
        for form in FORMS:
            update_config(term_loop=form)
            eng = LocalEngine(op, batch_size=chunk)
            init = obs.events("engine_init")[-1]
            assert eng.pair and init["table_ranges"] == R
            said[form] = (init["unrolled_columns"], init["scanned_columns"])
            got[form] = [np.asarray(eng.matvec(x)) for x in xs]
    finally:
        update_config(term_loop="auto")
    wide = sum(i.shape[0] for i, _ in widest_pieces(eng._ell_blocks, True))
    assert wide > 0
    assert [said[f] for f in FORMS] == [(wide, 0), (0, wide), (wide, 0)]
    assert [y.shape for y in got["auto"]] == [x.shape for x in xs]
    for y, y_scan, y_auto in zip(got["unroll"], got["scan"], got["auto"]):
        np.testing.assert_array_equal(y, y_auto)
        np.testing.assert_allclose(y_scan, y_auto, atol=1e-14, rtol=1e-13)
    obs.reset_all()


@pytest.mark.parametrize("form, unrolled", [("auto", True), ("scan", False),
                                            ("unroll", True)])
@pytest.mark.parametrize("cell, parts, width", [("chain_32_k1", 6, 42),
                                                ("chain_28", 3, 35)])
def test_term_loop_form_above_the_line(cell, parts, width, form, unrolled):
    """The form of the term loop at the two cells above the VMEM line, from
    the shapes of their ranges' staircases alone
    (``tests/data/<cell>_ranges.json``): one gather a column under
    ``auto``, the form whose tables the compiler places in VMEM (PR 36:
    PERF.md §6), the scan only under the hook; the counts are the widest
    range's columns, near and far, 42 and 35."""
    with open(os.path.join(ROOT, "tests", "data", cell + "_ranges.json"),
              encoding="utf-8") as f:
        data = json.load(f)
    R, W = gather_table_ranges(data["n_padded"], parts)
    blocks = []
    for r in range(R):
        rows = min(W, data["n_padded"] - r * W)
        for kind in ("near", "far"):
            _, levels = staircase_levels(np.array(data[kind][r]), rows)
            blocks.append(tuple((SimpleNamespace(shape=(k, L)), None)
                                for _, k, L in levels))
    update_config(term_loop=form)
    try:
        unroll, counts = ell_term_loop(widest_pieces(blocks, True), cut=True)
        below = ell_term_loop(widest_pieces(blocks, True))
    finally:
        update_config(term_loop="auto")
    assert unroll is unrolled
    assert counts == {"unrolled_columns": width if unrolled else 0,
                      "scanned_columns": 0 if unrolled else width}
    # the same shapes under the line: the scan unless the hook says unroll
    assert below[0] is (form == "unroll")
