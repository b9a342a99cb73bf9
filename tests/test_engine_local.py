"""Single-device jitted matvec vs host matvec and the dense reference.

The golden-test contract of TestMatrixVectorProduct.chpl:15-23 (atol 1e-14 /
rtol 1e-12, full pipeline) applied to the device path, in both engine modes
(precomputed-ELL and fused/on-the-fly).
"""

from functools import partial

import numpy as np
import pytest

from distributed_matvec_tpu.models.basis import SpinBasis
from distributed_matvec_tpu.parallel.engine import LocalEngine

from test_operator import CONFIGS, build_heisenberg, dense_effective_matrix

ATOL, RTOL = 1e-13, 1e-12


@pytest.mark.parametrize("mode", ["ell", "fused"])
@pytest.mark.parametrize("n,hw,inv,syms", CONFIGS)
def test_local_engine_matches_dense(n, hw, inv, syms, mode, rng):
    op = build_heisenberg(n, hw, inv, syms)
    op.basis.build()
    h_eff = dense_effective_matrix(op)
    x = rng.random(op.basis.number_states) - 0.5
    if not op.effective_is_real:
        x = x.astype(np.complex128)
    eng = LocalEngine(op, batch_size=61, mode=mode)  # force chunking + padding
    y = np.asarray(eng.matvec(x))
    y_ref = h_eff @ x
    if op.effective_is_real:
        y_ref = y_ref.real
    np.testing.assert_allclose(y, y_ref, atol=ATOL, rtol=RTOL)


@pytest.mark.parametrize("mode", ["ell", "fused"])
def test_single_chunk_path(mode, rng):
    op = build_heisenberg(8, 4)
    op.basis.build()
    x = rng.random(op.basis.number_states) - 0.5
    eng = LocalEngine(op, mode=mode)  # batch larger than basis → one chunk
    assert eng.num_chunks == 1
    y = np.asarray(eng.matvec(x))
    np.testing.assert_allclose(y, op.matvec_host(x), atol=ATOL, rtol=RTOL)


@pytest.mark.parametrize("mode", ["ell", "fused"])
def test_batch_matvec_matches_columns(mode, rng):
    """Rank-2 batches: matvec(X)[:, i] == matvec(X[:, i]) — the numVectors
    contract of ls_chpl_matrix_vector_product (DistributedMatrixVector.chpl:1095-1110)."""
    op = build_heisenberg(10, 5, -1)
    op.basis.build()
    n = op.basis.number_states
    X = rng.random((n, 3)) - 0.5
    eng = LocalEngine(op, batch_size=100, mode=mode)
    Y = np.asarray(eng.matvec(X))
    for k in range(X.shape[1]):
        np.testing.assert_allclose(
            Y[:, k], np.asarray(eng.matvec(X[:, k])), atol=ATOL, rtol=RTOL
        )


@pytest.mark.parametrize("mode", ["ell", "fused"])
def test_engine_detects_sector_violation(mode):
    """σˣ alone breaks hamming conservation → engine must raise (the halt
    analog of DistributedMatrixVector.chpl:113-118).  In ell mode the check
    fires at structure-build time, in fused mode on the first matvec."""
    from distributed_matvec_tpu.models.operator import Operator

    basis = SpinBasis(6, 3)
    op = Operator.from_expressions(basis, [("σˣ₀", [[0], [1]])])
    basis.build()
    with pytest.raises(RuntimeError, match="outside the basis"):
        eng = LocalEngine(op, mode=mode)
        eng.matvec(np.ones(basis.number_states))


def test_matvec_is_jit_cached(rng):
    op = build_heisenberg(10, 5, -1)
    op.basis.build()
    eng = LocalEngine(op, batch_size=32)
    x = rng.random(op.basis.number_states) - 0.5
    y1 = eng.matvec(x)
    y2 = eng.matvec(2 * x)
    np.testing.assert_allclose(2 * np.asarray(y1), np.asarray(y2), atol=1e-13)


def test_non_hermitian_rejected():
    from distributed_matvec_tpu.models.operator import Operator

    basis = SpinBasis(4, 2)
    op = Operator.from_expressions(basis, [("σ⁺₀ σ⁻₁", [[0, 1]])])
    basis.build()
    assert not op.is_hermitian
    with pytest.raises(ValueError, match="Hermitian"):
        LocalEngine(op)


def _ring(n):
    return [*range(1, n), 0]


# rings whose row widths (a row's domain walls: 2, 4, ... n) spread enough
# for the staircase to engage at CPU-test sizes (thousands of rows)
STAIR_RINGS = {
    "ring16": (16, 8, None, ()),                        # 12,870 rows, real
    "symm_ring20": (20, 10, 1, [(_ring(20), 0),         # 2,518 rows, |G|=80
                                ([*reversed(range(20))], 0)]),
    "momentum_ring18": (18, 9, None, [(_ring(18), 1)]),  # 2,700, complex
}


@pytest.fixture
def pair_form():
    """Complex sectors in (re, im)-f64 pair form, as on a TPU."""
    from distributed_matvec_tpu.utils.config import get_config, update_config

    prev = get_config().complex_pair
    update_config(complex_pair="on")
    yield
    update_config(complex_pair=prev)


def _stair_engine(name, **kw):
    op = build_heisenberg(*STAIR_RINGS[name])
    op.basis.build()
    eng = LocalEngine(op, mode="ell", **kw)
    assert eng._ell_pos_of is not None and len(eng._ell_levels) > 1, \
        "staircase did not engage"
    return op, eng


def _cut_into(monkeypatch, eng, nb):
    """Steer the block rule through its input, not through an option of
    the program: the VMEM number under which ``eng``'s padded rows are cut
    into ``nb`` blocks.  Returns the block length."""
    from distributed_matvec_tpu.parallel import engine

    n_pad, parts = eng.n_padded, 3 if eng.real else 6
    row = engine.pad_to_multiple(parts, 4) * 4
    B = engine.pad_to_multiple(-(-n_pad // nb), engine.INDEX_TILE)
    monkeypatch.setattr(engine, "GATHER_VMEM_BYTES",
                        n_pad * row + B * (row + 4))
    assert engine.gather_row_blocks(n_pad, parts) == (nb, B)
    return B


def _independent_apply(op, X):
    """H·X from the definitions alone: the symmetry isometry of
    ``dense_ref`` around ``independent_ref``'s bit-operation ring apply on
    the whole fixed-magnetisation sector."""
    import dense_ref
    import independent_ref

    basis = op.basis
    n = basis.number_spins
    states = independent_ref.enumerate_fixed_hw(n, basis.hamming_weight)
    B = dense_ref.symmetry_isometry(n, basis.representatives, basis.norms,
                                    basis.group)[states.astype(np.int64)]
    cols = []
    for x in np.atleast_2d(X.T):
        full = B @ x
        hx = independent_ref.heisenberg_ring_apply(states, n, full.real) \
            + 1j * independent_ref.heisenberg_ring_apply(states, n,
                                                         full.imag)
        cols.append(B.getH() @ hx)
    Y = np.stack(cols, axis=1)
    Y = Y if np.iscomplexobj(X) else Y.real
    return Y if X.ndim == 2 else Y[:, 0]


def _primitives(jaxpr):
    """Names of every primitive in a jaxpr, sub-jaxprs included."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names += _primitives(sub)
    return names


def _apply_primitives(eng, x):
    import jax

    apply_fn, operands = eng.bound_matvec()
    return _primitives(jax.make_jaxpr(apply_fn)(x, operands).jaxpr)


@pytest.mark.parametrize("term_loop", ["auto", "unroll"])
@pytest.mark.parametrize("name", list(STAIR_RINGS))
def test_staircase_matches_independent_reference(name, term_loop, rng,
                                                 pair_form):
    """The staircase apply — rows ordered by non-zero count, one level a
    column length, the result gathered back to basis order — against the
    independent reference: a plain ring, a fully symmetric one and a
    complex momentum sector in pair form; one vector and a batch; padded
    rows beyond the basis (batch_size 1000); the scan term loop (``auto``)
    and the unrolled one (the test hook)."""
    from distributed_matvec_tpu.utils.config import update_config

    update_config(term_loop=term_loop)
    try:
        op, eng = _stair_engine(name, batch_size=1000)
        assert eng.n_padded > eng.n_states
        assert eng.pair == (not op.effective_is_real)
        n = op.basis.number_states
        X = rng.random((n, 3)) - 0.5
        if eng.pair:
            X = X + 1j * (rng.random((n, 3)) - 0.5)
        Y_ref = _independent_apply(op, X)
        np.testing.assert_allclose(np.asarray(eng.matvec(X[:, 0])),
                                   Y_ref[:, 0], atol=1e-13, rtol=1e-12)
        if eng.pair:    # a batch of pair vectors is [N, k, 2]
            from distributed_matvec_tpu.ops import kernels as K

            Y = K.complex_from_pair(np.asarray(eng.matvec(
                np.moveaxis(K.pair_from_complex(X.T), 0, 1))))
        else:
            Y = np.asarray(eng.matvec(X))
        np.testing.assert_allclose(Y, Y_ref, atol=1e-13, rtol=1e-12)
    finally:
        update_config(term_loop="auto")


def test_staircase_accounting(rng):
    """``gather_slots``, ``live_entries`` and ``levels`` against the ring's
    own arithmetic: a row has one entry a domain wall."""
    op, eng = _stair_engine("ring16", batch_size=1000)
    n, n_pad = eng.n_states, eng.n_padded
    s = op.basis.representatives
    walls = s ^ ((s >> np.uint64(1)) | ((s & np.uint64(1)) << np.uint64(15)))
    nnz = np.array([bin(int(w)).count("1") for w in walls])
    Tmax = int(nnz.max())
    rows_gt = np.array([(nnz > t).sum() for t in range(Tmax)])
    lengths = -(-rows_gt // 1024) * 1024
    assert eng._ell_counts == {
        "gather_slots": int(lengths.sum()) + n_pad,
        "live_entries": int(nnz.sum()),
        "levels": len(set(lengths)),
        "terms": 16, "widest_row": Tmax,
        "row_blocks": 1, "gather_pieces": len(set(lengths)) + 1,
        "build_passes": 1, "table_bytes": n_pad * 16,
        "table_ranges": 1, "near_slots": 0,
        "far_slots": int(lengths.sum())}
    # the level arrays are those columns, longest first, and nothing else
    assert [i.shape for i, _ in eng._ell_levels] == \
        [(int((lengths == L).sum()), int(L))
         for L in sorted(set(lengths), reverse=True)]
    live = sum(int(np.count_nonzero(np.asarray(c)))
               for _, c in eng._ell_levels)
    assert live == nnz.sum()
    # row_of: a permutation of the padded rows, widest rows first, ties in
    # basis order; the same on a second build
    pos_of = np.asarray(eng._ell_pos_of)
    row_of = np.argsort(pos_of)
    np.testing.assert_array_equal(np.sort(pos_of), np.arange(n_pad))
    nnz_pad = np.concatenate([nnz, np.zeros(n_pad - n, int)])
    np.testing.assert_array_equal(
        row_of, np.argsort(-nnz_pad, kind="stable"))
    _, eng2 = _stair_engine("ring16", batch_size=1000)
    np.testing.assert_array_equal(pos_of, np.asarray(eng2._ell_pos_of))
    # the ledger's per-row width and the phase counts follow the levels
    slots = int(lengths.sum())
    assert eng.ell_width == -(-slots // n_pad)
    counts = eng._phase_counts(1)
    assert counts["compute"]["gathers"] == slots
    assert counts["accumulate"]["gathers"] == n_pad


@pytest.mark.parametrize("term_loop", ["auto", "unroll"])
def test_staircase_apply_has_no_scatter(term_loop):
    """One gather a table column and one back to basis order; no scatter
    (the two-level format's tail paid one), in either term-loop form."""
    from distributed_matvec_tpu.utils.config import update_config

    update_config(term_loop=term_loop)
    try:
        _, eng = _stair_engine("symm_ring20")
        prims = _apply_primitives(eng, np.zeros(eng.n_states))
    finally:
        update_config(term_loop="auto")
    assert not [p for p in prims if "scatter" in p]
    # scan (``auto``): a gather a level + the un-permute; unrolled: one a
    # column
    width = sum(i.shape[0] for i, _ in eng._ell_levels)
    assert prims.count("gather") == \
        (width if term_loop == "unroll" else len(eng._ell_levels)) + 1


def test_row_block_rule_reads_the_shapes():
    """gather_row_blocks: the block length from the table's rows, the lanes
    of one gathered row and one number for the chip's VMEM — the two
    benchmark bases, what the trace measured on either side of the step,
    a pair-form table, and the cases where the rows stay whole."""
    from distributed_matvec_tpu.parallel.engine import (
        GATHER_VMEM_BYTES, INDEX_TILE, block_pieces, gather_row_blocks)

    assert gather_row_blocks(4_718_592, 3) == (2, 2_359_296)   # chain_32_symm
    assert gather_row_blocks(5_242_880, 3) == (3, 1_747_968)   # square_5x5
    # what ran at 4.317 ns a slot fits by the rule, what ran at 6.056 does
    # not (PERF.md §5: table rows x 16 B + gathered rows x 20 B)
    for rows, gathered, fits in ((4_707_969, 2_030_592, True),
                                 (4_707_969, 3_326_976, False),
                                 (5_200_300, 1_949_696, True),
                                 (5_200_300, 3_198_976, False)):
        assert (rows * 16 + gathered * 20 <= GATHER_VMEM_BYTES) == fits
    # six parts take eight lanes: half the rows of the chain cut in two,
    # the chain itself (151 MB of table) and square_6x6 (253 MB) left whole
    assert gather_row_blocks(2_359_296, 6) == (2, 1_179_648)
    assert gather_row_blocks(4_718_592, 6)[0] == 1
    assert gather_row_blocks(15_859_712, 3)[0] == 1
    # near the line at which the table alone fills VMEM (7,733,248 rows of
    # 16 B) it leaves room for short blocks only, 301 of 25,600 rows at
    # 7.7 M: up to 32 blocks (7.44 M rows) the rows are cut as before, past
    # it they stay whole, as they do above the line (chain_28's 613 MiB
    # table, 40,173,568 rows)
    assert gather_row_blocks(7_000_000, 3) == (12, 583_680)
    assert gather_row_blocks(7_440_000, 3) == (32, 233_472)
    for rows in (7_450_000, 7_700_000, 7_733_248, 7_800_000, 40_173_568):
        assert gather_row_blocks(rows, 3) == (1, -(-rows // 1024) * 1024)
    # a basis shorter than one block, and an empty one
    assert gather_row_blocks(13_000, 3) == (1, 13 * INDEX_TILE)
    assert gather_row_blocks(0, 3) == (1, 0)
    # a level is a prefix of the packed rows: one piece a block it reaches
    levels = ((0, 6, 5 * INDEX_TILE), (6, 2, 3 * INDEX_TILE),
              (8, 4, INDEX_TILE))
    T = INDEX_TILE
    assert block_pieces(levels, 2 * T) == (
        ((0, 0, 2 * T), (1, 0, 2 * T), (2, 0, T)),
        ((0, 2 * T, 2 * T), (1, 2 * T, T)),
        ((0, 4 * T, T),))
    assert block_pieces(levels, 5 * T) == (
        tuple((li, 0, L) for li, (_, _, L) in enumerate(levels)),)


@pytest.mark.parametrize("name, batch_size, nb", [
    ("ring16", 1000, 2),             # plain, padded rows (13,000 of 12,870)
    ("ring16", 1000, 3),
    ("ring16", 1000, 5),
    ("symm_ring20", None, 2),        # symmetric; rows not a tile multiple
    ("symm_ring20", None, 3),
    ("momentum_ring18", 512, 2),     # pair form: eight lanes a row
    ("momentum_ring18", 512, 3),
])
@pytest.mark.parametrize("term_loop", ["auto", "unroll"])
def test_row_blocked_apply_is_the_unblocked_one(name, batch_size, nb,
                                                term_loop, rng, pair_form,
                                                monkeypatch):
    """The staircase cut into 2, 3 and 5 row blocks: the counts of the
    uncut one but for ``row_blocks`` and ``gather_pieces``, its levels
    piece for piece, and the same apply bit for bit — one vector and a
    batch, in both forms of the term loop."""
    from distributed_matvec_tpu.utils.config import update_config

    kw = {} if batch_size is None else {"batch_size": batch_size}
    op, whole = _stair_engine(name, **kw)
    B = _cut_into(monkeypatch, whole, nb)
    cut = LocalEngine(op, mode="ell", **kw)
    assert cut._ell_counts["row_blocks"] == nb == len(cut._ell_blocks)
    assert cut._ell_counts["gather_pieces"] == len(cut._ell_levels) + nb
    assert {**cut._ell_counts, "row_blocks": 1,
            "gather_pieces": len(whole._ell_levels) + 1} == whole._ell_counts
    np.testing.assert_array_equal(np.asarray(cut._ell_pos_of),
                                  np.asarray(whole._ell_pos_of))
    # block b holds rows [b B, (b + 1) B) of every level that reaches it,
    # the longest level first
    for b, pieces in enumerate(cut._ell_blocks):
        reach = [lv for lv in whole._ell_levels if lv[0].shape[1] > b * B]
        assert len(pieces) == len(reach)
        for (i_c, c_c), (i_w, c_w) in zip(pieces, reach):
            rows = slice(b * B, (b + 1) * B)
            np.testing.assert_array_equal(np.asarray(i_c),
                                          np.asarray(i_w)[:, rows])
            np.testing.assert_array_equal(np.asarray(c_c),
                                          np.asarray(c_w)[:, rows])
    assert cut.ell_nbytes == whole.ell_nbytes
    assert cut._phase_counts(1) == whole._phase_counts(1)
    n = cut.n_states
    X = rng.random((n, 3)) - 0.5
    if cut.pair:
        from distributed_matvec_tpu.ops import kernels as K

        X = K.pair_from_complex(X + 1j * (rng.random((n, 3)) - 0.5))
    update_config(term_loop=term_loop)
    try:
        # the scan form, which every engine takes, repeats bit for bit;
        # unrolled, the compiler sees all of a row's adds and may contract
        # them differently in two programs
        same = np.testing.assert_array_equal if term_loop == "auto" else \
            partial(np.testing.assert_allclose, rtol=1e-13, atol=1e-14)
        for x in (X[:, 0], X):
            same(np.asarray(cut.matvec(x)), np.asarray(whole.matvec(x)))
        prims = _apply_primitives(cut, X[:, 0])
    finally:
        update_config(term_loop="auto")
    assert "scatter-add" not in prims
    width = sum(i.shape[0] for i, _ in cut._ell_levels)
    assert prims.count("gather") == cut._ell_counts["gather_pieces"] \
        + (width - len(cut._ell_levels) if term_loop == "unroll" else 0)


def _cut_table_into(monkeypatch, eng, R):
    """Steer the table rule through its input: the VMEM number under which
    ``x`` as a gather table leaves the row-block rule no room and
    ``eng``'s padded rows are cut into ``R`` table ranges.  Returns the
    range length."""
    from distributed_matvec_tpu.parallel import engine

    n_pad, parts = eng.n_padded, 3 if eng.real else 6
    row = engine.pad_to_multiple(parts, 4) * 4
    W = engine.pad_to_multiple(-(-n_pad // R), engine.INDEX_TILE)
    assert W * (R - 1) < n_pad, "no such cut of these rows"
    monkeypatch.setattr(engine, "GATHER_VMEM_BYTES", W * (2 * row + 4))
    assert engine.gather_row_blocks(n_pad, parts)[0] == 1
    assert engine.gather_table_ranges(n_pad, parts) == (R, W)
    return W


# name, batch size, ranges: a plain ring with padded rows (13,000 of
# 12,870), a fully symmetric one (rows not a tile multiple; columns far
# from the diagonal), a complex momentum sector in pair form (eight lanes
# a row, so half the rows a range), chunks that straddle the ranges of
# a row's range.  (Two ranges never occur: where the table leaves the
# row-block rule no room, 16.6 B a row pass the VMEM number, and a range
# takes 36.)
TABLE_CUTS = [("ring16", 1000, 3), ("ring16", 1000, 4), ("ring16", 1000, 5),
              ("ring16", 61, 3), ("symm_ring20", None, 3),
              ("momentum_ring18", 512, 3)]


@pytest.mark.parametrize("name, batch_size, R", TABLE_CUTS)
@pytest.mark.parametrize("term_loop", ["auto", "scan"])
def test_table_cut_apply_matches_independent_reference(
        name, batch_size, R, term_loop, rng, pair_form, monkeypatch):
    """Where ``x`` does not fit VMEM as a gather table (the rule's number
    patched down to that) rows and columns are cut into 3, 4 and 5 ranges:
    a near and a far staircase a range, every entry stored once, near
    where its column lies in its row's range; the apply against the
    independent reference and against the uncut engine, one vector and a
    batch, in both forms of the term loop (where the table is cut ``auto``
    is one gather a column, PR 36; the hook asks for the scan); no
    scatter."""
    from distributed_matvec_tpu.utils.config import update_config

    kw = {} if batch_size is None else {"batch_size": batch_size}
    op, whole = _stair_engine(name, **kw)
    W = _cut_table_into(monkeypatch, whole, R)
    cut = LocalEngine(op, mode="ell", **kw)
    counts, n_pad = cut._ell_counts, cut.n_padded
    assert (counts["table_ranges"], counts["row_blocks"],
            counts["build_passes"]) == (R, R, 1)
    assert len(cut._ell_blocks) == len(cut._ell_pos_of) == 2 * R
    slots = [sum(i.size for i, _ in blk) for blk in cut._ell_blocks]
    assert (sum(slots[0::2]), sum(slots[1::2])) == \
        (counts["near_slots"], counts["far_slots"])
    unpermute = sum(p.size for p in cut._ell_pos_of if p is not None)
    assert counts["gather_slots"] == sum(slots) + unpermute
    assert counts["gather_pieces"] == len(cut._ell_levels) + sum(
        p is not None for p in cut._ell_pos_of)
    assert counts["levels"] == len(cut._ell_levels)
    assert {k: counts[k] for k in ("live_entries", "terms", "table_bytes")} \
        == {k: whole._ell_counts[k]
            for k in ("live_entries", "terms", "table_bytes")}
    # every entry of the uncut engine's levels, once: near ones with
    # range-local columns inside the range, far ones outside it
    live, near = 0, 0
    for j, blk in enumerate(cut._ell_blocks):
        r, far = divmod(j, 2)
        rows = min(W, n_pad - r * W)
        for idx, cf in blk:
            idx, cf = np.asarray(idx), np.asarray(cf)
            hit = cf.reshape(idx.shape + (-1,)).any(axis=-1)
            live += int(hit.sum())
            assert not idx[~hit].any()
            if far:
                assert (idx[hit] // W != r).all()
            else:
                assert idx.max(initial=0) < rows
                near += int(hit.sum())
    assert live == counts["live_entries"] and 0 < near < live
    assert cut.ell_nbytes == sum(
        i.nbytes + c.nbytes for i, c in cut._ell_levels) + 4 * unpermute
    # above the line the build's inputs (states, norms, lookup) go with
    # the build; below it they stay, as they always have
    import jax

    held = [jax.tree_util.tree_leaves([eng.memory_arrays()[k]
                                       for k in ("lookup", "basis_rows")])
            for eng in (cut, whole)]
    assert (len(held[0]), len(held[1])) == (0, 4)
    ph = cut._phase_counts(1)
    assert ph["compute"]["gathers"] == sum(slots)
    assert ph["accumulate"]["gathers"] == unpermute
    n = cut.n_states
    X = rng.random((n, 3)) - 0.5
    if cut.pair:
        X = X + 1j * (rng.random((n, 3)) - 0.5)
    Y_ref = _independent_apply(op, X)
    if cut.pair:
        from distributed_matvec_tpu.ops import kernels as K

        Xp = np.moveaxis(K.pair_from_complex(X.T), 0, 1)   # [N, k, 2]
        back = K.complex_from_pair
    else:
        Xp, back = X, np.asarray
    update_config(term_loop=term_loop)
    try:
        for x, y_ref in ((Xp[:, 0], Y_ref[:, 0]), (Xp, Y_ref)):
            y = back(np.asarray(cut.matvec(x)))
            np.testing.assert_allclose(y, y_ref, atol=1e-13, rtol=1e-12)
            # the sum's order changes with the cut, its terms do not
            np.testing.assert_allclose(
                y, back(np.asarray(whole.matvec(x))), atol=1e-14,
                rtol=1e-13)
        prims = _apply_primitives(cut, Xp[:, 0])
    finally:
        update_config(term_loop="auto")
    assert not [p for p in prims if "scatter" in p]
    width = sum(i.shape[0] for i, _ in cut._ell_levels)
    assert prims.count("gather") == counts["gather_pieces"] \
        + (width - len(cut._ell_levels) if term_loop == "auto" else 0)
    assert prims.count("scan") == (0 if term_loop == "auto"
                                   else len(cut._ell_levels))


def test_table_rule_reads_the_shapes():
    """gather_table_ranges: 1 wherever the row-block rule has room (every
    basis the benchmark had before chain_28, every test basis), and past
    that line the fewest ranges of which one is a table, a gather's rows
    and their indices inside the one VMEM number."""
    from distributed_matvec_tpu.parallel.engine import (
        GATHER_VMEM_BYTES, gather_row_blocks, gather_table_ranges)

    for rows, parts in ((4_718_592, 3), (5_242_880, 3), (2_359_296, 6),
                        (7_000_000, 3), (7_440_000, 3), (13_000, 3), (0, 3)):
        assert gather_table_ranges(rows, parts) == (1, rows)
    # the first row count the row-block rule leaves whole, and the line
    # at which the table alone fills VMEM
    assert gather_row_blocks(7_450_000, 3)[0] == 1
    assert gather_table_ranges(7_450_000, 3) == (3, 2_484_224)
    assert gather_table_ranges(7_733_248, 3) == (3, 2_578_432)
    assert gather_table_ranges(40_173_568, 3) == (12, 3_348_480)  # chain_28
    assert gather_table_ranges(15_804_956, 3) == (5, 3_161_088)   # square_6x6
    assert gather_table_ranges(15_859_712, 3) == (5, 3_172_352)
    # six parts a row (pair form, a two-column batch): half the rows
    assert gather_table_ranges(4_718_592, 6) == (3, 1_572_864)
    assert gather_table_ranges(20_086_784, 6) == (12, 1_674_240)
    for rows, parts in ((7_450_000, 3), (40_173_568, 3), (4_718_592, 6)):
        R, W = gather_table_ranges(rows, parts)
        row = 16 if parts == 3 else 32
        assert W % 1024 == 0 and (R - 1) * W < rows <= R * W
        assert W * (2 * row + 4) <= GATHER_VMEM_BYTES
        assert -(-rows // (R - 1)) * (2 * row + 4) > GATHER_VMEM_BYTES


@pytest.mark.parametrize("name, kw, blocks, digest", [
    ("ring16", {"batch_size": 1000}, 1, "66a03d06007ee26f"),
    ("symm_ring20", {}, 1, "6d3c1426786c0c0d"),
    ("ring16", {"batch_size": 1000}, 3, "3bcf59f66944337b"),
])
def test_below_the_line_the_structure_is_the_parents(name, kw, blocks,
                                                     digest, monkeypatch):
    """Where the table is not cut ``structure_arrays()`` is what PR 32's
    tree built, to the byte: SHA-256 over shape and bytes of every leaf,
    taken from that tree (commit c23823c) for a plain ring, a symmetric
    one and the plain ring in three row blocks."""
    import hashlib

    import jax

    op, eng = _stair_engine(name, **kw)
    if blocks > 1:
        _cut_into(monkeypatch, eng, blocks)
        eng = LocalEngine(op, mode="ell", **kw)
    assert eng._ell_counts["row_blocks"] == blocks
    assert eng._ell_counts["table_ranges"] == 1
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(eng.structure_arrays()):
        a = np.asarray(leaf)
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    assert h.hexdigest()[:16] == digest


def test_equal_width_rows_keep_plain_table(rng):
    """An operator whose rows are equally wide (a transverse field on the
    full basis: every state flips at each of its n sites) reads one level
    in basis order off its histogram: no row order, no un-permute gather."""
    from distributed_matvec_tpu.models.operator import Operator

    n = 12
    basis = SpinBasis(n)
    op = Operator.from_expressions(basis, [("σˣ₀", [[i] for i in range(n)])])
    basis.build()
    eng = LocalEngine(op, mode="ell", batch_size=1024)
    assert eng._ell_pos_of is None
    assert [i.shape for i, _ in eng._ell_levels] == [(n, eng.n_padded)]
    assert eng._ell_counts == {"gather_slots": n * eng.n_padded,
                               "live_entries": n * 2 ** n, "levels": 1,
                               "terms": n, "widest_row": n,
                               "row_blocks": 1, "gather_pieces": 1,
                               "build_passes": 1,
                               "table_bytes": 16 * eng.n_padded,
                               "table_ranges": 1, "near_slots": 0,
                               "far_slots": n * eng.n_padded}
    x = rng.random(2 ** n) - 0.5
    prims = _apply_primitives(eng, x)
    # one level, its columns scanned: one gather, and none to un-permute
    assert prims.count("gather") == 1 and "scatter-add" not in prims
    np.testing.assert_allclose(np.asarray(eng.matvec(x)), op.matvec_host(x),
                               atol=1e-13, rtol=1e-12)


def test_staircase_levels_reads_the_histogram():
    """staircase_levels: the form is a function of the row-nnz histogram
    alone — equal rows and tiny bases keep the plain table, spread rows
    take one level a distinct rounded column length."""
    from distributed_matvec_tpu.parallel.engine import (INDEX_TILE,
                                                        staircase_levels)

    T, n = 16, 100_000
    hist = np.zeros(T + 1, np.int64)
    hist[T] = n                              # all rows full width
    assert staircase_levels(hist, n) == (False, ((0, T, n),))
    hist = np.zeros(T + 1, np.int64)
    hist[4] = n                              # uniform narrow rows: truncate
    assert staircase_levels(hist, n) == (False, ((0, 4, n),))
    hist = np.zeros(T + 1, np.int64)
    hist[4], hist[T] = n - 10, 10            # a few wide rows over a bulk
    stair, levels = staircase_levels(hist, n)
    assert stair and levels == ((0, 4, 100_352), (4, T - 4, INDEX_TILE))
    hist = np.zeros(T + 1, np.int64)
    hist[2], hist[6] = 300, 200              # spread, but under one tile
    assert staircase_levels(hist, 512) == (False, ((0, 6, 512),))
    hist = np.zeros(T + 1, np.int64)         # pad rows only / empty basis
    hist[0] = 64
    assert staircase_levels(hist, 64) == (False, ((0, 0, 64),))
    assert staircase_levels(np.zeros(T + 1, np.int64), 0) \
        == (False, ((0, 0, 0),))


@pytest.mark.parametrize("name, batch_size, nb", [
    ("ring16", 61, 1),           # staircase, chunks that straddle levels
    ("momentum_ring18", 512, 1),     # staircase, pair-form coefficients
    ("small_momentum_ring12", 61, 1),    # plain table (under one index tile)
    ("ring16", 61, 3),           # ... cut into row blocks (12,871 rows)
    ("ring16", 1000, 5),
    ("momentum_ring18", 512, 2),
])
def test_lowmem_build_matches_onepass(name, batch_size, nb, rng, pair_form,
                                      monkeypatch):
    """The two-pass low-memory ELL build (count → pack in packed row order)
    produces the arrays of the one-pass build, level for level and, where
    the rows are cut into blocks, piece for piece, and a bit-identical
    matvec."""
    from distributed_matvec_tpu.utils.config import get_config, update_config

    spec = STAIR_RINGS.get(name) or (12, 6, None, [(_ring(12), 2)])
    op = build_heisenberg(*spec)
    op.basis.build()
    prev_budget = get_config().ell_build_budget_gb
    try:
        eng_ref = LocalEngine(op, batch_size=batch_size, mode="ell")
        if nb > 1:
            _cut_into(monkeypatch, eng_ref, nb)
            eng_ref = LocalEngine(op, batch_size=batch_size, mode="ell")
            assert len(eng_ref._ell_blocks) == nb
        update_config(ell_build_budget_gb=1e-9)   # force two-pass
        eng_lm = LocalEngine(op, batch_size=batch_size, mode="ell")
    finally:
        update_config(ell_build_budget_gb=prev_budget)
    assert (eng_ref._ell_pos_of is not None) == (name in STAIR_RINGS)
    assert eng_lm._ell_counts == {**eng_ref._ell_counts, "build_passes": 2}
    assert eng_ref._ell_counts["build_passes"] == 1
    assert len(eng_lm._ell_levels) == len(eng_ref._ell_levels)
    assert [len(b) for b in eng_lm._ell_blocks] == \
        [len(b) for b in eng_ref._ell_blocks]
    for (i_lm, c_lm), (i_ref, c_ref) in zip(eng_lm._ell_levels,
                                            eng_ref._ell_levels):
        np.testing.assert_array_equal(np.asarray(i_lm), np.asarray(i_ref))
        np.testing.assert_array_equal(np.asarray(c_lm), np.asarray(c_ref))
    if eng_ref._ell_pos_of is None:
        assert eng_lm._ell_pos_of is None
    else:
        np.testing.assert_array_equal(np.asarray(eng_lm._ell_pos_of),
                                      np.asarray(eng_ref._ell_pos_of))
    N = op.basis.number_states
    x = rng.random(N) - 0.5
    if not op.effective_is_real:
        x = x + 1j * (rng.random(N) - 0.5)
    np.testing.assert_array_equal(np.asarray(eng_ref.matvec(x)),
                                  np.asarray(eng_lm.matvec(x)))


def test_compact_mode_matches_dense(rng):
    """compact mode (sign-tagged 4 B/entry, coefficients derived as
    W·s·n(j)/n(i) at matvec time) matches the dense reference for isotropic
    Heisenberg sectors, rank-1 and rank-2, both gather paths."""
    from distributed_matvec_tpu.utils.config import get_config, update_config

    prev = get_config().split_gather
    op = build_heisenberg(12, 6, 1,
                          [([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 0], 0),
                           ([11, 10, 9, 8, 7, 6, 5, 4, 3, 2, 1, 0], 0)])
    op.basis.build()
    h = dense_effective_matrix(op)
    N = op.basis.number_states
    x = rng.random(N) - 0.5
    X = rng.random((N, 3)) - 0.5
    try:
        for sg in ("off", "on"):
            update_config(split_gather=sg)
            eng = LocalEngine(op, batch_size=61, mode="compact")
            np.testing.assert_allclose(np.asarray(eng.matvec(x)), h @ x,
                                       atol=1e-13, rtol=1e-12)
            np.testing.assert_allclose(np.asarray(eng.matvec(X)), h @ X,
                                       atol=1e-13, rtol=1e-12)
    finally:
        update_config(split_gather=prev)


def test_compact_mode_xxz_qualifies(rng):
    """Anisotropy (Δ) only rescales the DIAGONAL, so the XXZ chain keeps a
    single off-diagonal magnitude and qualifies for compact mode."""
    from distributed_matvec_tpu.models.lattices import xxz_chain

    op = xxz_chain(10, delta=0.37)
    op.basis.build()
    eng = LocalEngine(op, mode="compact")
    n = op.basis.number_states
    x = rng.random(n) - 0.5
    np.testing.assert_allclose(np.asarray(eng.matvec(x)), op.matvec_host(x),
                               atol=1e-13, rtol=1e-12)


def test_compact_mode_refusals():
    """compact mode must refuse anisotropic couplings (several off-diagonal
    magnitudes) and complex-character sectors."""
    from distributed_matvec_tpu.models.basis import SpinBasis
    from distributed_matvec_tpu.models.lattices import (chain_edges,
                                                        heisenberg_from_edges)

    b = SpinBasis(8, 4)
    op = heisenberg_from_edges(b, chain_edges(8)) \
        + 0.44 * heisenberg_from_edges(b, [(i, (i + 2) % 8)
                                           for i in range(8)])
    b.build()
    with pytest.raises(ValueError, match="single off-diagonal magnitude"):
        LocalEngine(op, mode="compact")

    b2 = SpinBasis(10, 5, None, [([1, 2, 3, 4, 5, 6, 7, 8, 9, 0], 1)])
    op2 = heisenberg_from_edges(b2, chain_edges(10))
    b2.build()
    with pytest.raises(ValueError, match="real sector"):
        LocalEngine(op2, mode="compact")


def test_structure_cache_roundtrip(tmp_path, rng):
    """ELL/compact structure checkpoints restore bit-identically and are
    keyed by a fingerprint: a different operator must NOT reuse them."""
    path = str(tmp_path / "cache.h5")
    op = build_heisenberg(12, 6, 1,
                          [([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 0], 0),
                           ([11, 10, 9, 8, 7, 6, 5, 4, 3, 2, 1, 0], 0)])
    op.basis.build()
    N = op.basis.number_states
    x = rng.random(N) - 0.5

    for mode in ("ell", "compact"):
        eng1 = LocalEngine(op, batch_size=61, mode=mode,
                           structure_cache=path)
        y1 = np.asarray(eng1.matvec(x))
        # second construction must restore, not rebuild
        import distributed_matvec_tpu.parallel.engine as E
        builder = "_build_ell" if mode == "ell" else "_build_compact"
        orig = getattr(E.LocalEngine, builder)
        def _boom(self):
            raise AssertionError("structure cache was not used")
        setattr(E.LocalEngine, builder, _boom)
        try:
            eng2 = LocalEngine(op, batch_size=61, mode=mode,
                               structure_cache=path)
        finally:
            setattr(E.LocalEngine, builder, orig)
        np.testing.assert_array_equal(y1, np.asarray(eng2.matvec(x)))

    # a different operator (scaled coupling) must invalidate the cache
    op2 = 2.0 * build_heisenberg(
        12, 6, 1, [([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 0], 0),
                   ([11, 10, 9, 8, 7, 6, 5, 4, 3, 2, 1, 0], 0)])
    op2.basis.build()
    eng3 = LocalEngine(op2, batch_size=61, mode="ell",
                       structure_cache=path)
    np.testing.assert_allclose(np.asarray(eng3.matvec(x)),
                               2.0 * np.asarray(
                                   LocalEngine(op, batch_size=61,
                                               mode="ell").matvec(x)),
                               atol=1e-13)


@pytest.mark.parametrize("nb, ranges", [(1, 1), (3, 1), (3, 3)], ids=[
    "whole", "three_row_blocks", "three_table_ranges"])
def test_structure_cache_staircase_layout(tmp_path, rng, nb, ranges,
                                          monkeypatch):
    """The staircase checkpoints and restores piece for piece (level for
    level where the rows are not cut; a near and a far staircase a range
    where the table is), with its row order and counts; a file in an older
    layout (v1: main table + tail; v2: whole levels and no blocks; v3: no
    table ranges) is refused — by fingerprint as an old build wrote it, by
    its keys should the fingerprint ever match — and rebuilt, not
    misread."""
    import hashlib

    from distributed_matvec_tpu.io.hdf5 import (load_engine_structure,
                                                save_engine_structure)
    from distributed_matvec_tpu.parallel.engine import hash_basis_operator

    path = str(tmp_path / "stair.h5")
    sidecar = LocalEngine._structure_sidecar(path)
    op, eng1 = _stair_engine("symm_ring20")
    if ranges > 1:
        _cut_table_into(monkeypatch, eng1, ranges)
    elif nb > 1:
        _cut_into(monkeypatch, eng1, nb)
    eng1 = LocalEngine(op, mode="ell", structure_cache=path)
    assert not eng1.structure_restored
    assert len(eng1._ell_blocks) == (nb if ranges == 1 else 2 * ranges)
    assert (eng1._ell_counts["row_blocks"],
            eng1._ell_counts["table_ranges"]) == (nb, ranges)
    x = rng.random(eng1.n_states) - 0.5
    y1 = np.asarray(eng1.matvec(x))
    eng2 = LocalEngine(op, mode="ell", structure_cache=path)
    assert eng2.structure_restored
    assert eng2._ell_counts == eng1._ell_counts
    assert eng2._ell_range_rows == eng1._ell_range_rows
    if ranges > 1:
        assert len(eng2._ell_pos_of) == 2 * ranges
        for p2, p1 in zip(eng2._ell_pos_of, eng1._ell_pos_of):
            assert (p2 is None) == (p1 is None)
            if p1 is not None:
                np.testing.assert_array_equal(np.asarray(p2),
                                              np.asarray(p1))
    else:
        np.testing.assert_array_equal(np.asarray(eng2._ell_pos_of),
                                      np.asarray(eng1._ell_pos_of))
    assert [len(b) for b in eng2._ell_blocks] == \
        [len(b) for b in eng1._ell_blocks]
    for (i2, c2), (i1, c1) in zip(eng2._ell_levels, eng1._ell_levels):
        np.testing.assert_array_equal(np.asarray(i2), np.asarray(i1))
        np.testing.assert_array_equal(np.asarray(c2), np.asarray(c1))
    np.testing.assert_array_equal(y1, np.asarray(eng2.matvec(x)))
    # the capacity planner reads the same file: rows, mean width, bytes
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "dmt_capacity_levels", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "capacity.py"))
    capacity = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(capacity)
    st = capacity.load_structure(sidecar)
    assert (st["mode"], st["n_padded"], st["T0"], st["pair"]) == \
        ("ell", eng1.n_padded, eng1.ell_width, False)
    assert st["table_bytes"] == eng1.ell_nbytes

    T0 = 8
    v1 = {"T0": T0, "idx": np.zeros((T0, eng1.n_padded), np.int32),
          "coeff": np.zeros((T0, eng1.n_padded))}
    v2 = {"gather_slots": 1, "live_entries": 1, "levels": 1,
          "level0_idx": np.zeros((T0, eng1.n_padded), np.int32),
          "level0_coeff": np.zeros((T0, eng1.n_padded)),
          "pos_of": np.arange(eng1.n_padded, dtype=np.int32)}
    v3 = dict(v2, block_pieces="1", row_blocks=1, gather_pieces=2,
              build_passes=1, table_bytes=16 * eng1.n_padded)
    for layout, old in (("v1", v1), ("v2", v2), ("v3", v3)):
        h = hashlib.sha256()
        hash_basis_operator(h, op)
        h.update(f"ell|False|True|{eng1.batch_size}|{eng1.n_states}"
                 f"|{eng1.n_padded}|{layout}".encode())
        assert h.hexdigest() != eng1._structure_fingerprint()
        for fingerprint in (h.hexdigest(), eng1._structure_fingerprint()):
            save_engine_structure(sidecar, fingerprint, "ell", old)
            eng3 = LocalEngine(op, mode="ell", structure_cache=path)
            assert not eng3.structure_restored
            np.testing.assert_array_equal(y1, np.asarray(eng3.matvec(x)))
            # ... and the rebuild replaced the file with the new layout
            assert "table_ranges" in load_engine_structure(
                sidecar, eng1._structure_fingerprint())


def test_structure_cache_pair_roundtrip(tmp_path, rng):
    """Pair-form (re,im)-f64 coefficient tables checkpoint and restore
    bit-identically too (complex momentum sector)."""
    from distributed_matvec_tpu.utils.config import get_config, update_config

    path = str(tmp_path / "pair.h5")
    op = build_heisenberg(12, 6, None,
                          [([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 0], 2)])
    op.basis.build()
    N = op.basis.number_states
    x = (rng.random(N) - 0.5) + 1j * (rng.random(N) - 0.5)
    prev = get_config().complex_pair
    update_config(complex_pair="on")
    try:
        e1 = LocalEngine(op, batch_size=61, mode="ell",
                         structure_cache=path)
        assert e1.pair and not e1.structure_restored
        y1 = np.asarray(e1.matvec(x))
        e2 = LocalEngine(op, batch_size=61, mode="ell",
                         structure_cache=path)
        assert e2.structure_restored
        np.testing.assert_array_equal(y1, np.asarray(e2.matvec(x)))
        # a native-c128 engine must NOT reuse the pair checkpoint
        update_config(complex_pair="off")
        e3 = LocalEngine(op, batch_size=61, mode="ell",
                         structure_cache=path)
        assert not e3.structure_restored
        np.testing.assert_allclose(np.asarray(e3.matvec(x)), y1,
                                   atol=1e-15, rtol=1e-14)
    finally:
        update_config(complex_pair=prev)


def test_ell_split_cost_model_properties():
    """choose_ell_split: scatter-heavy layouts are rejected, truncation-only
    wins are kept, and degenerate histograms fall back to the full table."""
    from distributed_matvec_tpu.parallel.engine import choose_ell_split

    T, n = 16, 1000
    # all rows full width → no split possible
    hist = np.zeros(T + 1, np.int64)
    hist[T] = n
    assert choose_ell_split(hist, n, T) == (T, 0, T)
    # uniform narrow rows → pure truncation (no tail) must be kept
    hist = np.zeros(T + 1, np.int64)
    hist[4] = n
    T0, S, Tmax = choose_ell_split(hist, n, T)
    assert (T0, S, Tmax) == (4, 0, 4)
    # a few wide rows over a narrow bulk → split with a small tail
    hist = np.zeros(T + 1, np.int64)
    hist[4] = n - 10
    hist[T] = 10
    T0, S, Tmax = choose_ell_split(hist, n, T)
    assert T0 == 4 and S == 10 and Tmax == T
    # empty basis → full-width fallback, no crash
    assert choose_ell_split(np.zeros(T + 1, np.int64), 0, T) == (T, 0, 0)


def test_ell_split_gate_uses_real_rows():
    """Padded rows (nnz=0) must not widen the tail budget: with few real
    rows among many pad rows the whole operator must NOT land in the tail."""
    from distributed_matvec_tpu.parallel.engine import choose_ell_split

    T = 10
    hist = np.zeros(T + 1, np.int64)
    hist[0] = 772       # pad rows
    hist[T] = 252       # real rows, all full width
    T0, S, Tmax = choose_ell_split(hist, 1024, T, real_rows=252)
    assert T0 == T and S == 0, "all-real-rows tail slipped past the gate"


def test_split_gather_matches_plain(rng):
    """Forcing the triple-f32 split-gather path (ops/split_gather.py) must
    reproduce the plain-gather matvec to the last ulp — f64 and complex
    sectors, rank-1 and rank-2, ell and fused modes.  (The split/join itself
    is exact; the residual ~1-ulp wiggle comes from XLA fusing the two
    separately compiled programs differently, e.g. CPU FMA contraction.)"""
    from distributed_matvec_tpu.utils.config import update_config

    cases = [
        build_heisenberg(12, 6, None),                       # f64
        build_heisenberg(10, 5, None, [([*range(1, 10), 0], 1)]),  # c128
    ]
    for op in cases:
        op.basis.build()
        n = op.basis.number_states
        x = rng.random(n) - 0.5
        X = np.stack([x, rng.random(n) - 0.5], axis=1)
        for mode in ("ell", "fused"):
            update_config(split_gather="off")
            ref_eng = LocalEngine(op, mode=mode)
            y_ref = np.asarray(ref_eng.matvec(x))
            Y_ref = np.asarray(ref_eng.matvec(X))
            update_config(split_gather="on")
            try:
                eng = LocalEngine(op, mode=mode)
                y = np.asarray(eng.matvec(x))
                Y = np.asarray(eng.matvec(X))
            finally:
                update_config(split_gather="auto")
            np.testing.assert_allclose(y, y_ref, atol=1e-14, rtol=1e-14)
            np.testing.assert_allclose(Y, Y_ref, atol=1e-14, rtol=1e-14)


def test_complex_on_tpu_guard(monkeypatch):
    """Complex sectors must fail LOUDLY on a TPU backend (this platform's
    compiler hangs on any complex128 program) — not hang for hours; the
    allow_complex_on_tpu knob bypasses the guard."""
    import jax

    from distributed_matvec_tpu.parallel.engine import check_complex_backend
    from distributed_matvec_tpu.utils.config import update_config

    from distributed_matvec_tpu.utils.config import get_config

    check_complex_backend(True)                  # real: never gated
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="complex128.*TPU"):
        check_complex_backend(False)
    check_complex_backend(False, platform="cpu")  # CPU mesh on TPU host: ok
    prev = get_config().allow_complex_on_tpu
    update_config(allow_complex_on_tpu=True)
    try:
        check_complex_backend(False)             # override allows
    finally:
        update_config(allow_complex_on_tpu=prev)


def test_traced_matvec_validates_via_callback():
    """A caller that only ever runs ``engine.matvec`` under its own jit
    (no eager probe) must still get loud sector-violation detection: a
    one-time RuntimeWarning at trace time, run-time validation through
    ``jax.debug.callback``, and a sticky failure re-raised by the next
    eager matvec even when the runtime swallows the callback exception."""
    import time

    import jax

    from distributed_matvec_tpu.models.operator import Operator

    basis = SpinBasis(6, 3)
    op = Operator.from_expressions(basis, [("σˣ₀", [[0], [1]])])
    basis.build()
    eng = LocalEngine(op, mode="fused")
    x = np.ones(basis.number_states)
    with pytest.warns(RuntimeWarning, match="traced before any eager"):
        try:
            jax.block_until_ready(jax.jit(eng.matvec)(x))
        except Exception:
            pass            # the callback's own exception may surface here
    deadline = time.time() + 10         # callbacks may complete async
    while eng._deferred_failure is None and time.time() < deadline:
        time.sleep(0.05)
    with pytest.raises(RuntimeError, match="outside the basis"):
        eng.matvec(x)


def test_traced_matvec_callback_marks_checked(rng):
    """The positive side: a VALID operator traced first validates through
    the callback and marks the engine checked — later eager calls skip
    re-validation and match the eager result."""
    import time

    import jax

    op = build_heisenberg(10, 5)
    op.basis.build()
    eng = LocalEngine(op, mode="fused", batch_size=32)
    x = rng.random(op.basis.number_states) - 0.5
    with pytest.warns(RuntimeWarning, match="traced before any eager"):
        y = np.asarray(jax.jit(eng.matvec)(x))
    deadline = time.time() + 10
    while not eng._checked and time.time() < deadline:
        time.sleep(0.05)
    assert eng._checked and eng._deferred_failure is None
    np.testing.assert_allclose(y, np.asarray(eng.matvec(x)),
                               atol=ATOL, rtol=RTOL)
