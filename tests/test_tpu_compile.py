"""Ask the chip's compiler, without the chip.

The TPU compiler is installed in the CPU sandbox and compiles for a device
that is described, not attached (``/opt/skills/guides/on-chip-measurement``
§2).  These tests lower the programs of the main path — the ones
``chip_smoke.py`` runs on the chip — for a described ``v5e:2x2`` at the
shapes of ``heisenberg_chain_32_symm`` (4,707,969 states, |G| = 128; shapes
read off a CPU build of that engine) and check each program's memory against
the 16 GB of one v5e chip.  Nothing runs, so they say nothing about results
or times; they catch what the compiler refuses before chip time is spent.

``jax.default_backend()`` is ``cpu`` here, so the knobs whose ``auto`` value
follows the backend (``split_gather``, ``complex_pair``) are steered to
their TPU values in the test, not through an option of the program.

Rules this file keeps (the guide's): the topology is described inside a
module-scoped fixture that skips when it cannot be, nothing TPU-related
happens at import or collection time, every compile happens in the test's
own process, the compile cache is off around the compiles (a described-
device executable can be written to it but not read back), and all such
tests live in this one file so one xdist worker holds the TPU library.
"""

import contextlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from distributed_matvec_tpu.utils.config import update_config

HBM_BYTES = 16 * 1024 ** 3          # one TPU v5e chip
FULL_YAML = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "data", "heisenberg_chain_32_symm.yaml")

# heisenberg_chain_32_symm as the engines build it (CPU build from
# data/heisenberg_chain_32_symm.yaml): 32 off-diagonal terms and a bucketed
# lookup with a 2^24 directory.  LocalEngine: the staircase levels
# ``(columns, rows)``, rows ordered by non-zero count and each column cut to
# the rows that reach it (a row's count is its domain walls: 2, 4, ... 32),
# rounded up to 1024; the engine's own rule then cuts the packed rows into
# blocks (``gather_row_blocks``) and the levels into a piece a block.
# DistributedEngine: a main ELL table of width 20 over the padded rows and a
# tail of 249,601 wide rows × 12, hash-sharded.
N = 4_707_969
N_PAD = 4_718_592
LEVELS = [(6, 4_708_352), (2, 4_707_328), (2, 4_694_016), (2, 4_600_832),
          (2, 4_224_000), (2, 3_326_976), (2, 2_030_592), (2, 878_592),
          (2, 249_856), (2, 44_032), (2, 5_120), (6, 1_024)]
# the benchmark's other histogram, heisenberg_square_5x5 (PERF.md §4), and a
# pair-form basis of half the chain's rows, each level half as long: the
# chain's own pair-form table (32 B a row, 151 MB) leaves the rule no room
HISTOGRAMS = {
    "chain_32_symm": (N, N_PAD, LEVELS),
    "square_5x5": (5_200_300, 5_242_880, [
        (14, 5_200_896), (2, 5_199_872), (2, 5_185_536), (2, 5_120_000),
        (2, 4_873_216), (2, 4_267_008), (2, 3_198_976), (2, 1_949_696),
        (2, 921_600), (2, 349_184), (2, 108_544), (2, 28_672), (2, 6_144),
        (2, 1_024)]),
    "half_chain": (2_353_985, 2_359_296,
                   [(k, -(-L // 2048) * 1024) for k, L in LEVELS]),
    # heisenberg_chain_28 as whole levels (tests/test_chain_28_config.py
    # pins them): what the two-pass build packed there before the table
    # was cut (PR 33; the apply's shapes since: ``_chain_28_ranges``)
    "chain_28": (40_116_600, 40_173_568, [
        (4, 40_117_248), (2, 40_115_200), (2, 40_057_856), (2, 39_485_440),
        (2, 36_622_336), (2, 28_893_184), (2, 17_114_112), (2, 6_807_552),
        (2, 1_654_784), (2, 223_232), (2, 15_360), (4, 1_024)]),
}
T, T0, T_TAIL, S_TAIL = 32, 20, 12, 249_601
CHUNK = 1 << 16
LK_DIR, LK_SHIFT, LK_PROBES = (1 << 24) + 1, 8, 6
M_CAP = 96                          # apps/diagonalize.py's default Krylov cap
# chain_32_k1 on four chips (the benchmark's chain_32_k1_x4): the plan's
# shapes read off the plain reference's matrix under the partition's hash
# (my CPU run, PR 39) and, where they differ, the chip's (the build span,
# my chip run, PR 39): M rows a shard, a query capacity C a peer, an ELL
# table 20 wide and a tail of 124,080 rows 10 wide, pair rows
K1_X4 = dict(M=2_348_928, C=2_320_800, T0=20, tail=(10, 124_080), pair=True)
# chain_32_symm on four chips (the benchmark's chain_32_symm_x4): M and C as
# the chip's plan build gives them (its packed exchange's gather read 4 C =
# 4,651,488 values from M rows): C / M = 0.988, where the plan all-gathers
SYMM_X4 = dict(M=1_177_216, C=1_162_872)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_knobs():
    """The values ``auto`` resolves to on a TPU backend."""
    update_config(split_gather="on", complex_pair="on")
    yield
    update_config(split_gather="auto", complex_pair="auto")


def _shapes(sharding):
    """``S(shape, dtype=f64)`` → a ShapeDtypeStruct placed by ``sharding``."""
    def S(shape, dtype=jnp.float64):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return S


def _fits(compiled, what):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < HBM_BYTES, f"{what}: {total / 2**30:.2f} GiB > 16 GiB"
    return total


def _pieces(histogram, pair=False, whole=False):
    """``(rows of every level piece, rows of every un-permute piece)`` at a
    histogram's shapes: the engine's own rule and cut, or, ``whole``, the
    levels as the parent of PR 31 stored and gathered them."""
    from distributed_matvec_tpu.parallel.engine import (block_pieces,
                                                        gather_row_blocks)

    _, n_pad, levels = HISTOGRAMS[histogram]
    if whole:
        return [[(k, L) for k, L in levels]], [n_pad]
    _, B = gather_row_blocks(n_pad, 6 if pair else 3)
    plan = block_pieces(tuple((0, k, L) for k, L in levels), B)
    return ([[(levels[li][0], rows) for li, _, rows in blk] for blk in plan],
            [min(B, n_pad - r0) for r0 in range(0, n_pad, B)])


def _local_ell_engine(sh, pair, histogram="chain_32_symm", whole=False,
                      columns=None):
    """A LocalEngine shell carrying a histogram's ELL shapes — the apply
    program depends on nothing else of the engine — and one vector for it
    (``columns`` of them in a batch)."""
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    S = _shapes(sh)
    n, n_pad, _ = HISTOGRAMS[histogram]
    ctail = (2,) if pair else ()
    eng = object.__new__(LocalEngine)
    eng.n_states, eng.n_padded = n, n_pad
    eng.pair, eng._dtype = pair, jnp.float64
    eng._ell_blocks = tuple(
        tuple((S((k, rows), jnp.int32), S((k, rows) + ctail, jnp.float64))
              for k, rows in blk)
        for blk in _pieces(histogram, pair, whole)[0])
    eng._ell_pos_of = S((n_pad,), jnp.int32)
    eng._ell_range_rows = 0             # the gather table is not cut
    eng._diag = S((n_pad,), jnp.float64)
    eng._make_ell_matvec()
    batch = () if columns is None else (columns,)
    return eng, S((n,) + batch + ctail, jnp.float64)


def _table_ranges(config, parts):
    """``(n, n_pad, W, staircases)`` of a configuration whose gather table
    is cut (``chain_28``; ``chain_32_k1``, whose pair-form rows of six
    ``parts`` halve a range): the states, the padded rows, the range length
    the engine's rule gives and, range by range, near then far, each
    staircase's ``(rows, ((columns, rows), ...))``, read off the
    independently counted histograms in ``tests/data`` (the same the
    engine's build reads off its own counts)."""
    import json

    from distributed_matvec_tpu.parallel.engine import (gather_table_ranges,
                                                        staircase_levels)

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", config + "_ranges.json")) as f:
        data = json.load(f)
    n_pad = data["n_padded"]
    R, W = gather_table_ranges(n_pad, parts)
    assert (R, W) == (data["ranges"], data["range_rows"])
    out = []
    for r in range(R):
        rows = min(W, n_pad - r * W)
        for kind in ("near", "far"):
            stair, levels = staircase_levels(np.array(data[kind][r]), rows)
            assert stair
            out.append((rows, tuple((k, L) for _, k, L in levels)))
    return data["n_states"], n_pad, W, out


def _chain_28_ranges():
    """``(W, staircases)`` of chain_28 with its gather table cut."""
    return _table_ranges("chain_28", 3)[2:]


def _range_engine(sh, config, pair=False, keep=None):
    """The LocalEngine shell of :func:`_local_ell_engine` at the shapes of
    a configuration above the VMEM line: two staircases a table range (in
    pair form where ``pair``).  ``keep`` empties every other range (no
    level, no un-permute; ``x`` keeps its length, so the far gathers'
    table is what it is in the whole program): the kept ranges' gathers at
    a fraction of the compile."""
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    S = _shapes(sh)
    n, n_pad, W, staircases = _table_ranges(config, 6 if pair else 3)
    if keep is not None:
        staircases = [st if j // 2 in keep else (st[0], ())
                      for j, st in enumerate(staircases)]
    ctail = (2,) if pair else ()
    eng = object.__new__(LocalEngine)
    eng.n_states, eng.n_padded = n, n_pad
    eng.pair, eng._dtype = pair, jnp.float64
    eng._ell_blocks = tuple(
        tuple((S((k, L), jnp.int32), S((k, L) + ctail, jnp.float64))
              for k, L in levels) for _, levels in staircases)
    eng._ell_pos_of = tuple(S((rows,), jnp.int32) if levels else None
                            for rows, levels in staircases)
    eng._ell_range_rows = W
    eng._diag = S((n_pad,), jnp.float64)
    eng._make_ell_matvec()
    return eng, S((n,) + ctail, jnp.float64)


def _distributed_ell_engine(topo, M=None, C=None, T0=T0, tail=None,
                            pair=False):
    """A DistributedEngine shell carrying chain_32_symm's hash-sharded ELL
    shapes (or the ones given: ``K1_X4``) on a 4-device mesh built from the
    described devices, the ``S(shape, dtype)`` that places a vector on it,
    and the mesh.  The exchange takes the form the plan's rule gives for
    (D, M, C): packed at the default C = M / 2, gathered at the cells' C
    (98.8% of M)."""
    from distributed_matvec_tpu.parallel.distributed import (
        DistributedEngine, exchange_gathers)
    from distributed_matvec_tpu.parallel.mesh import SHARD_AXIS

    D = 4
    mesh = Mesh(np.array(topo.devices[:D]), (SHARD_AXIS,))

    def S(shape, dtype):
        spec = P(SHARD_AXIS, *([None] * (len(shape) - 1)))
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    # shard rows incl. hash imbalance, and the per-peer query capacity
    M = M or -(-N // D // 1024) * 1024 + 4096
    C = C or M // 2
    t_tail, s_tail = tail or (T_TAIL, S_TAIL // D + 1024)
    pt = (2,) if pair else ()               # (re, im) pair rows
    eng = object.__new__(DistributedEngine)
    eng.n_devices, eng.query_capacity, eng.mesh = D, C, mesh
    eng.pair, eng._dtype, eng._ell_T0 = pair, jnp.float64, T0
    gathered = exchange_gathers(D, M, C)
    eng.exchange_form = "gathered" if gathered else "packed"
    eng._qin = None if gathered else S((D, D, C), jnp.int32)
    eng._ell_idx = S((D, T0, M), jnp.int32)
    eng._ell_coeff = S((D, T0, M) + pt, jnp.float64)
    eng._diag = S((D, M), jnp.float64)
    eng._ell_tail = (S((D, s_tail), jnp.int32),
                     S((D, t_tail, s_tail), jnp.int32),
                     S((D, t_tail, s_tail) + pt, jnp.float64))
    eng._make_ell_matvec()
    return eng, S((D, M) + pt, jnp.float64), mesh


def _compile(name, topo):
    """One program of the main path, compiled for the described chip(s):
    ``ell_apply``, ``window`` and ``full`` on one chip (``<program>@<key of
    HISTOGRAMS>`` at another histogram than the chain's), ``distributed_apply``
    (packed), ``distributed_apply_gathered`` (the cells' C / M of 0.988) and
    ``distributed_window`` on the 4-device mesh."""
    from distributed_matvec_tpu.solve.lanczos import (
        _buffer_rows, _make_block_runner, _make_window_runner)

    if name.startswith("distributed"):
        from distributed_matvec_tpu.parallel.mesh import SHARD_AXIS

        if name == "distributed_apply_gathered":
            eng, x, mesh = _distributed_ell_engine(topo, **SYMM_X4)
            assert eng.exchange_form == "gathered"
        else:
            eng, x, mesh = _distributed_ell_engine(topo)
            assert eng.exchange_form == "packed"
        if name.startswith("distributed_apply"):
            return jax.jit(eng._apply_fn).lower(x, eng._operands).compile()

        # the Lanczos window over hashed [D, M] vectors that
        # `apps/diagonalize.py --devices 4` runs, Krylov buffer sharded
        # with them
        def mv(v, ops):
            return eng._apply_fn(v, ops)[0].astype(jnp.float64)

        rep = NamedSharding(mesh, P())
        V = jax.ShapeDtypeStruct(
            (_buffer_rows(M_CAP),) + x.shape, jnp.float64,
            sharding=NamedSharding(mesh, P(None, SHARD_AXIS, None)))
        ab = jax.ShapeDtypeStruct((M_CAP,), jnp.float64, sharding=rep)
        om = jax.ShapeDtypeStruct((M_CAP + 1,), jnp.float64, sharding=rep)
        f64 = jax.ShapeDtypeStruct((), jnp.float64, sharding=rep)
        m0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
        fn = _make_window_runner(mv, M_CAP, x.shape, jnp.float64, 2, 16)
        return fn.lower(V, ab, ab, m0, (om, om, f64, f64),
                        eng._operands).compile()

    sh = SingleDeviceSharding(topo.devices[0])
    name, _, histogram = name.partition("@")
    eng, x = _range_engine(sh, "chain_28") if histogram == "chain_28" else \
        _local_ell_engine(sh, False, histogram or "chain_32_symm")
    apply_fn, operands = eng.bound_matvec()
    if name == "ell_apply":
        return jax.jit(apply_fn).lower(x, operands).compile()

    def mv(v, ops):
        return apply_fn(v, ops)[0].astype(jnp.float64)

    S = _shapes(sh)
    n = eng.n_states
    V = S((_buffer_rows(M_CAP), n))
    ab, i32 = S((M_CAP,)), S((), jnp.int32)
    if name == "window":
        # the host tracker's state rides beside (alpha, beta): its two
        # omega rows, its epsilon and its limit
        omega = (S((M_CAP + 1,)), S((M_CAP + 1,)), S(()), S(()))
        fn = _make_window_runner(mv, M_CAP, (n,), jnp.float64, 2, 16)
        return fn.lower(V, ab, ab, i32, omega, operands).compile()
    assert name == "full", name
    fn = _make_block_runner(mv, M_CAP, (n,), jnp.float64, 2)
    return fn.lower(V, ab, ab, i32, i32, operands).compile()


@pytest.fixture(scope="module")
def compiled(topo):
    """``compiled(name)``: :func:`_compile`, once a module — the memory
    tests and the scope tests read the same executables."""
    done = {}

    def get(name):
        if name not in done:
            done[name] = _compile(name, topo)
        return done[name]
    return get


@pytest.mark.parametrize("pair", [False, True],
                         ids=["real_sector", "re_im_pair_momentum_sector"])
def test_ell_apply_compiles(one_chip, tpu_knobs, compiled, pair):
    """The LocalEngine ELL apply with triple-f32 split gathers — and its
    (re, im)-pair form, which complex momentum sectors take on a TPU."""
    if not pair:
        exe = compiled("ell_apply")
    else:
        eng, x = _local_ell_engine(one_chip, pair)
        apply_fn, operands = eng.bound_matvec()
        exe = jax.jit(apply_fn).lower(x, operands).compile()
    _fits(exe, "ell apply")
    _gathers_the_staircase(exe, pair=pair)


def _gathers_the_staircase(exe, histogram="chain_32_symm", pair=False,
                           whole=False):
    """The optimised HLO gathers ``x``'s f32 parts once a piece of a level
    (the body of the ``lax.scan`` over the piece's columns), at that piece's
    length, and the accumulator's once a row block — and scatters nothing
    (the two-level format's tail did)."""
    text = exe.as_text()
    assert "scatter" not in text
    gathered = [int(rows) for rows in re.findall(
        rf"= f32\[(\d+),{6 if pair else 3}\]\S* gather\(", text)]
    blocks, unpermute = _pieces(histogram, pair, whole)
    assert sorted(gathered) == sorted(
        [rows for blk in blocks for _, rows in blk] + unpermute)


def _gather_results(exe, parts=3):
    """``(rows, in VMEM)`` of every gather fusion of the optimised HLO whose
    result is ``f32[rows, parts]``: memory space ``S(1)`` in the result's
    layout is the chip's VMEM, none is HBM."""
    found = []
    for line in exe.as_text().splitlines():
        m = re.search(rf"= f32\[(\d+),{parts}\](\{{[^}}]*\}}) fusion\(", line)
        if m and "kind=kCustom" in line:
            found.append((int(m.group(1)), "S(1)" in m.group(2)))
    return found


@pytest.mark.parametrize("histogram", ["chain_32_symm", "square_5x5"])
@pytest.mark.parametrize("program", ["ell_apply", "window", "full"])
def test_every_gather_writes_to_vmem(tpu_knobs, compiled, program,
                                     histogram):
    """Where a row gather's result lives decides its rate on a v5e: 4.32 ns
    a slot in VMEM, 6.06 in HBM (PERF.md §6, PR 31).  With the packed rows
    cut into blocks by the engine's own rule, every gather of the apply —
    a piece of a level, a block of the un-permute — writes to VMEM, alone
    and nested in the solver's block programs, at both of the benchmark's
    histograms (2 blocks of 2,359,296 rows and 3 of 1,747,968)."""
    exe = compiled(program if histogram == "chain_32_symm"
                   else f"{program}@{histogram}")
    _fits(exe, f"{program} at {histogram}")
    _gathers_the_staircase(exe, histogram)
    results = _gather_results(exe)
    blocks, unpermute = _pieces(histogram)
    assert len(blocks) == {"chain_32_symm": 2, "square_5x5": 3}[histogram]
    assert len(results) == sum(map(len, blocks)) + len(unpermute)
    assert [rows for rows, in_vmem in results if not in_vmem] == []


@pytest.mark.parametrize("histogram, pair, in_hbm", [
    ("chain_32_symm", False, 7), ("square_5x5", False, 8),
    ("half_chain", True, 7)])
def test_whole_levels_gather_to_hbm(one_chip, tpu_knobs, histogram, pair,
                                    in_hbm):
    """The test above can fail: the levels as PR 31's parent kept them (a
    whole level a gather; the shape lists above) leave the results of the
    long gathers in HBM — every level of 3.2 Mrows and more and the
    un-permute gather, the ones the trace read at 6.06 ns a slot — and
    only the short ones in VMEM."""
    eng, x = _local_ell_engine(one_chip, pair, histogram, whole=True)
    apply_fn, operands = eng.bound_matvec()
    exe = jax.jit(apply_fn).lower(x, operands).compile()
    _gathers_the_staircase(exe, histogram, pair, whole=True)
    results = _gather_results(exe, parts=6 if pair else 3)
    assert len(results) == len(HISTOGRAMS[histogram][2]) + 1
    rows_hbm = sorted(rows for rows, in_vmem in results if not in_vmem)
    assert len(rows_hbm) == in_hbm
    assert min(rows_hbm) > max(
        [rows for rows, in_vmem in results if in_vmem])


def _gather_operands(exe, parts=3):
    """``(rows, table rows, table in VMEM, indices in VMEM, result in
    VMEM, un-permute)`` of every gather fusion of the optimised HLO whose
    result is ``f32[rows, parts]``: the placement of its two operands read
    off the instructions that define them."""
    text = exe.as_text()
    layout = dict(re.findall(r"(%[\w.\-]+) = (\S+) ", text))
    found = []
    for line in text.splitlines():
        m = re.search(rf"= f32\[(\d+),{parts}\](\{{[^}}]*\}}) "
                      r"fusion\(([^)]*)\)", line)
        if m and "kind=kCustom" in line:
            table, index = (layout[o.strip()]
                            for o in m.group(3).split(",")[:2])
            found.append((int(m.group(1)),
                          int(re.match(r"f32\[(\d+),", table).group(1)),
                          "S(1)" in table, "S(1)" in index,
                          "S(1)" in m.group(2), "apply/unpermute" in line))
    return found


def test_chain_28_gathers_its_near_entries_from_vmem(tpu_knobs, compiled):
    """Above 7.44 M rows ``x`` cannot be a gather table in VMEM, so the
    table is cut (PR 33): 12 ranges of 3,348,480 rows at chain_28, each a
    near staircase gathered from its own range of ``x`` and a far one
    gathered from whole ``x``, every column a gather of its own (PR 36: no
    loop over a level's columns is left, 318 before; the three ``while``
    the program keeps are the compiler's own, over the split parts of
    whole ``x``).  In the optimised HLO for a described v5e every one of
    the 414 gathers writes to VMEM; the 274 near gathers and the 24 that
    put a range's sums back in range order read a table in VMEM (a range
    of ``x``, an accumulator of a range's rows); the 116 far gathers read
    the 642 MB table in HBM, named as such.  Indices are in VMEM too, but
    for the near and un-permute gathers of 3.23 Mrows and more, which
    stream them from HBM as chain_32_symm's 2,359,296-row pieces do (4.317
    ns a slot there: PERF.md §5).  The apply's temporaries, 1.9 GB beside
    8.1 GB of arguments, fit the chip."""
    exe = compiled("ell_apply@chain_28")
    assert _fits(exe, "ell apply at chain_28") < 11.0e9
    assert 1.6e9 < exe.memory_analysis().temp_size_in_bytes < 2.4e9
    assert "scatter" not in exe.as_text()
    W, staircases = _chain_28_ranges()
    n = HISTOGRAMS["chain_28"][0]
    gathers = _gather_operands(exe)
    want = sorted([L for _, levels in staircases for k, L in levels
                   for _ in range(k)] + [rows for rows, _ in staircases])
    assert len(want) == 390 + 24
    assert sorted(g[0] for g in gathers) == want
    assert all(result for *_, result, _ in gathers)
    near = [g for g in gathers if g[1] <= W]
    far = [g for g in gathers if g[1] > W]
    assert sorted(g[0] for g in far) == sorted(
        L for _, levels in staircases[1::2] for k, L in levels
        for _ in range(k))
    assert len(near) == 274 + 24 and len(far) == 116
    assert sum(g[5] for g in gathers) == 24 == sum(g[5] for g in near)
    # the near tables: a range of x (the last one ends with the states),
    # or the accumulator of a range's rows; the far one: whole x, in HBM
    assert {g[1] for g in near} <= {W, n - 11 * W, 40_173_568 - 11 * W}
    assert all(table for _, _, table, *_ in near)
    assert {(g[1], g[2]) for g in far} == {(n, False)}
    assert not re.search(r"f32\[40\d{6},3\]\{[^}]*S\(1\)", exe.as_text())
    # one gather a column (a loop would be one a level: 294 + 24), and the
    # first near levels' 49 columns stream their indices one by one, as
    # the one-column levels beside them do (82 streamed before: 12 + 46)
    streamed = [g for g in gathers if not g[3]]
    assert len(streamed) == 119 and min(g[0] for g in streamed) > 3_220_000
    assert all(g[1] <= W for g in streamed)
    assert sum(g[5] for g in streamed) == 24
    assert max(g[0] for g in near if g[3]) < 3_220_000


def _chain_32_k1_placement(exe, keep=None):
    """``(un-permutes, near, far, W, first)`` of a chain_32_k1 apply's
    gathers (:func:`_gather_operands`; every range, or those of ``keep``),
    checked for what holds in either form of the term loop: nothing
    scattered, every result and every index array in VMEM, the un-permutes
    (full length, the accumulator as table) with their table there too,
    the far ones reading whole ``x`` in HBM, the near tables a range of
    ``x``.  ``first``: each kept range's first near level, the only long
    level with more than one column."""
    assert "scatter" not in exe.as_text()
    n, n_pad, W, staircases = _table_ranges("chain_32_k1", 6)
    ranges = range(6) if keep is None else keep
    gathers = _gather_operands(exe, parts=6)
    assert all(result and index for _, _, _, index, result, _ in gathers)
    unpermute = [g for g in gathers if g[5]]
    near = [g for g in gathers if g[1] <= W and not g[5]]
    far = [g for g in gathers if g[1] > W]
    assert {g[:3] for g in unpermute} == {(W, W, True)}
    assert len(unpermute) == 2 * len(ranges)
    assert {(g[1], g[2]) for g in far} == {(n, False)}
    assert {g[1] for g in near} == {W} | ({W - (n_pad - n)} if 5 in ranges
                                          else set())
    return (unpermute, near, far, W,
            [staircases[2 * r][1][0] for r in ranges])


@pytest.mark.parametrize("form", [
    "auto", pytest.param("scan", marks=pytest.mark.slow)])
def test_chain_32_k1_first_near_levels_in_two_ranges(one_chip, tpu_knobs,
                                                     form):
    """The mend of PR 36 and the finding of PR 35 on a shell of two of
    chain_32_k1's six ranges (ranges 0 and 1, the other four emptied; 100 s
    a compile where the whole apply takes five minutes, so tier-1 guards
    the placement).  A range's first near level, ``(6, 1,572,864)``, is its
    only long level with more than one column.  Unrolled (``auto`` where
    the table is cut) there is no ``while`` and each of the twelve columns
    is a gather whose table, indices and result are all in ``S(1)``, as
    every other near gather's and the four un-permutes' are: 66 gathers, no
    table in HBM but whole ``x``.  Under ``lax.scan`` (the ``term_loop``
    hook; what every engine ran until PR 36; marked slow, the test above
    can fail) the level's table, a range of ``x``, reaches the gather as an
    element of the loop's tuple and stays in HBM: 50 gathers, the two first
    levels' tables in HBM (and in this shell one shorter level's, 988,160
    rows), every other near table in VMEM."""
    update_config(term_loop=form)
    try:
        eng, x = _range_engine(one_chip, "chain_32_k1", pair=True,
                               keep=(0, 1))
        apply_fn, operands = eng.bound_matvec()
        exe = jax.jit(apply_fn).lower(x, operands).compile()
    finally:
        update_config(term_loop="auto")
    _, near, far, W, first = _chain_32_k1_placement(exe, (0, 1))
    assert first == [(6, W)] * 2
    in_hbm = [g[:2] for g in near if not g[2]]
    if form == "scan":
        assert " while(" in exe.as_text()
        assert (len(near), len(far)) == (31, 15)
        assert sorted(in_hbm)[-2:] == [(W, W)] * 2
        assert set(in_hbm) <= {(W, W), (988_160, W)}
        assert max(g[0] for g in near if g[2]) == 1_571_840
    else:
        assert " while(" not in exe.as_text()
        assert (len(near), len(far)) == (42, 20)
        assert in_hbm == []
        assert sum(g[0] == W for g in near) == 12


@pytest.mark.slow
def test_chain_32_k1_gathers_its_near_entries_from_vmem(one_chip, tpu_knobs):
    """chain_32_k1's table is cut into 6 ranges of 1,572,864 rows of 32 B,
    which the rule counts as a table, a gather's rows and their indices at
    once inside 118 MiB (68 B a row, 107 MB).  Until PR 36 the first near
    level of every range, the only levels with more than one column, ran
    under ``lax.scan`` and read its table from HBM (38 column gathers an
    apply, 59.4 M of its 146.4 M near and un-permute rows, at 12-15 ns a
    row for 3.3: PERF.md §6); every column is now a gather of its own.  In
    the optimised HLO for a described v5e there is no ``while``, all 216
    gathers write to VMEM and read their indices there, the 137 near ones
    and the 12 that put a range's sums back in range order read their table
    there too, and the 67 far ones read whole ``x`` in HBM, as at chain_28.
    Marked slow: the compile takes five minutes on the CPU, twice
    chain_28's; two ranges of it are compiled in tier-1
    (``test_chain_32_k1_first_near_levels_in_two_ranges``)."""
    eng, x = _range_engine(one_chip, "chain_32_k1", pair=True)
    apply_fn, operands = eng.bound_matvec()
    exe = jax.jit(apply_fn).lower(x, operands).compile()
    assert _fits(exe, "ell apply at chain_32_k1") < 6.0e9
    assert 0.9e9 < exe.memory_analysis().temp_size_in_bytes < 1.4e9
    assert " while(" not in exe.as_text()
    unpermute, near, far, W, first = _chain_32_k1_placement(exe)
    _, _, _, staircases = _table_ranges("chain_32_k1", 6)
    want = sorted([L for _, levels in staircases for k, L in levels
                   for _ in range(k)] + [rows for rows, _ in staircases])
    assert len(want) == 204 + 12
    assert sorted(g[0] for g in unpermute + near + far) == want
    assert (len(unpermute), len(near), len(far)) == (12, 137, 67)
    # no near table is left in HBM, the 38 first-level columns' least
    assert all(table for _, _, table, *_ in near)
    assert sorted(first) == [(6, W)] * 4 + [(7, 1_526_784), (7, W)]
    assert sum(k * L for k, L in first) == 59_446_272
    assert sum(g[0] in (W, 1_526_784) for g in near) == 38


def test_range_build_chunk_compiles_at_chain_28(one_chip):
    """One ``ell_range_chunk`` step of the build above the VMEM line at
    chain_28's shapes (28 terms, no group, chunks of 65,536 rows): the
    kernels, the lookup and the near / far / dead pack of a chunk's rows,
    whose slabs go to the host.  It holds no range-wide table: under a
    quarter of a GB of temporaries beside its arguments."""
    from functools import partial

    from distributed_matvec_tpu.models.yaml_io import load_config_from_yaml
    from distributed_matvec_tpu.ops import kernels as K
    from distributed_matvec_tpu.parallel.engine import _range_chunk

    S = _shapes(one_chip)
    n = HISTOGRAMS["chain_28"][0]
    op = load_config_from_yaml(
        FULL_YAML.replace("chain_32_symm", "chain_28")).hamiltonian
    tables = jax.tree_util.tree_map(
        lambda a: S(a.shape, a.dtype), K.device_tables(op, pair=False))
    exe = jax.jit(partial(_range_chunk, shift=LK_SHIFT, probes=LK_PROBES,
                          is_pair=False)).lower(
        tables, S((n, 2), jnp.uint32), S(((1 << 20) + 1,), jnp.int32),
        S((CHUNK,), jnp.uint64), S((CHUNK,), jnp.float64),
        S((), jnp.int32), S((), jnp.int32)).compile()
    _fits(exe, "ell_range_chunk at chain_28")
    m = exe.memory_analysis()
    assert m.temp_size_in_bytes < 0.25e9
    # the slabs: 28 x 65,536 indices, coefficients and two counts a row
    assert 28 * CHUNK * 12 + 2 * CHUNK * 4 <= m.output_size_in_bytes \
        < 1.2 * (28 * CHUNK * 12 + 2 * CHUNK * 4)


def test_pair_form_blocks_gather_to_vmem(one_chip, tpu_knobs):
    """A pair-form engine's gathered row is six f32 parts in eight lanes,
    32 B, so the rule gives it shorter blocks: at half the chain's rows, 2
    of 1,179,648, and every gather's result in VMEM."""
    eng, x = _local_ell_engine(one_chip, True, "half_chain")
    apply_fn, operands = eng.bound_matvec()
    exe = jax.jit(apply_fn).lower(x, operands).compile()
    _fits(exe, "pair-form ell apply")
    _gathers_the_staircase(exe, "half_chain", pair=True)
    blocks, unpermute = _pieces("half_chain", pair=True)
    assert unpermute == [1_179_648] * 2
    results = _gather_results(exe, parts=6)
    assert len(results) == sum(map(len, blocks)) + 2
    assert all(in_vmem for _, in_vmem in results)


def test_batched_apply_runs_the_blocks_cut_for_one_vector(one_chip,
                                                          tpu_knobs):
    """The cut is made at build for one vector; a two-column ``x`` gathers
    rows of six parts through the same blocks.  Held to fitting the chip
    only: the placement it gets is PERF.md §7's to record."""
    eng, x = _local_ell_engine(one_chip, False, columns=2)
    apply_fn, operands = eng.bound_matvec()
    exe = jax.jit(apply_fn).lower(x, operands).compile()
    _fits(exe, "two-column ell apply")
    results = _gather_results(exe, parts=6)
    assert len(results) == sum(map(len, _pieces("chain_32_symm")[0])) + 2


def test_structure_build_chunk_compiles(one_chip):
    """One ``ell_fill_chunk`` step: the |G| = 128 orbit scan over a 65,536-
    row chunk, the u64 bucketed basis lookup, the left-pack of the chunk's
    rows and the update of the donated full-width tables and row counts."""
    from functools import partial

    from distributed_matvec_tpu.models.yaml_io import load_config_from_yaml
    from distributed_matvec_tpu.ops import kernels as K
    from distributed_matvec_tpu.parallel.engine import _ell_fill_chunk

    S = _shapes(one_chip)
    # the operator's kernel tables need the symmetry group, not the basis
    op = load_config_from_yaml(FULL_YAML).hamiltonian
    tables = jax.tree_util.tree_map(
        lambda a: S(a.shape, a.dtype), K.device_tables(op, pair=False))
    assert tables.off.x.shape[0] == T
    assert tables.group.char_real.shape[0] == 128
    fn = jax.jit(partial(_ell_fill_chunk, shift=LK_SHIFT, probes=LK_PROBES,
                         is_pair=False), donate_argnums=(0, 1, 2, 3))
    compiled = fn.lower(
        S((T, N_PAD), jnp.int32), S((T, N_PAD), jnp.float64),
        S((N_PAD,), jnp.int32), S((), jnp.int64), tables,
        S((N, 2), jnp.uint32),
        S((LK_DIR,), jnp.int32), S((CHUNK,), jnp.uint64),
        S((CHUNK,), jnp.float64), S((), jnp.int32)).compile()
    _fits(compiled, "ell_fill_chunk")


def test_two_pass_build_chunks_compile_at_chain_28(one_chip):
    """``count_row_nnz`` and ``ell_lowmem_pack`` steps of the two-pass
    build at the shapes of chain_28's whole levels (28 terms, no group, 613
    chunks; what that basis took until its table was cut, and what a basis
    under the VMEM line with tables over the build budget takes): the pack
    step takes the 7.0 GB of chunk-padded level buffers donated and writes
    them in place (its output aliases them), with under 2 GB of
    temporaries (the f64 emulation splits the longest coefficient buffer
    whole to update a chunk of it)."""
    from functools import partial

    from distributed_matvec_tpu.models.yaml_io import load_config_from_yaml
    from distributed_matvec_tpu.ops import kernels as K
    from distributed_matvec_tpu.parallel.engine import (
        _count_chunk_nnz, _lowmem_pack_chunk, pad_to_multiple)

    S = _shapes(one_chip)
    n, n_pad, shapes = HISTOGRAMS["chain_28"]
    op = load_config_from_yaml(
        FULL_YAML.replace("chain_32_symm", "chain_28")).hamiltonian
    tables = jax.tree_util.tree_map(
        lambda a: S(a.shape, a.dtype), K.device_tables(op, pair=False))
    assert tables.off.x.shape[0] == 28
    levels = tuple((t0, k, L) for t0, (k, L) in zip(
        np.cumsum([0] + [k for k, _ in shapes]), shapes))
    lookup = (S((n, 2), jnp.uint32), S(((1 << 20) + 1,), jnp.int32),
              S((CHUNK,), jnp.uint64), S((CHUNK,), jnp.float64),
              S((), jnp.int32))
    statics = dict(shift=LK_SHIFT, probes=LK_PROBES, is_pair=False)
    count = jax.jit(partial(_count_chunk_nnz, **statics),
                    donate_argnums=(0, 1)).lower(
        S((n_pad,), jnp.int32), S((), jnp.int64), tables, *lookup).compile()
    _fits(count, "count_row_nnz at chain_28")
    bufs = tuple((S((k, pad_to_multiple(L, CHUNK)), jnp.int32),
                  S((k, pad_to_multiple(L, CHUNK)), jnp.float64))
                 for _, k, L in levels)
    pack = jax.jit(partial(_lowmem_pack_chunk, levels=levels, **statics),
                   donate_argnums=(0,)).lower(bufs, tables,
                                              *lookup).compile()
    _fits(pack, "ell_lowmem_pack at chain_28")
    m = pack.memory_analysis()
    held = sum(12 * k * pad_to_multiple(L, CHUNK) for _, k, L in levels)
    assert held == 7_003_963_392
    assert m.alias_size_in_bytes == held <= m.output_size_in_bytes \
        < held + 4096
    assert m.temp_size_in_bytes < 2e9


@pytest.mark.parametrize("program",
                         ["window", "full", "restart", "ritz_vectors"])
def test_lanczos_programs_compile(one_chip, tpu_knobs, compiled, program):
    """The Lanczos programs at n = 4.7M with the 96-row Krylov buffer: the
    16-step selective window and the full-sweep block (ELL apply traced in,
    buffer donated), the thick restart, and the Ritz-vector assembly.  The
    last two were ``tensordot``s that XLA:TPU expanded into an
    ``f32[8, 96, n]`` temporary — 23.85 GB, refused on the chip (PR 22)."""
    from distributed_matvec_tpu.solve.lanczos import (
        _buffer_rows, _combine_rows, _make_restart)

    S = _shapes(one_chip)
    V = S((_buffer_rows(M_CAP), N))
    if program in ("window", "full"):
        exe = compiled(program)
    elif program == "restart":
        fn = _make_restart(M_CAP, (N,), jnp.float64, 24)
        exe = fn.lower(V, S((M_CAP, 24))).compile()
    else:
        exe = _combine_rows.lower(S((M_CAP, 1)), V).compile()
    # beside the program: the ELL tables it does not take as arguments
    assert _fits(exe, f"lanczos {program}") + 1.2e9 < HBM_BYTES
    if program in ("window", "full"):
        _gathers_the_staircase(exe)
        assert all(in_vmem for _, in_vmem in _gather_results(exe))


@pytest.mark.parametrize("chips", [1, 4])
def test_krylov_buffer_programs_compile(topo, one_chip, chips):
    """The buffer's maker and its row setter (PR 38) at chain_32_symm's
    sizes, on one chip and over the four-chip mesh: the maker takes one row
    and puts out the buffer (each chip its quarter, sharded as the row is
    behind an unsharded row axis); the setter aliases the buffer it is
    given.  What the compiler holds inside them is written down here
    because the allocator's ``peak_bytes_in_use`` does not show it: the f64
    output is combined from two f32 halves (``X64Combine``), a temporary of
    the buffer's size in the maker and one and a half in the setter, as in
    every program that writes the buffer."""
    from distributed_matvec_tpu.solve.lanczos import (
        _buffer_programs, _buffer_rows)

    rows = _buffer_rows(M_CAP)
    if chips == 1:
        row = _shapes(one_chip)((N,))
        buffer = jax.ShapeDtypeStruct((rows, N), jnp.float64,
                                      sharding=one_chip)
    else:
        _, row, mesh = _distributed_ell_engine(topo)
        buffer = jax.ShapeDtypeStruct(
            (rows,) + row.shape, jnp.float64,
            sharding=NamedSharding(mesh, P(None, *row.sharding.spec)))
    make, set_row = _buffer_programs(M_CAP, row)
    held = rows * int(np.prod(row.shape)) * 8 // chips   # a chip's share
    index = jax.ShapeDtypeStruct((), jnp.int32)

    exe = make.lower(row).compile()
    m = exe.memory_analysis()
    assert m.argument_size_in_bytes < 2 * held // rows
    assert held <= m.output_size_in_bytes < 1.001 * held
    assert m.alias_size_in_bytes == 0
    assert m.temp_size_in_bytes < 1.001 * held
    _fits(exe, "krylov buffer maker")
    if chips == 4:
        assert exe.output_shardings == buffer.sharding
        assert "all-" not in exe.as_text()      # every chip fills its own

    exe = set_row.lower(buffer, index, row).compile()
    m = exe.memory_analysis()
    assert held <= m.alias_size_in_bytes == m.output_size_in_bytes
    assert m.temp_size_in_bytes < 1.6 * held
    _fits(exe, "krylov buffer row setter")
    if chips == 4:
        assert exe.output_shardings == buffer.sharding


def test_distributed_ell_apply_compiles_on_four_devices(tpu_knobs, compiled):
    """The hash-sharded ELL apply on a 4-device mesh built from the
    described devices: the emulated-f64 ``all_to_all`` under ``shard_map``
    must partition, and each device's share must fit its HBM — and the
    Lanczos window over the hashed vectors."""
    exe = compiled("distributed_apply")
    assert "all-to-all" in exe.as_text()
    # memory_analysis() of a partitioned program is per device
    _fits(exe, "distributed ell apply (per device)")
    _fits(compiled("distributed_window"),
          "distributed lanczos window (per device)")


def _fusion_signatures(exe):
    """The optimised HLO's fusions as a device trace names them
    (``benchmark/trace_reduce.py::signature``): result and operand shapes,
    each operand's shape read off the instruction that defines it."""
    text = exe.as_text()
    shape_of = dict(re.findall(r"(%[\w.\-]+) = (\w+\[[0-9,]*\])", text))
    found = []
    for m in re.finditer(r"= (\w+\[[0-9,]*\])\S* fusion\(([^)]*)\)", text):
        names = re.sub(r"/\*.*?\*/", "", m.group(2)).split(",")
        operands = [shape_of[o.strip()] for o in names if o.strip()]
        found.append(f"fusion {m.group(1)}({','.join(operands)})")
    return found


def test_chain_32_k1_x4_programs_fit_four_chips(topo, tpu_knobs):
    """``chain_32_k1_x4``'s shard shapes on the described 2x2 mesh: the
    pair-form distributed apply and the full-sweep block program, the
    largest of a solve's programs (the J-aware reorthogonalisation over a
    104-row buffer of ``[4, M, 2]`` pair rows, 3.91 GB a chip), compile and
    fit a chip with the 0.3 GB that stay resident beside their arguments.
    At this C (98.8% of M) the plan all-gathers the shards, and the apply's
    gathers are the ones ``benchmark/dist_gathers.py`` classifies as row
    gathers: 20 + 10 from the four shards all-gathered (4 M rows of six f32
    parts, the chip's own M and the 3 M the exchange put beside them), and
    no pack.  With its pair rows
    flattened (PR 39's parent) the block program needed 16.84 GB of the
    15.75 GB the compiler allows a v5e chip, and was refused."""
    import importlib
    import sys

    from distributed_matvec_tpu.solve.lanczos import (_buffer_rows,
                                                      _make_block_runner)

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    gathers = importlib.import_module("benchmark.dist_gathers")
    eng, x, mesh = _distributed_ell_engine(topo, **K1_X4)
    M = K1_X4["M"]
    assert eng.exchange_form == "gathered"
    exe = jax.jit(eng._apply_fn).lower(x, eng._operands).compile()
    # 2.95 GB (3.01 packed); an asynchronous all-gather in the exchange's
    # place held 3.62: the compiler sliced every column's coefficients and
    # clamped its indices while the collective ran
    assert _fits(exe, "pair-form distributed apply") < 3.5e9
    assert "all-to-all" in exe.as_text() and "all-gather" not in exe.as_text()
    sigs = _fusion_signatures(exe)
    rows = [(int(g.group(1)), int(g.group(2))) for g in map(
        gathers.ROW.match, sigs) if g and int(g.group(3)) == 4 * M]
    packs = [sig for sig in sigs if gathers.PACK.match(sig)
             and int(gathers.PACK.match(sig).group(3)) == M]
    assert sorted(rows) == sorted([(M, 6)] * 20 + [(K1_X4["tail"][1], 6)] * 10)
    assert packs == []

    def mv(v, ops):
        return eng._apply_fn(v, ops)[0].astype(jnp.float64)

    rep = NamedSharding(mesh, P())
    V = jax.ShapeDtypeStruct(
        (_buffer_rows(M_CAP),) + x.shape, jnp.float64,
        sharding=NamedSharding(mesh, P(None, *x.sharding.spec)))
    ab = jax.ShapeDtypeStruct((M_CAP,), jnp.float64, sharding=rep)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
    fn = _make_block_runner(mv, M_CAP, x.shape, jnp.float64, 2, pair=True)
    full = fn.lower(V, ab, ab, i32, i32, eng._operands).compile()
    # 13.66 GB (13.77 packed; 15.35 with an all-gather for the exchange)
    assert _fits(full, "pair-form full-sweep block (per device)") < 14e9


# what ``jax.named_scope`` leaves in the optimised HLO: every instruction's
# ``metadata={op_name="jit(f)/.../<scope>/<primitive>"}``.  A device trace
# carries that string per operation (stat ``tf_op``), so the scopes are how
# a trace tells the phases of an apply and of an iteration apart.
APPLY_SCOPES = ["apply/split", "apply/diag", "apply/terms"]
LOCAL_SCOPES = APPLY_SCOPES + ["apply/unpermute"]
EXCHANGE_SCOPES = ["apply/pack", "apply/exchange", "apply/tail"]
LANCZOS_SCOPES = ["lanczos/apply", "lanczos/reorth", "lanczos/recurrence",
                  "lanczos/store"]
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s.*?[\w\-]+\(",
                          re.M)


@pytest.mark.parametrize("program, scopes", [
    ("ell_apply", LOCAL_SCOPES),
    ("distributed_apply", APPLY_SCOPES + EXCHANGE_SCOPES),
    ("distributed_apply_gathered",
     APPLY_SCOPES + ["apply/exchange", "apply/tail"]),
    ("window", LANCZOS_SCOPES + ["lanczos/omega"] + LOCAL_SCOPES),
    ("full", LANCZOS_SCOPES + LOCAL_SCOPES),
], ids=["ell_apply", "distributed_apply_packed",
        "distributed_apply_gathered", "window", "full"])
def test_named_scopes_reach_the_tpu_hlo(topo, tpu_knobs, compiled,
                                        monkeypatch, program, scopes):
    """The TPU-optimised HLO of the programs a cell runs carries every
    named scope in some instruction's ``op_name``, and the scopes are
    metadata only: compiled with ``jax.named_scope`` switched off, each
    program has the same number of instructions.  The distributed apply in
    both forms of its exchange: packed (a gather named ``apply/pack``, then
    an ``all-to-all``) and gathered (an ``all-to-all`` of the whole shard,
    nothing packed)."""
    text = compiled(program).as_text()
    op_names = re.findall(r'op_name="([^"]*)"', text)
    for scope in scopes:
        assert any(f"/{scope}/" in n for n in op_names), scope
    lines = text.splitlines()
    if program == "distributed_apply":
        # the gather that fills the send buffer is the pack, by name
        assert any(" gather(" in line and "/apply/pack/" in line
                   for line in lines)
        assert any(" all-to-all(" in line and "/apply/exchange/" in line
                   for line in lines)
    if program == "distributed_apply_gathered":
        assert any(" all-to-all(" in line and "/apply/exchange/" in line
                   for line in lines)
        assert not any("/apply/pack/" in n for n in op_names)
        assert not any(" gather(" in line and "/apply/exchange/" in line
                       for line in lines)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _compile(program, topo).as_text()
    assert "apply/" not in bare and "lanczos/" not in bare
    assert len(_INSTRUCTION.findall(bare)) == len(_INSTRUCTION.findall(text))


def test_complex128_is_refused_not_hung(one_chip):
    """What ``parallel/engine.py::check_complex_backend`` says: the TPU
    compiler refuses a complex128 program with an error (it does not hang),
    which is why complex sectors run in (re, im)-pair form there."""
    x = jax.ShapeDtypeStruct((128,), jnp.complex128, sharding=one_chip)
    with pytest.raises(Exception) as e:
        jax.jit(lambda a: (a * a.conj()).real.sum()).lower(x).compile()
    assert not isinstance(e.value, (AssertionError, TimeoutError))
