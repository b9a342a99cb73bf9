"""Host enumeration: bit tricks, rank/unrank, hashing, representatives."""

import math
import os

import numpy as np
import pytest

from distributed_matvec_tpu.enumeration import host as en
from distributed_matvec_tpu.models.symmetry import SymmetryGroup

import dense_ref


def test_next_state_fixed_hamming_small():
    # semantic reference from StatesEnumeration.chpl:21-30
    def slow(v):
        m = bin(v).count("1")
        v += 1
        while bin(v).count("1") != m:
            v += 1
        return v

    for v in [1, 2, 3, 5, 7, 0b1010, 0b0111, 0b110100, (1 << 10) - 1]:
        assert en.next_state_fixed_hamming(v) == slow(v)


@pytest.mark.parametrize("n,k", [(4, 2), (6, 3), (8, 1), (8, 8), (10, 5), (12, 0), (16, 4)])
def test_fixed_hamming_states(n, k):
    s = en.fixed_hamming_states(n, k)
    assert s.size == math.comb(n, k)
    assert (np.diff(s.astype(np.int64)) > 0).all()  # strictly ascending
    assert (np.bitwise_count(s) == k).all()
    # first and last match the min/max estimates
    if k > 0:
        assert s[0] == (1 << k) - 1
        assert s[-1] == ((1 << k) - 1) << (n - k)


def test_fixed_hamming_states_match_next_state_iteration():
    s = en.fixed_hamming_states(8, 3)
    v = (1 << 3) - 1
    for expected in s:
        assert v == expected
        v = en.next_state_fixed_hamming(v)


@pytest.mark.parametrize("n,k", [(8, 3), (10, 5), (12, 4)])
def test_rank_unrank_roundtrip(n, k):
    s = en.fixed_hamming_states(n, k)
    ranks = en.fixed_hamming_rank(s)
    np.testing.assert_array_equal(ranks, np.arange(s.size, dtype=np.uint64))
    for r in [0, 1, s.size // 2, s.size - 1]:
        assert en.fixed_hamming_unrank(r, k) == s[r]


def test_hash64_is_splitmix64_finalizer():
    # independently computed splitmix64 finalizer values
    def ref(x):
        mask = (1 << 64) - 1
        x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & mask
        x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & mask
        return x ^ (x >> 31)

    xs = np.array([0, 1, 2, 12345, (1 << 63) | 12345], dtype=np.uint64)
    got = en.hash64(xs)
    for x, g in zip(xs, got):
        assert int(g) == ref(int(x))


def test_shard_index_range():
    s = en.fixed_hamming_states(12, 6)
    for n_shards in (1, 2, 4, 8):
        idx = en.shard_index(s, n_shards)
        assert idx.min() >= 0 and idx.max() < n_shards
        if n_shards > 1:
            counts = np.bincount(idx, minlength=n_shards)
            # hash-balanced to within a few σ
            assert counts.min() > 0.5 * s.size / n_shards


@pytest.mark.parametrize(
    "n,hw,gens,inv",
    [
        (10, 5, [], -1),                              # chain_10-style inversion only
        (8, 4, [([1, 2, 3, 4, 5, 6, 7, 0], 0)], None),  # translation sector 0
        (8, 4, [([1, 2, 3, 4, 5, 6, 7, 0], 1)], None),  # complex characters
        (8, 4, [([1, 2, 3, 4, 5, 6, 7, 0], 0), ([7, 6, 5, 4, 3, 2, 1, 0], 0)], 1),
        (12, 6, [([2, 10, 0, 4, 3, 7, 11, 5, 9, 8, 1, 6], 1)], None),  # issue_01.yaml group
        (9, None, [([1, 2, 3, 4, 5, 6, 7, 8, 0], 3)], None),  # no hamming sector
    ],
)
def test_enumerate_representatives_vs_brute_force(n, hw, gens, inv):
    group = SymmetryGroup.build(n, gens, inv)
    candidates = en.all_states(n, hw)
    reps, norms = en.enumerate_representatives(n, hw, group)
    ref_reps, ref_norms = dense_ref.brute_force_representatives(n, candidates, group)
    np.testing.assert_array_equal(reps, ref_reps)
    np.testing.assert_allclose(norms, ref_norms, atol=1e-13)
    assert (np.diff(reps.astype(np.int64)) > 0).all()


def test_chain_10_inversion_count():
    # C(10,5)/2 = 126 representatives (data/heisenberg_chain_10.yaml sector)
    group = SymmetryGroup.build(10, [], -1)
    reps, norms = en.enumerate_representatives(10, 5, group)
    assert reps.size == 126
    np.testing.assert_allclose(norms, np.sqrt(0.5))


def test_state_info_consistency():
    """state_info of any state maps into the enumerated representative set."""
    group = SymmetryGroup.build(
        8, [([1, 2, 3, 4, 5, 6, 7, 0], 0), ([7, 6, 5, 4, 3, 2, 1, 0], 0)], 1
    )
    reps, _ = en.enumerate_representatives(8, 4, group)
    all_s = en.all_states(8, 4)
    r, chars, norms = group.state_info(all_s)
    live = norms > 0
    assert np.isin(r[live], reps).all()
    # orbit-invariance of the norm
    np.testing.assert_allclose(norms, group.state_info(r)[2], atol=1e-13)


def test_square_edges_keeps_doubled_wrap_bonds():
    """Regression: periodic 4x2 torus has doubled vertical bonds."""
    from distributed_matvec_tpu.models.lattices import chain_edges, square_edges

    e42 = square_edges(4, 2)
    assert e42.count((0, 4)) == 2
    assert chain_edges(2) == [(0, 1), (1, 0)]
    # no duplicates for sizes > 2
    e44 = square_edges(4, 4)
    assert len(e44) == len(set(e44)) == 32


def test_basis_json_roundtrip_preserves_subclass():
    from distributed_matvec_tpu.models.basis import (
        SpinBasis,
        SpinfulFermionBasis,
        SpinlessFermionBasis,
    )

    b = SpinfulFermionBasis(3, 2, 1)
    b2 = SpinBasis.from_json(b.to_json())
    assert isinstance(b2, SpinfulFermionBasis)
    np.testing.assert_array_equal(
        b.build().representatives, b2.build().representatives
    )
    assert b.number_states == 9  # C(3,2)·C(3,1)
    s = SpinlessFermionBasis(5, 2)
    s2 = SpinBasis.from_json(s.to_json())
    assert isinstance(s2, SpinlessFermionBasis)
    assert s2.build().number_states == 10


# -- native (C++) enumeration kernel ----------------------------------------


def _native_or_skip():
    import pytest

    from distributed_matvec_tpu.enumeration import native

    if not native.native_available():
        pytest.skip("no C++ toolchain")
    return native


def test_native_matches_numpy_enumeration():
    """The streaming C++ kernel must agree exactly (states AND norms) with
    the portable NumPy path on every sector shape: translation, momentum,
    translation×parity×inversion, no-hamming."""
    from distributed_matvec_tpu.enumeration import host
    from distributed_matvec_tpu.models.symmetry import SymmetryGroup

    native = _native_or_skip()
    configs = [
        (8, 4, [([*range(1, 8), 0], 0)], None),
        (10, 5, [([*range(1, 10), 0], 1)], None),         # complex sector
        (12, 6, [([*range(1, 12), 0], 0),
                 ([*reversed(range(12))], 0)], 1),
        (13, 6, [([*range(1, 13), 0], 3)], None),
        (12, None, [([*range(1, 12), 0], 0)], None),      # no hamming
        (16, 8, [([*range(1, 16), 0], 0),
                 ([*reversed(range(16))], 0)], -1),       # antisymmetric inv
    ]
    for n, hw, syms, inv in configs:
        g = SymmetryGroup.build(n, syms, inv)
        s_np, n_np = host.enumerate_representatives(n, hw, g)
        s_c, n_c = native.enumerate_representatives_native(n, hw, g)
        np.testing.assert_array_equal(s_np, s_c)
        np.testing.assert_allclose(n_np, n_c, atol=1e-14)


def test_native_chunking_boundaries():
    """Many tiny chunks must tile the range without loss or duplication."""
    from distributed_matvec_tpu.enumeration import host
    from distributed_matvec_tpu.models.symmetry import SymmetryGroup

    native = _native_or_skip()
    g = SymmetryGroup.build(14, [([*range(1, 14), 0], 0)])
    s_ref, _ = host.enumerate_representatives(14, 7, g)
    for n_chunks in (1, 3, 64, 500):
        s_c, _ = native.enumerate_representatives_native(
            14, 7, g, n_chunks=n_chunks)
        np.testing.assert_array_equal(s_ref, s_c)


def test_build_uses_backend_dispatch():
    from distributed_matvec_tpu.models.basis import SpinBasis
    from distributed_matvec_tpu.utils.config import update_config

    _native_or_skip()
    syms = [([*range(1, 12), 0], 0)]
    try:
        update_config(enumeration_backend="native")
        b1 = SpinBasis(12, 6, None, syms).build()
        update_config(enumeration_backend="numpy")
        b2 = SpinBasis(12, 6, None, syms).build()
    finally:
        update_config(enumeration_backend="auto")
    np.testing.assert_array_equal(b1.representatives, b2.representatives)
    np.testing.assert_allclose(b1.norms, b2.norms, atol=1e-14)


# -- the native build is loud, and only its own binary is ever loaded --------


@pytest.fixture
def fresh_native(monkeypatch, tmp_path):
    """The native module pointed at a private copy of the source, with no
    library loaded — so a test can break the build without touching the
    kernel the rest of the suite uses."""
    import shutil

    native = _native_or_skip()
    src = tmp_path / "_native.cpp"
    shutil.copy(native._SRC, src)
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_HERE", str(tmp_path))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_lib_error", None)
    return native


def test_failed_native_build_raises_with_compiler_stderr(fresh_native,
                                                         tmp_path):
    """A build failure is an exception carrying the compiler's words — never
    a silent NumPy run of a sector the kernel was chosen for."""
    from distributed_matvec_tpu.enumeration import enumerate_representatives
    from distributed_matvec_tpu.models.symmetry import SymmetryGroup

    native = fresh_native
    with open(native._SRC, "a") as f:
        f.write("\nthis is not C++;\n")
    g = SymmetryGroup.build(8, [([*range(1, 8), 0], 0)])
    with pytest.raises(native.NativeBuildError) as e:
        enumerate_representatives(8, 4, g)
    assert "compiler stderr" in str(e.value) and "error" in str(e.value)
    assert "-march=native" not in str(e.value)
    assert not list(tmp_path.glob("*.so*"))          # no half-built file
    # the failure is remembered: no second compile, same error
    with pytest.raises(native.NativeBuildError):
        native._load()


def test_missing_compiler_raises(fresh_native, monkeypatch):
    native = fresh_native
    monkeypatch.setattr(native, "_CXX", "no-such-compiler-xyz")
    assert not native.native_available()
    with pytest.raises(native.NativeBuildError, match="no-such-compiler"):
        native._load()


def test_stale_native_binary_is_not_loaded(fresh_native, tmp_path):
    """The library's file name is keyed on the source and the flags: a
    binary built from an older source (or by -march=native on another CPU)
    sits under another name and is never opened."""
    native = fresh_native
    old = native._so_path()
    native._load()
    assert os.path.exists(old)
    # corrupt the old binary in place: loading it now would fail loudly
    with open(old, "wb") as f:
        f.write(b"not an ELF file")
    with open(native._SRC, "a") as f:
        f.write("\n// a source change\n")
    native._lib = None
    new = native._so_path()
    assert new != old
    native._load()                                   # builds, loads `new`
    assert os.path.exists(new)
    assert not os.path.exists(old)                   # and sweeps the stale one
    from distributed_matvec_tpu.enumeration import host
    from distributed_matvec_tpu.models.symmetry import SymmetryGroup

    g = SymmetryGroup.build(8, [([*range(1, 8), 0], 0)])
    np.testing.assert_array_equal(
        native.enumerate_representatives_native(8, 4, g)[0],
        host.enumerate_representatives(8, 4, g)[0])
