"""Distributed-memory enumeration (StatesEnumeration.chpl:305-514 analog):
representatives stream into per-shard datasets — never a global host array —
validated against the hash layout of the ordinary enumeration and against
the pure-combinatorics sector-dimension census.
"""

import numpy as np
import pytest

from distributed_matvec_tpu.enumeration.native import native_available
from distributed_matvec_tpu.enumeration.sharded import (
    enumerate_to_shards, load_shard, shard_manifest)
from distributed_matvec_tpu.models.basis import SpinBasis
from distributed_matvec_tpu.models.symmetry import SymmetryGroup
from distributed_matvec_tpu.parallel.shuffle import HashedLayout

needs_native = pytest.mark.skipif(not native_available(),
                                  reason="native kernel unavailable")

SECTOR_CASES = [
    (12, 6, None, ()),
    (12, 6, 1, [([*range(1, 12), 0], 0), ([*range(11, -1, -1)], 0)]),
    (10, 5, -1, ()),
    (10, 5, None, [([*range(1, 10), 0], 1)]),     # complex characters
    (10, 5, None, [([*range(1, 10), 0], 5)]),     # momentum pi
    (14, 7, 1, [([*range(1, 14), 0], 7)]),        # mixed, nontrivial sector
]


@pytest.mark.parametrize("n,hw,inv,syms", SECTOR_CASES)
def test_census_matches_enumeration(n, hw, inv, syms):
    """The projector-trace census (pure combinatorics, no enumeration)
    equals the enumerated sector size across sector types."""
    b = SpinBasis(number_spins=n, hamming_weight=hw, spin_inversion=inv,
                  symmetries=list(syms))
    b.build()
    assert b.group.sector_dimension_census(hw) == b.number_states


@needs_native
@pytest.mark.parametrize("n,hw,inv,syms", SECTOR_CASES[:4])
@pytest.mark.parametrize("n_shards", [4, 8])
def test_shards_match_hash_layout(n, hw, inv, syms, n_shards, tmp_path):
    """Shard contents must be exactly the HashedLayout partition of the
    ordinary (global) enumeration: same states, same norms, same per-shard
    sorted order."""
    b = SpinBasis(number_spins=n, hamming_weight=hw, spin_inversion=inv,
                  symmetries=list(syms))
    b.build()
    path = str(tmp_path / "shards.h5")
    man = enumerate_to_shards(n, hw, b.group, n_shards, path)
    assert not man["restored"]
    assert man["total"] == b.number_states
    layout = HashedLayout(b.representatives, n_shards)
    np.testing.assert_array_equal(man["counts"], layout.counts)
    reps_h = layout.to_hashed(b.representatives, fill=0)
    norms_h = layout.to_hashed(b.norms, fill=0.0)
    for d in range(n_shards):
        s, nn = load_shard(path, d)
        c = layout.counts[d]
        assert s.size == c
        np.testing.assert_array_equal(s, reps_h[d, :c])
        np.testing.assert_allclose(nn, norms_h[d, :c], atol=1e-14)
        assert (np.diff(s.astype(np.int64)) > 0).all()   # sorted, unique


@needs_native
def test_shards_restore(tmp_path):
    b = SpinBasis(number_spins=12, hamming_weight=6)
    b.build()
    path = str(tmp_path / "s.h5")
    man1 = enumerate_to_shards(12, 6, b.group, 4, path)
    assert not man1["restored"]
    man2 = enumerate_to_shards(12, 6, b.group, 4, path)
    assert man2["restored"] and man2["total"] == man1["total"]
    # different parameters must NOT restore (fingerprint mismatch)
    man3 = enumerate_to_shards(12, 6, b.group, 8, path)
    assert not man3["restored"] and man3["total"] == man1["total"]
    assert shard_manifest(path)["n_shards"] == 8


def _mp_enum_worker(args):
    """Module-level worker (picklable for spawn): one rank's slice of a
    multi-process enumeration.  The group is rebuilt in-process — ranks
    share nothing but the output directory."""
    n, hw, inv, syms, n_shards, path, rank, n_ranks = args
    from distributed_matvec_tpu.enumeration.sharded import enumerate_to_shards
    from distributed_matvec_tpu.models.basis import SpinBasis

    b = SpinBasis(number_spins=n, hamming_weight=hw, spin_inversion=inv,
                  symmetries=[list(s) for s in syms])
    man = enumerate_to_shards(n, hw, b.group, n_shards, path,
                              rank=rank, n_ranks=n_ranks)
    return man["total"]


@needs_native
@pytest.mark.parametrize("n_ranks", [2, 3])
def test_multiprocess_enumeration_matches_single(n_ranks, tmp_path):
    """Cross-process parallel enumeration (the per-locale concurrent
    enumeration of StatesEnumeration.chpl:321-334): every rank enumerates a
    disjoint index-space slice in its own OS process, the finalize step
    census-validates the union, and the combined shards are bit-identical
    to a single-process enumeration of the same sector."""
    import multiprocessing as mp
    from concurrent.futures import ProcessPoolExecutor

    from distributed_matvec_tpu.enumeration.sharded import finalize_shard_parts

    n, hw, inv = 14, 7, 1
    syms = (([*range(1, 14), 0], 0),)
    n_shards = 8
    b = SpinBasis(number_spins=n, hamming_weight=hw, spin_inversion=inv,
                  symmetries=[list(s) for s in syms])
    b.build()

    single = str(tmp_path / "single.h5")
    enumerate_to_shards(n, hw, b.group, n_shards, single)

    multi = str(tmp_path / "multi.h5")
    ctx = mp.get_context("spawn")
    with ProcessPoolExecutor(max_workers=n_ranks, mp_context=ctx) as ex:
        totals = list(ex.map(_mp_enum_worker, [
            (n, hw, inv, syms, n_shards, multi, r, n_ranks)
            for r in range(n_ranks)]))
    # disjoint slices: rank totals sum to the sector dimension
    assert sum(totals) == b.number_states
    man = finalize_shard_parts(n, hw, b.group, n_shards, multi, n_ranks)
    assert man["total"] == b.number_states
    sman = shard_manifest(single)
    assert man["counts"] == sman["counts"]
    for d in range(n_shards):
        s1, w1 = load_shard(single, d)
        s2, w2 = load_shard(multi, d)
        np.testing.assert_array_equal(s1, s2)
        np.testing.assert_allclose(w1, w2, atol=1e-14)
        assert (np.diff(s2.astype(np.int64)) > 0).all()

    # restore semantics: a rerun of any rank and of the finalize is a no-op
    man_r = _mp_enum_worker((n, hw, inv, syms, n_shards, multi, 0, n_ranks))
    assert man_r == totals[0]
    man2 = finalize_shard_parts(n, hw, b.group, n_shards, multi, n_ranks)
    assert man2["restored"] and man2["total"] == man["total"]


@needs_native
def test_multiprocess_enumeration_feeds_engine(tmp_path):
    """A part-manifest shard file is a first-class engine input: the
    DistributedEngine built from it matches the host matvec."""
    import jax as _jax

    if len(_jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    from distributed_matvec_tpu.enumeration.sharded import finalize_shard_parts
    from distributed_matvec_tpu.models.yaml_io import operator_from_dict
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine

    n, hw = 12, 6
    b = SpinBasis(number_spins=n, hamming_weight=hw)
    path = str(tmp_path / "mp.h5")
    for r in range(2):
        enumerate_to_shards(n, hw, b.group, 8, path, rank=r, n_ranks=2)
    finalize_shard_parts(n, hw, b.group, 8, path, 2)

    ham = {"terms": [{"expression": "σˣ₀ σˣ₁ + σʸ₀ σʸ₁ + σᶻ₀ σᶻ₁",
                      "sites": [[i, (i + 1) % n] for i in range(n)]}]}
    fresh = SpinBasis(number_spins=n, hamming_weight=hw)
    op = operator_from_dict(ham, fresh)
    eng = DistributedEngine.from_shards(op, path, n_devices=8)

    ref_basis = SpinBasis(number_spins=n, hamming_weight=hw)
    ref_basis.build()
    op_ref = operator_from_dict(ham, ref_basis)
    x = np.random.default_rng(11).standard_normal(ref_basis.number_states)
    np.testing.assert_allclose(eng.matvec_global(x), op_ref.matvec_host(x),
                               atol=1e-13, rtol=1e-12)


def test_census_chain_40_symm_value():
    """The scale target's census: 137 846 528 820 candidates reduce to
    861 725 794 representatives under the 160-element symmetry group —
    the number the chain_40 sharded run must reproduce."""
    g = SymmetryGroup.build(
        40, [([*range(1, 40), 0], 0), ([*range(39, -1, -1)], 0)],
        spin_inversion=1)
    assert len(g) == 160
    assert g.sector_dimension_census(20) == 861_725_794


@needs_native
def test_engine_from_shards(tmp_path):
    """DistributedEngine.from_shards: engine built straight from the shard
    file with an UNBUILT basis — no global representative array anywhere —
    must match the conventional engine and the host matvec, and solve to
    the same ground state from a shard-native random start."""
    import jax as _jax

    if len(_jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    from distributed_matvec_tpu.models.yaml_io import operator_from_dict
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine
    from distributed_matvec_tpu.solve import lanczos

    n, hw = 12, 6
    syms = [([*range(1, n), 0], 0), ([*range(n - 1, -1, -1)], 0)]
    ref_basis = SpinBasis(number_spins=n, hamming_weight=hw,
                          spin_inversion=1, symmetries=list(syms))
    ref_basis.build()
    path = str(tmp_path / "shards.h5")
    enumerate_to_shards(n, hw, ref_basis.group, 8, path)

    ham = {"terms": [{"expression": "σˣ₀ σˣ₁ + σʸ₀ σʸ₁ + σᶻ₀ σᶻ₁",
                      "sites": [[i, (i + 1) % n] for i in range(n)]}]}
    fresh_basis = SpinBasis(number_spins=n, hamming_weight=hw,
                            spin_inversion=1, symmetries=list(syms))
    op = operator_from_dict(ham, fresh_basis)
    eng = DistributedEngine.from_shards(op, path, n_devices=8)
    assert not fresh_basis.is_built          # truly global-array-free
    assert eng.n_states == ref_basis.number_states

    # hashed matvec vs the host path on the built twin
    op_ref = operator_from_dict(ham, ref_basis)
    x = np.random.default_rng(3).standard_normal(ref_basis.number_states)
    y = eng.matvec_global(x)                 # lazy layout materialization
    np.testing.assert_allclose(y, op_ref.matvec_host(x),
                               atol=1e-13, rtol=1e-12)

    # shard-native solve: random_hashed never touches a global array
    res = lanczos(eng.matvec, v0=eng.random_hashed(seed=5), k=1, tol=1e-10)
    want = np.linalg.eigvalsh(op_ref.to_sparse().toarray())[0]
    assert abs(float(res.eigenvalues[0]) - want) < 1e-8


@needs_native
def test_cli_shards_saves_sharded_eigenvectors(tmp_path):
    """--shards WITHOUT --no-eigenvectors: the driver saves eigenvectors one
    shard at a time (vector_shards/eigenvector_i) — never a global [N]
    array — and the reassembled state is the true ground state (residual
    check against the independent host matvec).  Observables run on the
    hashed psi directly."""
    import os
    import subprocess
    import sys

    import h5py

    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_X64="true",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), os.pardir),
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    app = os.path.join(os.path.dirname(__file__), os.pardir, "apps",
                       "diagonalize.py")
    n, hw = 10, 5
    yml = str(tmp_path / "m.yaml")
    with open(yml, "w") as f:
        f.write("""
basis: {number_spins: 10, hamming_weight: 5}
hamiltonian:
  name: H
  terms:
    - {expression: "σˣ₀ σˣ₁ + σʸ₀ σʸ₁ + σᶻ₀ σᶻ₁", sites: [[0,1],[1,2],[2,3],[3,4],[4,5],[5,6],[6,7],[7,8],[8,9],[9,0]]}
observables:
  - name: nn
    terms:
      - {expression: "σˣ₀ σˣ₁ + σʸ₀ σʸ₁ + σᶻ₀ σᶻ₁", sites: [[0, 1]]}
""")
    shards = str(tmp_path / "s.h5")
    b = SpinBasis(number_spins=n, hamming_weight=hw)
    b.build()
    enumerate_to_shards(n, hw, b.group, 8, shards)
    out = str(tmp_path / "out.h5")
    r = subprocess.run(
        [sys.executable, app, yml, "-o", out, "--shards", shards,
         "-k", "1", "--observables"],
        capture_output=True, text=True, env=env, timeout=240)
    assert r.returncode == 0, (r.stdout[-800:], r.stderr[-1500:])

    from distributed_matvec_tpu.io.sharded_io import (
        hashed_vector_counts, load_hashed_shard)
    from distributed_matvec_tpu.models.yaml_io import load_config_from_yaml
    from distributed_matvec_tpu.parallel.shuffle import HashedLayout

    counts = hashed_vector_counts(out)
    layout = HashedLayout(b.representatives, 8)
    np.testing.assert_array_equal(counts, layout.counts)
    # reassemble the block-order psi from the per-shard datasets
    psi_h = np.zeros((8, layout.shard_size))
    for d in range(8):
        rows = load_hashed_shard(out, d, name="eigenvector_0")
        assert rows.shape == (counts[d],)
        psi_h[d, : counts[d]] = rows
    psi = layout.from_hashed(psi_h)
    with h5py.File(out, "r") as f:
        e0 = float(f["hamiltonian/eigenvalues"][0])
        assert "hamiltonian/eigenvectors" not in f   # no global array saved
        corr = float(f["observables/nn"][()])
    cfg = load_config_from_yaml(yml, hamiltonian=True)
    cfg.basis.build()
    resid = np.linalg.norm(cfg.hamiltonian.matvec_host(psi) - e0 * psi)
    assert abs(np.linalg.norm(psi) - 1) < 1e-10
    assert resid < 1e-8, resid
    assert abs(corr - e0 / n) < 1e-6                 # ring bond correlator


def test_stream_block_to_shards_matches_layout(tmp_path, rng):
    """Chunked block→shard vector routing (MyHDF5 hyperslab + B2H analog)
    must equal HashedLayout.to_hashed exactly, rank-1 and batch."""
    from distributed_matvec_tpu.io.hdf5 import save_golden
    from distributed_matvec_tpu.io.sharded_io import (
        load_hashed_shard, stream_block_to_shards)

    b = SpinBasis(number_spins=14, hamming_weight=7)
    b.build()
    n = b.number_states
    X = rng.random((3, n)) - 0.5            # golden layout: [k, N]
    src = str(tmp_path / "golden.h5")
    save_golden(src, b.representatives, X, X)
    out = str(tmp_path / "xshards.h5")
    counts = stream_block_to_shards(src, out, 8, chunk=777)

    layout = HashedLayout(b.representatives, 8)
    np.testing.assert_array_equal(counts, layout.counts)
    want = layout.to_hashed(X.T, fill=0)     # [D, M, k]
    for d in range(8):
        got = load_hashed_shard(out, d)
        np.testing.assert_array_equal(got, want[d, : counts[d]])


def test_save_load_hashed_vector_round_trip(tmp_path, rng):
    """Per-shard hashed-vector checkpoint (readDatasetAsBlocks analog):
    device array in, pad rows stripped on disk, per-shard reads back."""
    import jax as _jax

    if len(_jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    from distributed_matvec_tpu.io.sharded_io import (
        hashed_vector_counts, load_hashed_shard, save_hashed_vector)
    from test_operator import build_heisenberg
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine

    op = build_heisenberg(12, 6)
    op.basis.build()
    eng = DistributedEngine(op, n_devices=8)
    xh = eng.random_hashed(seed=9)
    path = str(tmp_path / "v.h5")
    save_hashed_vector(path, xh, eng.counts)
    np.testing.assert_array_equal(hashed_vector_counts(path), eng.counts)
    xh_np = np.asarray(xh)
    for d in range(8):
        got = load_hashed_shard(path, d)
        np.testing.assert_array_equal(got, xh_np[d, : eng.counts[d]])


@needs_native
def test_cli_shards_observables(tmp_path):
    """--shards + --observables: observables run through shard-native
    engines from the SAME shard file (no per-observable global basis
    rebuild); value cross-checked against the host matvec."""
    import subprocess
    import sys
    import os

    import h5py

    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_X64="true",
               PYTHONPATH="/root/repo",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    app = os.path.join(os.path.dirname(__file__), os.pardir, "apps",
                       "diagonalize.py")
    yml = str(tmp_path / "m.yaml")
    with open(yml, "w") as f:
        f.write("""
basis: {number_spins: 10, hamming_weight: 5}
hamiltonian:
  name: H
  terms:
    - {expression: "σˣ₀ σˣ₁ + σʸ₀ σʸ₁ + σᶻ₀ σᶻ₁", sites: &l [[0,1],[1,2],[2,3],[3,4],[4,5],[5,6],[6,7],[7,8],[8,9],[9,0]]}
observables:
  - name: nn
    terms:
      - {expression: "σˣ₀ σˣ₁ + σʸ₀ σʸ₁ + σᶻ₀ σᶻ₁", sites: [[0, 1]]}
""")
    shards = str(tmp_path / "s.h5")
    from distributed_matvec_tpu.enumeration.sharded import enumerate_to_shards
    b = SpinBasis(number_spins=10, hamming_weight=5)
    b.build()
    enumerate_to_shards(10, 5, b.group, 8, shards)
    out = str(tmp_path / "out.h5")
    r = subprocess.run(
        [sys.executable, app, yml, "-o", out, "--shards", shards,
         "-k", "1", "--observables"],
        capture_output=True, text=True, env=env, timeout=240)
    assert r.returncode == 0, (r.stdout[-800:], r.stderr[-1500:])
    with h5py.File(out, "r") as f:
        corr = float(f["observables/nn"][()])
        psi = f["hamiltonian/eigenvalues"][...]
    # bond correlator of the 10-ring GS = E0 / 10
    assert abs(corr - psi[0] / 10) < 1e-6, (corr, psi[0] / 10)


def test_rank_file_meta_and_counts_discovery(tmp_path, rng):
    """ADVICE r4 low items: (a) ``hashed_vector_counts`` must read counts
    when a multi-process save wrote only ``path.r<rank>`` files; (b) a
    stale base-path ``/ckpt_meta`` must not mask valid per-rank
    checkpoints when the caller filters by fingerprint."""
    from distributed_matvec_tpu.io.sharded_io import (
        hashed_vector_counts, load_hashed_meta, save_hashed_vectors)

    base = str(tmp_path / "v.h5")
    counts = np.array([2, 1], np.int64)
    xh = rng.random((2, 3))
    # simulate the rank-0 file of a multi-process run (a single-process
    # save writes to the exact path it is given)
    save_hashed_vectors(base + ".r0", {"v": xh}, counts,
                        meta={"fingerprint": "good", "m": 3})
    assert load_hashed_meta(base) is not None
    np.testing.assert_array_equal(hashed_vector_counts(base), counts)

    # a stale base-path file from an earlier single-process run
    save_hashed_vectors(base, {"v": xh}, counts,
                        meta={"fingerprint": "stale", "m": 1})
    got = load_hashed_meta(base)                   # unfiltered scan: stale
    assert str(got["fingerprint"]) == "stale"
    got = load_hashed_meta(base, expected_fingerprint="good")
    assert got is not None and int(got["m"]) == 3
    assert load_hashed_meta(base, expected_fingerprint="nope") is None


@needs_native
def test_reshard_cross_mesh_agreement(tmp_path):
    """``reshard_shards`` 8→4 plus the state-keyed probe: the re-routed
    file must hold exactly the HashedLayout-4 partition, and fused engines
    on the two mesh sizes must produce the same global ⟨x, Hx⟩ / ‖Hx‖ —
    the cross-mesh verification protocol of a chain_40-scale run."""
    import jax as _jax

    if len(_jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    from distributed_matvec_tpu.enumeration.sharded import reshard_shards
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine
    from test_operator import build_heisenberg

    op = build_heisenberg(14, 7, 1, [([*range(1, 14), 0], 0)])
    b = op.basis
    b.build()
    p8 = str(tmp_path / "s8.h5")
    p4 = str(tmp_path / "s4.h5")
    enumerate_to_shards(14, 7, b.group, 8, p8)
    man4 = reshard_shards(p8, p4, 4, group=b.group)
    # restore path: same fingerprint → no rewrite
    assert reshard_shards(p8, p4, 4, group=b.group)["restored"]
    # with the group, the resharded file is indistinguishable from a
    # direct 4-shard enumeration
    direct = enumerate_to_shards(14, 7, b.group, 4,
                                 str(tmp_path / "d4.h5"))
    assert man4["fingerprint"] == direct["fingerprint"]
    assert man4["counts"] == direct["counts"]
    layout4 = HashedLayout(b.representatives, 4)
    for d in range(4):
        s, nn = load_shard(p4, d)
        c = layout4.counts[d]
        np.testing.assert_array_equal(
            s, layout4.to_hashed(b.representatives, fill=0)[d, :c])
        np.testing.assert_array_equal(
            nn, layout4.to_hashed(b.norms, fill=0.0)[d, :c])

    e8 = DistributedEngine.from_shards(op, p8, n_devices=8, mode="fused")
    e4 = DistributedEngine.from_shards(op, p4, n_devices=4, mode="fused")
    x8, x4 = e8.state_keyed_hashed(salt=3), e4.state_keyed_hashed(salt=3)
    # the probe is a pure function of the state: identical global vector
    np.testing.assert_allclose(
        float(np.linalg.norm(np.asarray(x8))),
        float(np.linalg.norm(np.asarray(x4))), rtol=1e-13)
    y8, y4 = e8.matvec(x8), e4.matvec(x4)
    s8 = float(e8.dot(x8, y8))
    s4 = float(e4.dot(x4, y4))
    np.testing.assert_allclose(s8, s4, rtol=1e-12)
    np.testing.assert_allclose(float(np.linalg.norm(np.asarray(y8))),
                               float(np.linalg.norm(np.asarray(y4))),
                               rtol=1e-12)
