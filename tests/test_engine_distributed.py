"""Multi-device matvec: hash-sharded engine vs LocalEngine vs host matvec.

The analog of the reference's GASNet-smp multi-locale testing
(SURVEY.md §4): 2/4/8 virtual CPU devices stand in for locales; the
engine must be bit-compatible with the single-device path at the golden
tolerances (TestMatrixVectorProduct.chpl:15-16).
"""

import jax
import numpy as np
import pytest

from distributed_matvec_tpu.models.basis import SpinBasis
from distributed_matvec_tpu.parallel.distributed import DistributedEngine
from distributed_matvec_tpu.parallel.shuffle import HashedLayout

from test_operator import build_heisenberg

ATOL, RTOL = 1e-13, 1e-12

def _ndev() -> int:
    """Device count, queried lazily: a module-import-time ``jax.devices()``
    initializes the backend during pytest collection, where an XLA-level
    fatal (bad XLA_FLAGS) aborts the whole run instead of
    failing one module."""
    return len(jax.devices())


# string condition → evaluated lazily at test setup, not at import
needs_8 = pytest.mark.skipif("_ndev() < 8", reason="needs 8 virtual devices")


# -- layout shuffles ---------------------------------------------------------


@pytest.mark.parametrize("n_shards", [2, 4, 8])
@pytest.mark.parametrize("batch", [None, 3])
def test_shuffle_round_trip(n_shards, batch, rng):
    """Block→hashed→block identity — the Example02 property test
    (example/Example02.chpl:20-48) on fabricated batched vectors."""
    states = np.sort(rng.choice(2**40, size=501, replace=False)).astype(np.uint64)
    layout = HashedLayout(states, n_shards, pad_multiple=8)
    shape = (states.size,) if batch is None else (states.size, batch)
    arr = rng.random(shape)
    hashed = layout.to_hashed(arr)
    assert hashed.shape[:2] == (n_shards, layout.shard_size)
    back = layout.from_hashed(hashed)
    np.testing.assert_array_equal(back, arr)
    # device path agrees with host path
    np.testing.assert_array_equal(
        np.asarray(layout.to_hashed_device(arr)), hashed)
    np.testing.assert_array_equal(
        np.asarray(layout.from_hashed_device(hashed)), arr)


def test_shuffle_counts_match_hash(rng):
    states = np.arange(1000, dtype=np.uint64) * np.uint64(2654435761)
    layout = HashedLayout(np.sort(states), 4, pad_multiple=8)
    assert layout.counts.sum() == states.size
    from distributed_matvec_tpu.enumeration.host import shard_index

    owner = shard_index(np.sort(states), 4)
    np.testing.assert_array_equal(layout.counts, np.bincount(owner, minlength=4))


# -- distributed matvec ------------------------------------------------------

DIST_CONFIGS = [
    # (n, hw, inv, syms, n_devices)
    (8, 4, None, (), 2),
    (10, 5, None, (), 4),
    (12, 6, None, (), 8),
    (10, 5, -1, (), 8),
    (12, 6, 1, [([*range(1, 12), 0], 0)], 8),          # chain_24_symm shape
    (10, 5, None, [([*range(1, 10), 0], 1)], 4),       # complex characters
]


@pytest.mark.parametrize("mode", ["ell", "fused"])
@pytest.mark.parametrize("n,hw,inv,syms,ndev", DIST_CONFIGS)
def test_distributed_matches_host(n, hw, inv, syms, ndev, mode, rng):
    if len(jax.devices()) < ndev:
        pytest.skip(f"needs {ndev} devices")
    op = build_heisenberg(n, hw, inv, syms)
    op.basis.build()
    x = rng.random(op.basis.number_states) - 0.5
    if not op.effective_is_real:
        x = x.astype(np.complex128)
    eng = DistributedEngine(op, n_devices=ndev, mode=mode, batch_size=64)
    y = eng.matvec_global(x)
    np.testing.assert_allclose(y, op.matvec_host(x), atol=ATOL, rtol=RTOL)


@needs_8
@pytest.mark.parametrize("mode", ["ell", "compact", "fused"])
def test_distributed_matches_local_engine(mode, rng):
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    op = build_heisenberg(12, 6, 1, [([*range(1, 12), 0], 0)])
    op.basis.build()
    x = rng.random(op.basis.number_states) - 0.5
    local = LocalEngine(op, mode=mode)
    dist = DistributedEngine(op, n_devices=8, mode=mode, batch_size=32)
    np.testing.assert_allclose(
        dist.matvec_global(x), np.asarray(local.matvec(x)), atol=ATOL, rtol=RTOL
    )


@needs_8
@pytest.mark.parametrize("mode", ["ell", "compact", "fused"])
def test_distributed_batch(mode, rng):
    op = build_heisenberg(10, 5, None, ())
    op.basis.build()
    n = op.basis.number_states
    X = rng.random((n, 3)) - 0.5
    eng = DistributedEngine(op, n_devices=8, mode=mode)
    Y = eng.from_hashed(eng.matvec(eng.to_hashed(X)))
    for k in range(3):
        np.testing.assert_allclose(
            Y[:, k], op.matvec_host(X[:, k]), atol=ATOL, rtol=RTOL
        )


@needs_8
def test_distributed_batch_fused_pair(rng):
    """Fused batches must ride the pair (re, im) layout too: hashed
    [D, M, k, 2] in one program."""
    from distributed_matvec_tpu.utils.config import update_config

    op = build_heisenberg(10, 5, None, [([*range(1, 10), 0], 1)])
    op.basis.build()
    assert not op.effective_is_real
    n = op.basis.number_states
    X = (rng.random((n, 3)) - 0.5) + 1j * (rng.random((n, 3)) - 0.5)
    update_config(complex_pair="on")
    try:
        eng = DistributedEngine(op, n_devices=8, mode="fused")
        assert eng.pair
        Y = eng.matvec_global(X)
    finally:
        update_config(complex_pair="auto")
    for k in range(3):
        np.testing.assert_allclose(
            Y[:, k], op.matvec_host(X[:, k]), atol=ATOL, rtol=RTOL
        )


@needs_8
def test_distributed_batch_fused_economics(rng):
    """A fused k=4 batch shares the routing (hash, sort, all_to_all index
    side) across columns, so it must cost well under 4 single applies —
    the gate is <= 1.5x one apply (generous vs the measured ~1.1x, to
    absorb CPU timing noise)."""
    import time

    op = build_heisenberg(12, 6, None, ())
    op.basis.build()
    n = op.basis.number_states
    eng = DistributedEngine(op, n_devices=8, mode="fused")
    x1 = eng.to_hashed(rng.random(n) - 0.5)
    x4 = eng.to_hashed(rng.random((n, 4)) - 0.5)
    # warm both programs (compile + first-call counter check)
    eng.matvec(x1).block_until_ready()
    eng.matvec(x4).block_until_ready()

    def best_of(f, reps=5):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            f()
            ts.append(time.perf_counter() - t0)
        return min(ts)

    # re-measure up to 3 times: a wall-clock ratio on shared CI hardware
    # can be skewed by a transient load spike, which retrying absorbs
    # without weakening the gate itself
    for attempt in range(3):
        t1 = best_of(lambda: eng.matvec(x1, check=False).block_until_ready())
        t4 = best_of(lambda: eng.matvec(x4, check=False).block_until_ready())
        if t4 <= 1.5 * t1 + 1e-3:
            break
    else:
        raise AssertionError((t4, t1))


@needs_8
def test_fused_overflow_detection(rng):
    """A deliberately tiny all_to_all capacity must be *detected*, not
    silently wrong — the analog of the reference's bounded-buffer flow
    control (DistributedMatrixVector.chpl:456, :638-661)."""
    from distributed_matvec_tpu.utils.config import get_config, update_config

    op = build_heisenberg(12, 6)
    op.basis.build()
    x = rng.random(op.basis.number_states) - 0.5
    cfg = get_config()
    saved = (cfg.all_to_all_capacity_factor, cfg.remote_buffer_size)
    update_config(all_to_all_capacity_factor=1.0, remote_buffer_size=8)
    try:
        eng = DistributedEngine(op, n_devices=8, mode="fused", batch_size=128)
        with pytest.raises(RuntimeError, match="overflow"):
            eng.matvec(eng.to_hashed(x))
    finally:
        update_config(all_to_all_capacity_factor=saved[0],
                      remote_buffer_size=saved[1])


@needs_8
def test_distributed_dot_matches_host(rng):
    op = build_heisenberg(10, 5)
    op.basis.build()
    n = op.basis.number_states
    a, b = rng.random(n), rng.random(n)
    eng = DistributedEngine(op, n_devices=8)
    got = float(eng.dot(eng.to_hashed(a), eng.to_hashed(b)))
    assert abs(got - np.dot(a, b)) < 1e-10


def test_graft_entry_dryrun():
    import sys
    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as ge

    if len(jax.devices()) >= 8:
        ge.dryrun_multichip(8)
    else:
        pytest.skip("needs 8 devices")


@pytest.mark.parametrize("mode", ["ell", "compact"])
def test_distributed_ell_split_tail_exercised(mode, rng):
    """The two-level split must trigger on the sharded plan too (global T0,
    per-shard padded tail) and stay exact vs the host path."""
    op = build_heisenberg(16, 8, None)
    op.basis.build()
    eng = DistributedEngine(op, n_devices=4, mode=mode)
    assert eng._ell_T0 < eng.num_terms, "split did not trigger"
    tail = eng._ell_tail if mode == "ell" else eng._c_tail
    assert tail is not None, "tail path not exercised"
    n = op.basis.number_states
    x = rng.random(n) - 0.5
    np.testing.assert_allclose(eng.matvec_global(x), op.matvec_host(x),
                               atol=1e-13, rtol=1e-12)


@pytest.mark.parametrize("mode", ["ell", "compact"])
def test_split_gather_distributed_matches_plain(mode, rng):
    from distributed_matvec_tpu.utils.config import update_config

    op = build_heisenberg(12, 6, None)
    op.basis.build()
    n = op.basis.number_states
    x = rng.random(n) - 0.5
    X = rng.random((n, 2)) - 0.5
    update_config(split_gather="off")
    ref = DistributedEngine(op, n_devices=4, mode=mode)
    y_ref = ref.matvec_global(x)
    Y_ref = ref.from_hashed(ref.matvec(ref.to_hashed(X)))
    update_config(split_gather="on")
    try:
        eng = DistributedEngine(op, n_devices=4, mode=mode)
        y = eng.matvec_global(x)
        Y = eng.from_hashed(eng.matvec(eng.to_hashed(X)))
    finally:
        update_config(split_gather="auto")
    np.testing.assert_allclose(y, y_ref, atol=1e-14, rtol=1e-14)
    np.testing.assert_allclose(Y, Y_ref, atol=1e-14, rtol=1e-14)


def test_distributed_compact_refusals():
    """Distributed compact refuses complex sectors and anisotropic couplings
    exactly like the local engine."""
    from distributed_matvec_tpu.models.basis import SpinBasis
    from distributed_matvec_tpu.models.lattices import (chain_edges,
                                                        heisenberg_from_edges)
    from distributed_matvec_tpu.utils.config import update_config

    b = SpinBasis(8, 4)
    op = heisenberg_from_edges(b, chain_edges(8)) \
        + 0.44 * heisenberg_from_edges(b, [(i, (i + 2) % 8)
                                           for i in range(8)])
    b.build()
    with pytest.raises(ValueError, match="single off-diagonal magnitude"):
        DistributedEngine(op, n_devices=2, mode="compact")

    b2 = SpinBasis(10, 5, None, [([1, 2, 3, 4, 5, 6, 7, 8, 9, 0], 1)])
    op2 = heisenberg_from_edges(b2, chain_edges(10))
    b2.build()
    update_config(complex_pair="on")
    try:
        with pytest.raises(ValueError, match="real sector"):
            DistributedEngine(op2, n_devices=2, mode="compact")
    finally:
        update_config(complex_pair="auto")


@needs_8
@pytest.mark.parametrize("mode", ["ell", "compact"])
def test_distributed_structure_cache(mode, tmp_path, rng):
    """The distributed routing plan checkpoints and restores bit-identically,
    keyed per mesh size (a D=4 plan must not satisfy a D=2 engine)."""
    op = build_heisenberg(12, 6, 1, [([*range(1, 12), 0], 0)])
    op.basis.build()
    x = rng.random(op.basis.number_states) - 0.5
    cache = str(tmp_path / "c.h5")
    e1 = DistributedEngine(op, n_devices=4, mode=mode, structure_cache=cache)
    assert not e1.structure_restored
    y1 = e1.matvec_global(x)
    e2 = DistributedEngine(op, n_devices=4, mode=mode, structure_cache=cache)
    assert e2.structure_restored
    np.testing.assert_array_equal(y1, e2.matvec_global(x))
    e3 = DistributedEngine(op, n_devices=2, mode=mode, structure_cache=cache)
    assert not e3.structure_restored


@pytest.mark.parametrize("D, M, C, gathered", [
    (4, 1_177_216, 1_162_872, True),    # chain_32_symm on four chips
    (4, 2_348_928, 2_320_800, True),    # its complex sector, pair form
    # at the line, 3 M == 4 C, all-gathering moves no more: it is taken
    (4, 100, 75, True),
    (4, 100, 74, False),
    (2, 100, 50, True),
    (2, 100, 49, False),
    (8, 1_664, 1_128, False),           # a 16-site ring over eight shards
    (1, 100, 100, False),               # one device exchanges nothing
])
def test_exchange_form_rule(D, M, C, gathered):
    """The ELL exchange all-gathers the shards exactly where that moves no
    more values than the packed ``all_to_all``, (D - 1) M <= D C: at the
    line, on both sides of it, and never on one device."""
    from distributed_matvec_tpu.parallel.distributed import exchange_gathers

    assert exchange_gathers(D, M, C) is gathered


@needs_8
@pytest.mark.parametrize("ndev, form", [(4, "gathered"), (8, "packed")])
def test_structure_sidecar_keeps_the_exchange_form(ndev, form, tmp_path,
                                                   rng):
    """The sidecar records the exchange's form beside C and a restored
    engine takes it: the same form, the routing table only where the
    exchange packs, the apply bit for bit."""
    import h5py

    op = build_heisenberg(16, 8, None)
    op.basis.build()
    x = rng.random(op.basis.number_states) - 0.5
    cache = str(tmp_path / "c.h5")
    e1 = DistributedEngine(op, n_devices=ndev, structure_cache=cache)
    assert not e1.structure_restored and e1.exchange_form == form
    with h5py.File(e1._structure_sidecar(cache), "r") as f:
        g = f["engine_structure"]
        assert str(g.attrs["form"]) == form
        assert ("qin_0" in g) == (form == "packed")
    e2 = DistributedEngine(op, n_devices=ndev, structure_cache=cache)
    assert e2.structure_restored and e2.exchange_form == form
    assert (e2._qin is None) == (form == "gathered")
    np.testing.assert_array_equal(e1.matvec_global(x), e2.matvec_global(x))


@needs_8
@pytest.mark.parametrize("ndev, form", [(4, "gathered"), (8, "packed")])
def test_a_sidecar_without_a_form_is_rebuilt(ndev, form, tmp_path, rng):
    """An ELL sidecar that does not record its exchange's form is not
    restored, in either form: the engine rebuilds the plan under the rule,
    writes the form, and applies bit for bit as before."""
    import h5py

    op = build_heisenberg(16, 8, None)
    op.basis.build()
    x = rng.random(op.basis.number_states) - 0.5
    cache = str(tmp_path / "c.h5")
    e1 = DistributedEngine(op, n_devices=ndev, structure_cache=cache)
    sidecar = e1._structure_sidecar(cache)
    with h5py.File(sidecar, "r+") as f:
        del f["engine_structure"].attrs["form"]
    e2 = DistributedEngine(op, n_devices=ndev, structure_cache=cache)
    assert not e2.structure_restored and e2.exchange_form == form
    with h5py.File(sidecar, "r") as f:
        assert str(f["engine_structure"].attrs["form"]) == form
    np.testing.assert_array_equal(e1.matvec_global(x), e2.matvec_global(x))
    np.testing.assert_allclose(e2.matvec_global(x), op.matvec_host(x),
                               atol=ATOL, rtol=RTOL)


@needs_8
@pytest.mark.parametrize("mode", ["ell", "compact", "fused"])
def test_engine_from_shards_all_modes(mode, tmp_path, rng):
    """Shard-native engines in EVERY mode (VERDICT r3 missing #3): the plan
    builds stream peer shards from the enumeration file one at a time —
    the global basis is never built — and match the host matvec; the
    per-shard structure cache restores bit-identically, keyed by the shard
    manifest fingerprint."""
    from distributed_matvec_tpu.enumeration.native import native_available
    from distributed_matvec_tpu.enumeration.sharded import enumerate_to_shards
    from distributed_matvec_tpu.models.lattices import (
        chain_edges, heisenberg_from_edges)
    from distributed_matvec_tpu.models.yaml_io import operator_from_dict

    if not native_available():
        pytest.skip("native kernel unavailable")
    n, hw = 12, 6
    syms = [([*range(1, n), 0], 0)]
    ref_basis = SpinBasis(number_spins=n, hamming_weight=hw,
                          spin_inversion=1, symmetries=list(syms))
    ref_basis.build()
    path = str(tmp_path / "shards.h5")
    enumerate_to_shards(n, hw, ref_basis.group, 8, path)

    ham = {"terms": [{"expression": "σˣ₀ σˣ₁ + σʸ₀ σʸ₁ + σᶻ₀ σᶻ₁",
                      "sites": [[i, (i + 1) % n] for i in range(n)]}]}
    fresh = SpinBasis(number_spins=n, hamming_weight=hw,
                      spin_inversion=1, symmetries=list(syms))
    op = operator_from_dict(ham, fresh)
    cache = str(tmp_path / "reps.h5")
    eng = DistributedEngine.from_shards(op, path, n_devices=8, mode=mode,
                                        structure_cache=cache)
    assert not fresh.is_built               # truly global-array-free
    assert eng.n_states == ref_basis.number_states

    op_ref = heisenberg_from_edges(ref_basis, chain_edges(n))
    x = rng.random(ref_basis.number_states) - 0.5
    y = eng.matvec_global(x)
    np.testing.assert_allclose(y, op_ref.matvec_host(x),
                               atol=1e-13, rtol=1e-12)

    if mode in ("ell", "compact"):
        assert not eng.structure_restored
        fresh2 = SpinBasis(number_spins=n, hamming_weight=hw,
                           spin_inversion=1, symmetries=list(syms))
        op2 = operator_from_dict(ham, fresh2)
        e2 = DistributedEngine.from_shards(op2, path, n_devices=8, mode=mode,
                                           structure_cache=cache)
        assert e2.structure_restored and not fresh2.is_built
        np.testing.assert_array_equal(y, e2.matvec_global(x))


@needs_8
@pytest.mark.slow
def test_plan_build_memory_bounded():
    """The streaming plan build must never materialize the dense
    [D, M, T] host arrays the old build used (~36 GB at chain_36_symm).
    chain_24 (N=2.7M, T=24) as the tractable proxy: the dense build's
    transients (owner/idx/coeff + the argsort copies of _split_tables)
    exceed 3.5 GB here; the streaming build + jax runtime + final packed
    structure measured 2.0 GB.  Bound 2.7 GB — fails if anyone
    reintroduces a full-width host materialization, with headroom for
    allocator noise."""
    import os
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent("""
        import resource, sys
        import jax
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_enable_x64", True)
        import numpy as np
        from distributed_matvec_tpu.models.basis import SpinBasis
        from distributed_matvec_tpu.models.yaml_io import operator_from_dict
        basis = SpinBasis(number_spins=24, hamming_weight=12)
        basis.build()
        op = operator_from_dict(
            {"terms": [{"expression":
                        "\\u03c3\\u02e3\\u2080 \\u03c3\\u02e3\\u2081 + "
                        "\\u03c3\\u02b8\\u2080 \\u03c3\\u02b8\\u2081 + "
                        "\\u03c3\\u1dbb\\u2080 \\u03c3\\u1dbb\\u2081",
                        "sites": [[i, (i + 1) % 24] for i in range(24)]}]},
            basis)
        from distributed_matvec_tpu.parallel.distributed import (
            DistributedEngine)
        eng = DistributedEngine(op, n_devices=8, mode="ell")
        x = np.random.default_rng(0).standard_normal(basis.number_states)
        y = eng.matvec_global(x)
        assert np.isfinite(y).all()
        peak_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024
        print("PEAK_MB", peak_mb)
        sys.exit(0 if peak_mb < 2700 else 17)
    """)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_X64"] = "true"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), os.pardir)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=540)
    assert r.returncode == 0, (r.returncode, r.stdout[-500:], r.stderr[-800:])


@pytest.mark.slow
def test_multihost_two_process(tmp_path):
    """A REAL multi-controller run: 2 jax.distributed processes, 4 CPU
    devices each, one 8-device mesh — the DCN analog of the reference's
    GASNet substrates (env/chpl-env-*.sh).  Each process packs only its
    addressable plan shards; all three engine modes matvec + a Lanczos
    block against single-process truth, then a shard-native from_shards
    engine where each process loads only its own shards from the file
    (multihost_worker.py)."""
    import os
    import socket
    import subprocess
    import sys

    from distributed_matvec_tpu.enumeration.native import native_available
    from distributed_matvec_tpu.enumeration.sharded import enumerate_to_shards

    shards = ""
    if native_available():
        b = SpinBasis(12, 6)
        shards = str(tmp_path / "mh_shards.h5")
        enumerate_to_shards(12, 6, b.group, 8, shards)

    worker = os.path.join(os.path.dirname(__file__), "multihost_worker.py")
    with socket.socket() as s:              # free port for the coordinator
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = [subprocess.Popen(
        [sys.executable, worker, str(pid), "2", str(port)]
        + ([shards] if shards else []),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env)
        for pid in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=420)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        raise
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid}:\n{out[-2000:]}"
        assert f"[p{pid}] MULTIHOST_OK" in out, out[-2000:]
        if shards:      # the shard-native leg must actually have run
            assert f"[p{pid}] from_shards compact: matvec" in out, out[-2000:]
            assert f"[p{pid}] from_shards resumed E0/4" in out, out[-2000:]
            assert f"[p{pid}] lobpcg E0/4" in out, out[-2000:]


@needs_8
def test_fused_exchange_counters_reach_obs(rng):
    """Satellite: the overflow/invalid counters the fused apply computes
    on-device are no longer dropped on the non-debug path — after the
    deferred drain they are visible (at zero, the healthy reading) as obs
    counters, alongside the per-apply rank-tagged matvec_apply events."""
    from distributed_matvec_tpu import obs

    obs.reset_all()
    try:
        op = build_heisenberg(10, 5)
        op.basis.build()
        x = rng.random(op.basis.number_states) - 0.5
        eng = DistributedEngine(op, n_devices=8, mode="fused")
        xh = eng.to_hashed(x)
        eng.matvec(xh)
        eng.matvec(xh)
        snap = obs.snapshot()                  # drains pending fetches
        c = snap["counters"]
        assert c.get("exchange_overflow{engine=distributed}") == 0
        assert c.get("exchange_invalid{engine=distributed}") == 0
        assert c.get("exchange_bytes{engine=distributed}", 0) > 0
        applies = obs.events("matvec_apply")
        assert len(applies) == 2
        assert all(ev["engine"] == "distributed" and ev["bytes"] > 0
                   and ev["rank"] == 0 for ev in applies)
        assert [ev["apply"] for ev in applies] == [0, 1]
        shards = obs.events("rank_shards")
        assert shards and shards[-1]["states"] == op.basis.number_states
    finally:
        obs.reset_all()


@needs_8
@pytest.mark.parametrize("mode", ["ell", "compact"])
def test_distributed_scan_branch(mode, rng, monkeypatch):
    """The lax.scan fallback of the term loops (taken only at LARGE T0,
    where unrolling would blow the program) must agree with the host —
    under shard_map the zero scan carries need varying-axes marking, which
    the unrolled branch never exercises (chain_36-scale regression)."""
    from distributed_matvec_tpu.parallel import distributed as dist_mod

    monkeypatch.setattr(dist_mod, "unroll_terms_ok",
                        lambda *a, **k: False)
    op = build_heisenberg(12, 6, 1, [([*range(1, 12), 0], 0)])
    op.basis.build()
    x = rng.random(op.basis.number_states) - 0.5
    eng = DistributedEngine(op, n_devices=8, mode=mode, batch_size=32)
    np.testing.assert_allclose(eng.matvec_global(x), op.matvec_host(x),
                               atol=ATOL, rtol=RTOL)


@needs_8
def test_fused_overflow_detected_under_trace(rng):
    """The distributed twin of the local traced-validation test (ADVICE
    r4 medium): a jit-only caller hitting a too-small all_to_all capacity
    gets a trace-time RuntimeWarning, run-time counter validation via
    ``jax.debug.callback``, and a sticky RuntimeError from the next eager
    matvec."""
    import time

    from distributed_matvec_tpu.utils.config import get_config, update_config

    op = build_heisenberg(12, 6)
    op.basis.build()
    x = rng.random(op.basis.number_states) - 0.5
    cfg = get_config()
    saved = (cfg.all_to_all_capacity_factor, cfg.remote_buffer_size)
    update_config(all_to_all_capacity_factor=1.0, remote_buffer_size=8)
    try:
        eng = DistributedEngine(op, n_devices=8, mode="fused",
                                batch_size=128)
        xh = eng.to_hashed(x)
        with pytest.warns(RuntimeWarning, match="traced before any eager"):
            try:
                jax.block_until_ready(jax.jit(eng.matvec)(xh))
            except Exception:
                pass        # callback exception may surface through the jit
        deadline = time.time() + 10
        while eng._deferred_failure is None and time.time() < deadline:
            time.sleep(0.05)
        with pytest.raises(RuntimeError, match="overflow"):
            eng.matvec(xh)
    finally:
        update_config(all_to_all_capacity_factor=saved[0],
                      remote_buffer_size=saved[1])
