"""Streamed engine mode: precomputed plan streaming vs the fused truth.

The streamed apply must be BIT-identical to fused — same chunking, same
bucket routing (`_bucket_positions` is shared), same accumulation order —
while never re-running the orbit scan: the plan is resolved once (build or
artifact-cache restore), lives in host RAM (or the sidecar disk tier), and
streams H2D per apply.  Plus the selective-reorthogonalization satellite:
ω-gated window MGS must reproduce full-reorth eigenvalues.
"""

import os

import jax
import numpy as np
import pytest

from distributed_matvec_tpu.parallel.distributed import DistributedEngine
from distributed_matvec_tpu.utils.config import update_config

from test_operator import build_heisenberg

ATOL, RTOL = 1e-13, 1e-12


def _ndev() -> int:
    return len(jax.devices())


needs_8 = pytest.mark.skipif("_ndev() < 8", reason="needs 8 virtual devices")
needs_4 = pytest.mark.skipif("_ndev() < 4", reason="needs 4 virtual devices")


STREAM_CONFIGS = [
    # (n, hw, inv, syms, ndev) — one |G|>1 chain-style sector, one trivial
    # group, one complex-character sector (c128 on CPU)
    (12, 6, 1, [([*range(1, 12), 0], 0)], 8),
    (10, 5, None, (), 4),
    (10, 5, None, [([*range(1, 10), 0], 1)], 4),
]


@pytest.mark.parametrize("n,hw,inv,syms,ndev", STREAM_CONFIGS)
def test_streamed_bit_identical_to_fused(n, hw, inv, syms, ndev, rng):
    """Acceptance: streamed y == fused y to the BIT (and ⟨x,Hx⟩ with it)
    on a |G|>1 config and a trivial-group config."""
    if _ndev() < ndev:
        pytest.skip(f"needs {ndev} devices")
    op = build_heisenberg(n, hw, inv, syms)
    op.basis.build()
    x = rng.random(op.basis.number_states) - 0.5
    if not op.effective_is_real:
        x = x.astype(np.complex128)
    ef = DistributedEngine(op, n_devices=ndev, mode="fused", batch_size=64)
    es = DistributedEngine(op, n_devices=ndev, mode="streamed",
                           batch_size=64)
    yf = np.asarray(ef.matvec(ef.to_hashed(x)))
    ys = np.asarray(es.matvec(es.to_hashed(x)))
    np.testing.assert_array_equal(yf, ys)
    assert complex(ef.dot(ef.to_hashed(x), jax.numpy.asarray(yf))) \
        == complex(es.dot(es.to_hashed(x), jax.numpy.asarray(ys)))
    # and both agree with the host truth
    np.testing.assert_allclose(es.from_hashed(ys), op.matvec_host(x),
                               atol=ATOL, rtol=RTOL)


@needs_8
def test_streamed_batch_bit_identical(rng):
    """A k=3 multi-RHS apply streams each plan chunk once and still equals
    the fused batch bit-for-bit (same program shape per column count)."""
    op = build_heisenberg(10, 5, None, ())
    op.basis.build()
    n = op.basis.number_states
    X = rng.random((n, 3)) - 0.5
    ef = DistributedEngine(op, n_devices=8, mode="fused")
    es = DistributedEngine(op, n_devices=8, mode="streamed")
    Yf = np.asarray(ef.matvec(ef.to_hashed(X)))
    Ys = np.asarray(es.matvec(es.to_hashed(X)))
    np.testing.assert_array_equal(Yf, Ys)
    Y = es.from_hashed(Ys)
    for k in range(3):
        np.testing.assert_allclose(Y[:, k], op.matvec_host(X[:, k]),
                                   atol=ATOL, rtol=RTOL)


@needs_4
def test_streamed_multichunk_and_single_device(rng):
    """Chunked plans (batch_size < shard rows) and the D=1 degenerate mesh
    both stream correctly."""
    op = build_heisenberg(10, 5, None, ())
    op.basis.build()
    x = rng.random(op.basis.number_states) - 0.5
    want = op.matvec_host(x)
    for ndev, bs in ((4, 16), (1, 32)):
        es = DistributedEngine(op, n_devices=ndev, mode="streamed",
                               batch_size=bs)
        assert es._plan_nchunks_v > 1
        np.testing.assert_allclose(
            es.from_hashed(es.matvec(es.to_hashed(x))), want,
            atol=ATOL, rtol=RTOL)


@needs_4
def test_streamed_counters_preserved(rng):
    """The structural overflow/invalid counters survive the plan: a
    too-small exchange capacity fails LOUDLY at build time (fused defers
    the same failure to the first apply), and a healthy run's applies keep
    the exchange_overflow/exchange_invalid obs series visible at zero."""
    from distributed_matvec_tpu import obs

    op = build_heisenberg(10, 5, None, ())
    op.basis.build()
    update_config(remote_buffer_size=8, all_to_all_capacity_factor=1.0)
    try:
        with pytest.warns(RuntimeWarning):
            with pytest.raises(RuntimeError, match="overflowed"):
                DistributedEngine(op, n_devices=4, mode="streamed",
                                  batch_size=64)
    finally:
        update_config(remote_buffer_size=150_000,
                      all_to_all_capacity_factor=1.25)

    obs.reset_all()
    try:
        es = DistributedEngine(op, n_devices=4, mode="streamed")
        xh = es.to_hashed(rng.random(op.basis.number_states) - 0.5)
        for _ in range(2):
            es.matvec(xh)
        obs.health_event_count()            # drain deferred fetches
        counters = obs.snapshot()["counters"]
        for name in ("exchange_overflow", "exchange_invalid"):
            hits = {k: v for k, v in counters.items()
                    if k.startswith(name)}
            assert hits and all(v == 0 for v in hits.values()), (name, hits)
    finally:
        obs.reset_all()


@needs_4
def test_streamed_plan_cache_roundtrip(tmp_path, rng, monkeypatch):
    """The plan sidecar under the artifact cache: built once, restored by
    the next construction (bit-identically), still correct with the cache
    OFF (pure host-RAM, no writes), and readable from the DISK tier when
    the RAM budget excludes it."""
    op = build_heisenberg(12, 6, 1, [([*range(1, 12), 0], 0)])
    op.basis.build()
    x = rng.random(op.basis.number_states) - 0.5

    monkeypatch.setenv("DMT_ARTIFACT_CACHE", "on")
    monkeypatch.setenv("DMT_ARTIFACT_DIR", str(tmp_path / "art"))
    e1 = DistributedEngine(op, n_devices=4, mode="streamed")
    assert not e1.structure_restored
    y1 = np.asarray(e1.matvec(e1.to_hashed(x)))
    e2 = DistributedEngine(op, n_devices=4, mode="streamed")
    assert e2.structure_restored
    np.testing.assert_array_equal(
        y1, np.asarray(e2.matvec(e2.to_hashed(x))))

    # disk tier: a zero RAM budget keeps the restored plan on disk
    update_config(stream_plan_ram_gb=0.0)
    try:
        e3 = DistributedEngine(op, n_devices=4, mode="streamed")
        assert e3.structure_restored
        assert e3._plan_chunks is None and e3._plan_disk
        np.testing.assert_array_equal(
            y1, np.asarray(e3.matvec(e3.to_hashed(x))))
    finally:
        update_config(stream_plan_ram_gb=8.0)

    # cache off: no restore, no disk writes, same answer
    monkeypatch.setenv("DMT_ARTIFACT_CACHE", "off")
    before = {p for p in (tmp_path / "art").rglob("*")}
    e4 = DistributedEngine(op, n_devices=4, mode="streamed")
    assert not e4.structure_restored
    assert e4._plan_chunks is not None and e4._plan_disk is None
    np.testing.assert_array_equal(
        y1, np.asarray(e4.matvec(e4.to_hashed(x))))
    assert {p for p in (tmp_path / "art").rglob("*")} == before


@needs_4
def test_streamed_plan_bytes_in_ledger(rng):
    """The host-RAM plan is a first-class memory-ledger citizen
    (device="host") and rides the engine_init memory_ledger context as
    plan_bytes — what tools/capacity.py calibrates the streamed tier
    from."""
    from distributed_matvec_tpu import obs
    from distributed_matvec_tpu.obs import memory as obs_memory

    obs.reset_all()
    try:
        es = DistributedEngine(op := build_op_cached(), n_devices=4,
                               mode="streamed")
        assert es.plan_bytes > 0
        assert obs_memory.ledger_total(device="host") >= es.plan_bytes
        led = [e for e in obs.events("memory_ledger")
               if e.get("mode") == "streamed"]
        assert led and int(led[-1]["plan_bytes"]) == es.plan_bytes
        ps = obs.events("plan_stream")
        assert ps and ps[-1]["plan_bytes"] == es.plan_bytes
        assert ps[-1]["tier"] == "ram"
    finally:
        obs.reset_all()


_op_cache = {}


def build_op_cached():
    op = _op_cache.get("op")
    if op is None:
        op = build_heisenberg(10, 5, None, ())
        op.basis.build()
        _op_cache["op"] = op
    return op


@needs_4
def test_streamed_refuses_outer_trace_solvers(rng):
    """bound_matvec (and therefore lanczos()/lobpcg) cannot trace a
    streamed engine; lanczos_block drives it eagerly and agrees with the
    plan-resident truth."""
    from distributed_matvec_tpu.solve import lanczos, lanczos_block

    op = build_op_cached()
    n = op.basis.number_states
    es = DistributedEngine(op, n_devices=4, mode="streamed")
    with pytest.raises(NotImplementedError):
        es.bound_matvec()
    with pytest.raises(ValueError, match="lanczos_block"):
        lanczos(es.matvec, v0=es.random_hashed(seed=1), k=1)

    res = lanczos_block(es.matvec, k=2, block_size=2, max_iters=80,
                        seed=3, compute_eigenvectors=True)
    ell = DistributedEngine(op, n_devices=4, mode="ell")
    ref = lanczos(ell.matvec, v0=ell.random_hashed(seed=1), k=2, tol=1e-10)
    np.testing.assert_allclose(res.eigenvalues, ref.eigenvalues,
                               atol=1e-8)
    # eigenvectors come back hashed; residual check through the engine
    v = res.eigenvectors[0]
    assert v.shape == (es.n_devices, es.shard_size)
    hv = np.asarray(es.matvec(v))
    np.testing.assert_allclose(
        hv, res.eigenvalues[0] * np.asarray(v), atol=1e-6)


def test_local_engine_streamed_pointer():
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    op = build_op_cached()
    with pytest.raises(ValueError, match="DistributedEngine"):
        LocalEngine(op, mode="streamed")


# -- selective reorthogonalization (satellite) ------------------------------


def test_selective_reorth_matches_full(rng):
    """Selective (ω-gated window) Lanczos reproduces full-reorth
    eigenvalues to machine precision, including through thick restarts."""
    from distributed_matvec_tpu.parallel.engine import LocalEngine
    from distributed_matvec_tpu.solve import lanczos

    op = build_heisenberg(14, 7)
    op.basis.build()
    n = op.basis.number_states
    eng = LocalEngine(op, mode="ell")
    full = lanczos(eng.matvec, n, k=2, tol=1e-11, seed=4, reorth="full")
    sel = lanczos(eng.matvec, n, k=2, tol=1e-11, seed=4,
                  reorth="selective")
    assert sel.converged and full.converged
    np.testing.assert_allclose(sel.eigenvalues, full.eigenvalues,
                               rtol=1e-12)
    # restart path
    full_r = lanczos(eng.matvec, n, k=1, tol=1e-11, seed=4, reorth="full",
                     max_basis_size=24)
    sel_r = lanczos(eng.matvec, n, k=1, tol=1e-11, seed=4,
                    reorth="selective", max_basis_size=24)
    np.testing.assert_allclose(sel_r.eigenvalues, full_r.eigenvalues,
                               rtol=1e-12)


def test_selective_reorth_fallback_event(rng, monkeypatch):
    """When ω crosses √ε the window block stops, the steps before the
    crossing are kept and the rest of the block runs the full sweep; a
    solver_health event marks the trigger with the step within the block —
    forced here by dropping the threshold to 0, which stops every window
    block at its first step."""
    from distributed_matvec_tpu import obs
    from distributed_matvec_tpu.obs import health as obs_health
    from distributed_matvec_tpu.parallel.engine import LocalEngine
    from distributed_matvec_tpu.solve import lanczos

    op = build_heisenberg(12, 6)
    op.basis.build()
    n = op.basis.number_states
    eng = LocalEngine(op, mode="ell")
    obs.reset_all()
    monkeypatch.setattr(obs_health, "OMEGA_WARN", 0.0)
    try:
        res = lanczos(eng.matvec, n, k=1, tol=1e-10, seed=6,
                      reorth="selective")
        assert res.converged
        evs = [e for e in obs.events("solver_health")
               if e.get("check") == "selective_reorth_fallback"]
        assert evs, "no fallback event despite a zero threshold"
        assert all(e["step"] == 0 for e in evs)
        root = [e for e in obs.events("span") if e["name"] == "lanczos"][-1]
        # one step run and thrown away a stop, no block run twice
        assert root["omega_stops"] == root["steps_discarded"] == len(evs)
        assert root["steps_run"] == res.num_iters + len(evs)
        ref = lanczos(eng.matvec, n, k=1, tol=1e-10, seed=6, reorth="full")
        np.testing.assert_allclose(res.eigenvalues, ref.eigenvalues,
                                   rtol=1e-12)
    finally:
        obs.reset_all()


def test_selective_reorth_pair_sector(rng):
    """Pair-mode (complex momentum sector forced to (re,im)-f64) solves
    stay correct under the selective policy — the window projects J·W
    rows too."""
    from distributed_matvec_tpu.parallel.engine import LocalEngine
    from distributed_matvec_tpu.solve import lanczos

    op = build_heisenberg(10, 5, None, [([*range(1, 10), 0], 1)])
    op.basis.build()
    assert not op.effective_is_real
    update_config(complex_pair="on")
    try:
        eng = LocalEngine(op, mode="ell")
        assert eng.pair
        n = op.basis.number_states
        full = lanczos(eng.matvec, n, k=1, tol=1e-10, seed=2,
                       reorth="full")
        sel = lanczos(eng.matvec, n, k=1, tol=1e-10, seed=2,
                      reorth="selective")
        np.testing.assert_allclose(sel.eigenvalues, full.eigenvalues,
                                   rtol=1e-11)
    finally:
        update_config(complex_pair="auto")
