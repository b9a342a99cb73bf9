"""Fault-tolerance layer: deterministic injection, retry/quarantine I/O,
preemption-safe solves, heartbeat watchdog (ISSUE 6).

In-process legs of the chaos story (`tools/fault_check.py` drives the
subprocess kill/resume legs): the ``DMT_FAULT`` registry semantics and its
provable inertness when unset (no-op singleton + byte-identical apply
HLO, the ``DMT_OBS=off`` guard style), the bounded-retry helper, the
corrupt-artifact rebuild/quarantine policy on every existing failure path
(basis checkpoint, structure sidecar, streamed disk-tier plan chunks),
the concurrent-writer atomicity of ``os.replace`` sidecar saves, the
SIGTERM latch → generation-consistent checkpoint → ``Preempted`` contract
in both Lanczos and LOBPCG, and the stall watchdog's report."""

import gc
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_matvec_tpu import obs
from distributed_matvec_tpu.solve import lanczos, lanczos_block, lobpcg
from distributed_matvec_tpu.utils import faults, preempt
from distributed_matvec_tpu.utils.config import get_config, update_config
from test_operator import build_heisenberg


@pytest.fixture
def clean_faults(monkeypatch):
    """Fresh fault registry + latch + obs state; everything restored."""
    monkeypatch.delenv("DMT_FAULT", raising=False)
    faults.reset()
    preempt.reset()
    obs.reset_all()
    yield monkeypatch
    faults.reset()
    preempt.reset()
    obs.reset_all()


def _arm(monkeypatch, spec):
    monkeypatch.setenv("DMT_FAULT", spec)
    faults.reset()


# ---------------------------------------------------------------------------
# registry semantics


def test_faults_unset_is_noop_singleton(clean_faults):
    """Unset → the shared null registry: check() is inert for every site
    and no state/instrument is created."""
    assert not faults.enabled()
    r1 = faults._registry()
    faults.check("exchange")
    faults.check("anything_at_all", exc=RuntimeError)
    assert faults._registry() is r1 is faults._NULL
    assert faults.fired_count("exchange") == 0
    assert obs.events() == []


def test_fault_fires_then_heals(clean_faults):
    """Default n=1: exactly one failure, then the site is spent — the
    shape every retry path needs."""
    _arm(clean_faults, "artifact_read")
    with pytest.raises(OSError, match=r"\[fault-injection\]"):
        faults.check("artifact_read")
    faults.check("artifact_read")          # healed
    assert faults.fired_count("artifact_read") == 1
    kinds = [e["kind"] for e in obs.events()]
    assert "fault_injected" in kinds
    assert obs.snapshot()["counters"][
        "fault_injected{site=artifact_read}"] == 1


def test_fault_spec_fields(clean_faults):
    """skip/n windows and caller-chosen exception types."""
    _arm(clean_faults, "s:skip=2:n=2")
    for _ in range(2):
        faults.check("s", exc=RuntimeError)     # skipped
    for _ in range(2):
        with pytest.raises(RuntimeError):
            faults.check("s", exc=RuntimeError)
    faults.check("s", exc=RuntimeError)         # budget spent
    assert faults.fired_count("s") == 2


def test_fault_probability_deterministic(clean_faults):
    """p < 1 draws from a per-site seeded RNG: two processes (registries)
    with the same spec fire on the same call sequence."""
    def fire_pattern():
        faults.reset()
        hits = []
        for i in range(64):
            try:
                faults.check("p", exc=OSError)
            except OSError:
                hits.append(i)
        return hits

    clean_faults.setenv("DMT_FAULT", "p:p=0.25:n=1000:seed=7")
    a = fire_pattern()
    b = fire_pattern()
    assert a == b and 4 < len(a) < 32


def test_fault_delay_injects_latency_not_error(clean_faults):
    import time

    _arm(clean_faults, "slow:delay=30:n=2")
    t0 = time.perf_counter()
    faults.check("slow")
    dt = time.perf_counter() - t0
    assert dt >= 0.025
    assert faults.fired_count("slow") == 1      # recorded, nothing raised


def test_fault_spec_errors_are_loud(clean_faults):
    """A typo'd chaos spec must not silently test nothing."""
    for bad in ("site:nope=1", "site:p", ":p=1"):
        clean_faults.setenv("DMT_FAULT", bad)
        faults.reset()
        with pytest.raises(faults.FaultSpecError):
            faults.check("site")
    faults.reset()


def test_with_retries_heals_and_exhausts(clean_faults):
    calls = []

    def flaky(fail_times):
        def fn():
            calls.append(1)
            if len(calls) <= fail_times:
                raise OSError("transient")
            return "ok"
        return fn

    assert faults.with_retries("t", flaky(2), attempts=3,
                               base_s=0.001) == "ok"
    assert len(calls) == 3
    assert obs.snapshot()["counters"]["io_retry{site=t}"] == 2
    calls.clear()
    with pytest.raises(OSError):
        faults.with_retries("t", flaky(99), attempts=3, base_s=0.001)
    assert len(calls) == 3


def test_apply_hlo_byte_identical_with_faults_armed(clean_faults):
    """The acceptance guard: every fault site is host-side, so the
    compiled apply program is byte-identical whether DMT_FAULT is armed
    or not (same contract as the DMT_OBS=off / health-probe guards)."""
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    op = build_heisenberg(10, 5)
    op.basis.build()
    eng = LocalEngine(op)
    x = np.random.default_rng(0).standard_normal(op.basis.number_states)

    def hlo():
        return jax.jit(eng._apply_fn).lower(
            jnp.asarray(x), eng._operands).compile().as_text()

    base = hlo()
    _arm(clean_faults, "exchange,plan_upload:n=3,artifact_read:p=0.5")
    assert faults.enabled()
    assert hlo() == base


# ---------------------------------------------------------------------------
# corrupt-artifact rebuild + quarantine (the existing failure paths,
# finally exercised by injected failures)


def test_corrupt_basis_artifact_rebuilds_then_quarantines(
        clean_faults, tmp_path):
    """A truncated basis checkpoint in the artifact cache must rebuild
    (not crash), count artifact_cache{event=corrupt}, and be quarantined
    into .quarantine/ on the second failing read."""
    from distributed_matvec_tpu.utils.artifacts import (artifact_path,
                                                        basis_fingerprint,
                                                        make_or_restore_basis)

    clean_faults.setenv("DMT_ARTIFACT_CACHE", "on")
    clean_faults.setenv("DMT_ARTIFACT_DIR", str(tmp_path / "art"))
    op = build_heisenberg(10, 5)
    basis = op.basis
    path = artifact_path("basis", basis_fingerprint(basis), ".h5")
    with open(path, "wb") as f:
        f.write(b"\x89HDF\r\n\x1a\nthis is not a real hdf5 file")

    assert make_or_restore_basis(basis, save=False) is False
    assert basis.is_built                       # rebuilt despite the file
    c = obs.snapshot()["counters"]
    assert c["artifact_cache{event=corrupt,kind=basis}"] == 1
    assert os.path.exists(path)                 # first failure: kept

    # the path fails AGAIN (persistent bit-rot): quarantined, and the
    # post-rebuild save then heals the cache with a fresh checkpoint
    b2 = build_heisenberg(10, 5).basis
    assert make_or_restore_basis(b2) is False and b2.is_built
    qdir = os.path.join(os.path.dirname(path), ".quarantine")
    assert os.path.isdir(qdir) and len(os.listdir(qdir)) == 1
    kinds = [e["kind"] for e in obs.events()]
    assert "artifact_quarantine" in kinds
    # third construction restores the healed checkpoint
    b3 = build_heisenberg(10, 5).basis
    assert make_or_restore_basis(b3) is True


def test_corrupt_structure_checkpoint_rebuilds(clean_faults, tmp_path):
    """An unreadable explicit structure sidecar is a miss (engine builds
    fresh and overwrites it), not an error."""
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine

    op = build_heisenberg(10, 5)
    op.basis.build()
    cache = str(tmp_path / "plan.h5")
    sidecar = f"{cache}.dist2.structure.h5"
    with open(sidecar, "wb") as f:
        f.write(b"garbage" * 64)
    eng = DistributedEngine(op, n_devices=2, mode="ell",
                            structure_cache=cache)
    assert not eng.structure_restored
    assert obs.snapshot()["counters"][
        "artifact_cache{event=corrupt,kind=structure}"] >= 1
    # the fresh build replaced the sidecar atomically; a second engine
    # restores it
    eng2 = DistributedEngine(op, n_devices=2, mode="ell",
                             structure_cache=cache)
    assert eng2.structure_restored


def test_os_replace_concurrent_writers(tmp_path):
    """Two writers hammering the same sidecar path while a reader loops:
    the reader must only ever observe a complete, fingerprint-valid file
    (the os.replace atomicity the save path promises)."""
    from distributed_matvec_tpu.io.hdf5 import (load_engine_structure,
                                                save_engine_structure)

    path = str(tmp_path / "race.h5")
    payload = {"a": np.arange(4096), "b": np.ones(1000)}
    stop = threading.Event()
    errors = []

    def writer(tag):
        i = 0
        while not stop.is_set():
            try:
                save_engine_structure(path, f"fp-{tag}", "ell",
                                      dict(payload, tag=tag))
            except Exception as e:       # pragma: no cover
                errors.append(e)
                return
            i += 1

    threads = [threading.Thread(target=writer, args=(t,))
               for t in ("w0", "w1")]
    for t in threads:
        t.start()
    good = 0
    try:
        # the reads below race the writers only once a first file is there:
        # on a loaded machine 200 reads can finish before the first save
        deadline = time.monotonic() + 60
        while not os.path.exists(path) and not errors \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        for _ in range(200):
            for fp in ("fp-w0", "fp-w1"):
                got = load_engine_structure(path, fp)
                if got is not None:
                    # complete: the payload written with that fingerprint
                    assert got["tag"] == fp[3:]
                    assert got["a"].shape == (4096,)
                    good += 1
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert not errors
    assert good > 0                       # the race actually exercised reads


def test_stream_disk_tier_corrupt_chunk_rebuilds(clean_faults, tmp_path):
    """Satellite: a corrupt ``*.stream.h5`` sidecar chunk on the DISK tier
    logs artifact_cache{event=corrupt} and rebuilds that chunk's plan from
    structure bit-identically instead of raising mid-apply; the sidecar's
    second failure quarantines it and the plan returns to host RAM."""
    import h5py

    from distributed_matvec_tpu.parallel.distributed import DistributedEngine

    clean_faults.setenv("DMT_ARTIFACT_CACHE", "on")
    clean_faults.setenv("DMT_ARTIFACT_DIR", str(tmp_path / "art"))
    old = get_config().stream_plan_ram_gb
    update_config(stream_plan_ram_gb=0.0)
    try:
        op = build_heisenberg(12, 6)
        op.basis.build()
        n = op.basis.number_states
        x = np.random.default_rng(3).standard_normal(n)

        e1 = DistributedEngine(op, n_devices=2, mode="streamed")
        xh = e1.to_hashed(x)
        y_ref = np.asarray(e1.matvec(xh))
        assert e1._plan_chunks is None, "disk tier must be active"
        path = list(e1._plan_disk.values())[0]
        del e1, xh
        gc.collect()

        e2 = DistributedEngine(op, n_devices=2, mode="streamed")
        assert e2.structure_restored and e2._plan_chunks is None

        def corrupt():
            for fobj in list(e2._plan_files.values()):
                fobj.close()
            e2._plan_files.clear()
            with h5py.File(path, "r+") as f:
                f["engine_structure"]["dest_0_0"][...] = 0

        corrupt()
        y = np.asarray(e2.matvec(e2.to_hashed(x)))
        np.testing.assert_array_equal(y, y_ref)
        c = obs.snapshot()["counters"]
        assert c["artifact_cache{event=corrupt,kind=stream_plan}"] == 1
        assert any(e["kind"] == "plan_chunk_rebuilt" for e in obs.events())
        assert os.path.exists(path)          # first failure: kept

        # second corruption: quarantine + full rebuild back into RAM
        corrupt()
        e2._plan_repaired.clear()
        y = np.asarray(e2.matvec(e2.to_hashed(x)))
        np.testing.assert_array_equal(y, y_ref)
        assert not os.path.exists(path)
        assert e2._plan_chunks is not None and e2._plan_disk is None
        c = obs.snapshot()["counters"]
        assert c["artifact_cache{event=quarantine,kind=stream_plan}"] == 1
    finally:
        update_config(stream_plan_ram_gb=old)


def test_stream_ram_restore_rejects_corrupt_sidecar(clean_faults, tmp_path):
    """RAM-tier restores verify the per-chunk checksums once up front: a
    corrupt sidecar is a miss (fresh build), never a silently-wrong plan."""
    import h5py

    from distributed_matvec_tpu.parallel.distributed import DistributedEngine

    clean_faults.setenv("DMT_ARTIFACT_CACHE", "on")
    clean_faults.setenv("DMT_ARTIFACT_DIR", str(tmp_path / "art"))
    op = build_heisenberg(12, 6)
    op.basis.build()
    x = np.random.default_rng(3).standard_normal(op.basis.number_states)

    e1 = DistributedEngine(op, n_devices=2, mode="streamed")
    y_ref = np.asarray(e1.matvec(e1.to_hashed(x)))
    root = str(tmp_path / "art")
    sidecars = [os.path.join(dp, f) for dp, _, fs in os.walk(root)
                for f in fs if f.endswith(".stream.h5")]
    assert len(sidecars) == 1
    del e1
    gc.collect()
    with h5py.File(sidecars[0], "r+") as f:
        f["engine_structure"]["coeff_1_0"][...] = 0.5

    e2 = DistributedEngine(op, n_devices=2, mode="streamed")
    assert not e2.structure_restored          # corrupt → miss → rebuild
    y = np.asarray(e2.matvec(e2.to_hashed(x)))
    np.testing.assert_array_equal(y, y_ref)
    assert obs.snapshot()["counters"][
        "artifact_cache{event=corrupt,kind=stream_plan}"] >= 1


def test_fault_site_plan_chunk_read_retries(clean_faults, tmp_path):
    """A transient disk-tier read failure heals inside the apply (bounded
    retry), with io_retry accounting."""
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine

    clean_faults.setenv("DMT_ARTIFACT_CACHE", "on")
    clean_faults.setenv("DMT_ARTIFACT_DIR", str(tmp_path / "art"))
    old = get_config().stream_plan_ram_gb
    update_config(stream_plan_ram_gb=0.0)
    try:
        op = build_heisenberg(12, 6)
        op.basis.build()
        x = np.random.default_rng(3).standard_normal(op.basis.number_states)
        eng = DistributedEngine(op, n_devices=2, mode="streamed")
        assert eng._plan_chunks is None
        y_ref = np.asarray(eng.matvec(eng.to_hashed(x)))
        _arm(clean_faults, "plan_chunk_read:n=1")
        y = np.asarray(eng.matvec(eng.to_hashed(x)))
        np.testing.assert_array_equal(y, y_ref)
        assert faults.fired_count("plan_chunk_read") == 1
        assert obs.snapshot()["counters"][
            "io_retry{site=plan_chunk_read}"] >= 1
    finally:
        update_config(stream_plan_ram_gb=old)


# ---------------------------------------------------------------------------
# preemption-safe solves


def _dense_problem(n=400, seed=3):
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((n, n))
    A = (A + A.T) / 2
    Aj = jnp.asarray(A)
    return A, (lambda x: Aj @ x)


def test_lanczos_preempt_checkpoints_and_resumes_bit_consistent(
        clean_faults, tmp_path):
    """The latch → safe-point checkpoint → Preempted → resume loop, with
    the resumed E0 matching an uninterrupted solve to rtol 1e-12 (the
    ROADMAP acceptance, in-process form)."""
    A, mv = _dense_problem()
    want = lanczos(mv, 400, k=1, tol=1e-11, max_iters=300, check_every=8)
    assert want.converged
    ck = str(tmp_path / "lz.h5")

    preempt.trigger()
    with pytest.raises(preempt.Preempted) as ei:
        lanczos(mv, 400, k=1, tol=1e-11, max_iters=300, check_every=8,
                checkpoint_path=ck, checkpoint_every=100)
    assert ei.value.solver == "lanczos" and ei.value.iters == 8
    kinds = [(e["kind"], e.get("status"), e.get("reason"))
             for e in obs.events()]
    assert ("solver_checkpoint", "written", "preempt") in kinds
    assert any(k == "solver_preempted" for k, _, _ in kinds)

    preempt.reset()
    res = lanczos(mv, 400, k=1, tol=1e-11, max_iters=300, check_every=8,
                  checkpoint_path=ck)
    assert res.resumed_from == 8 and res.converged
    rel = abs(res.eigenvalues[0] - want.eigenvalues[0]) \
        / abs(want.eigenvalues[0])
    assert rel < 1e-12


def test_lanczos_ckpt_write_fault_degrades_softly(clean_faults, tmp_path):
    """An injected checkpoint-write failure must not kill the solve: it
    converges, emits solver_checkpoint{status=failed}, and a later
    generation lands."""
    A, mv = _dense_problem()
    ck = str(tmp_path / "lz.h5")
    _arm(clean_faults, "ckpt_write:n=1")
    res = lanczos(mv, 400, k=1, tol=1e-11, max_iters=300, check_every=8,
                  checkpoint_path=ck, checkpoint_every=1)
    assert res.converged
    statuses = [e.get("status") for e in obs.events()
                if e["kind"] == "solver_checkpoint"]
    assert "failed" in statuses and "written" in statuses


def test_lanczos_block_preempts_cleanly(clean_faults):
    op = build_heisenberg(10, 5)
    op.basis.build()
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    eng = LocalEngine(op)
    preempt.trigger()
    with pytest.raises(preempt.Preempted):
        lanczos_block(eng.matvec, op.basis.number_states, k=2,
                      max_iters=60)
    preempt.reset()


def test_preempt_latch_and_handler_contract(clean_faults):
    """trigger() latches; ensure_installed is idempotent and the handler
    only sets the flag (checked via direct invocation — sending real
    signals inside pytest is rude to the runner)."""
    assert not preempt.requested()
    assert preempt.ensure_installed()
    assert preempt.ensure_installed()       # idempotent
    import signal as _sig

    preempt._handler(_sig.SIGTERM, None)
    assert preempt.requested()
    assert preempt.signal_number() == _sig.SIGTERM
    assert preempt.agreed(False) is True
    preempt.reset()
    assert not preempt.requested()


def test_lobpcg_checkpoint_resume_and_preempt(clean_faults, tmp_path):
    """Satellite: LOBPCG checkpoint/resume parity — a budget-truncated
    segmented solve resumes with cumulative iterations and converges to
    the dense truth; a latched preemption exits at a segment boundary
    with the checkpoint written."""
    op = build_heisenberg(10, 5)
    op.basis.build()
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    eng = LocalEngine(op)
    n = op.basis.number_states
    want = np.linalg.eigvalsh(op.to_sparse().toarray())[0]
    ck = str(tmp_path / "lob.h5")

    evals1, _, it1 = lobpcg(eng.matvec, n, k=1, tol=1e-9, max_iters=12,
                            checkpoint_path=ck, checkpoint_every=6)
    assert it1 <= 12
    evals2, V2, it2 = lobpcg(eng.matvec, n, k=1, tol=1e-9, max_iters=400,
                             checkpoint_path=ck, checkpoint_every=50)
    assert it2 > it1                        # cumulative, resumed
    assert any(e["kind"] == "solver_resume" for e in obs.events())
    np.testing.assert_allclose(evals2[0], want, atol=1e-6)
    assert V2.shape == (n, 1)

    # preemption between segments: checkpoint written, Preempted raised
    os.remove(ck)
    preempt.trigger()
    with pytest.raises(preempt.Preempted) as ei:
        lobpcg(eng.matvec, n, k=1, tol=1e-12, max_iters=400,
               checkpoint_path=ck, checkpoint_every=5)
    assert ei.value.solver == "lobpcg"
    assert os.path.exists(ck)
    preempt.reset()
    evals3, _, it3 = lobpcg(eng.matvec, n, k=1, tol=1e-8, max_iters=400,
                            checkpoint_path=ck, checkpoint_every=100)
    assert it3 > ei.value.iters
    np.testing.assert_allclose(evals3[0], want, atol=1e-5)


def test_lobpcg_checkpoint_keyed_by_operator(clean_faults, tmp_path):
    """A rerun against an edited Hamiltonian of the same size must MISS
    the foreign block (same contract as the Lanczos checkpoints)."""
    from distributed_matvec_tpu.models.yaml_io import operator_from_dict
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    op1 = build_heisenberg(10, 5)
    op1.basis.build()
    n = op1.basis.number_states
    ck = str(tmp_path / "lob.h5")
    lobpcg(LocalEngine(op1).matvec, n, k=1, tol=1e-9, max_iters=10,
           checkpoint_path=ck, checkpoint_every=5)

    ham2 = {"terms": [{"expression": "2.5 σᶻ₀ σᶻ₁ + σˣ₀ σˣ₁ + σʸ₀ σʸ₁",
                       "sites": [[i, (i + 1) % 10] for i in range(10)]}]}
    b2 = type(op1.basis)(number_spins=10, hamming_weight=5)
    op2 = operator_from_dict(ham2, b2)
    op2.basis.build()
    obs.reset_all()
    evals, _, _ = lobpcg(LocalEngine(op2).matvec, n, k=1, tol=1e-9,
                         max_iters=400, checkpoint_path=ck,
                         checkpoint_every=100)
    assert not any(e["kind"] == "solver_resume" for e in obs.events())
    want2 = np.linalg.eigvalsh(op2.to_sparse().toarray())[0]
    np.testing.assert_allclose(evals[0], want2, atol=1e-6)


# ---------------------------------------------------------------------------
# obs flush on signal/atexit (satellite)


def test_obs_sink_flush_registered_and_preempt_events_on_disk(
        clean_faults, tmp_path):
    """Opening the sink registers the atexit flush backstop, and the
    preemption path's final events (checkpoint-written included) are on
    disk in rank_0/events.jsonl before the exception even reaches the
    caller — never lost with the process."""
    # NB the events() FUNCTION re-exported by obs/__init__ shadows the
    # submodule on attribute lookup — fetch the module itself
    import importlib

    ev_mod = importlib.import_module("distributed_matvec_tpu.obs.events")

    update_config(obs_dir=str(tmp_path / "obs"))
    try:
        A, mv = _dense_problem()
        ck = str(tmp_path / "lz.h5")
        preempt.trigger()
        with pytest.raises(preempt.Preempted):
            lanczos(mv, 400, k=1, tol=1e-11, max_iters=300, check_every=8,
                    checkpoint_path=ck, checkpoint_every=100)
        assert ev_mod._atexit_registered
        path = os.path.join(str(tmp_path / "obs"), "rank_0",
                            "events.jsonl")
        with open(path) as f:
            lines = [json.loads(line) for line in f if line.strip()]
        kinds = [(e["kind"], e.get("status")) for e in lines]
        assert ("solver_checkpoint", "written") in kinds
        assert ("solver_preempted", None) in kinds
    finally:
        preempt.reset()
        update_config(obs_dir="")


# ---------------------------------------------------------------------------
# heartbeat watchdog


def test_heartbeat_stall_report(clean_faults, tmp_path):
    """A peer whose beat file goes stale past the timeout produces one
    stall_report event naming the rank and its age, and the on_stall hook
    fires exactly once (the default hook aborts; tests capture)."""
    from distributed_matvec_tpu.parallel.heartbeat import HeartbeatWatchdog

    d = str(tmp_path / "run")
    hb_dir = os.path.join(d, "heartbeat")
    os.makedirs(hb_dir)
    stale = os.path.join(hb_dir, "rank_1.hb")
    with open(stale, "w") as f:
        f.write("0\n")
    os.utime(stale, (1.0, 1.0))            # beat from 1970: definitely stale

    reports = []
    wd = HeartbeatWatchdog(d, interval_s=0.05, timeout_s=5.0, rank=0,
                           n_ranks=2, on_stall=reports.append)
    wd.start()
    t = wd._thread
    assert t is not None
    t.join(timeout=10)
    assert not t.is_alive(), "watchdog thread never reported the stall"
    wd.stop()
    assert len(reports) == 1
    assert reports[0]["stalled"] == [1]
    # pre-watchdog beat files take the startup grace (a relaunch must not
    # be killed by its dead predecessor's files), so the reported age is
    # measured from watchdog start — ≥ the timeout, rounded to 0.1
    assert reports[0]["ages_s"]["1"] >= 5.0
    evs = [e for e in obs.events() if e["kind"] == "stall_report"]
    assert len(evs) == 1 and evs[0]["stalled"] == [1]
    # this rank's own beat landed
    assert os.path.exists(os.path.join(hb_dir, "rank_0.hb"))


def test_heartbeat_healthy_peers_stay_quiet(clean_faults, tmp_path):
    from distributed_matvec_tpu.parallel.heartbeat import HeartbeatWatchdog

    d = str(tmp_path / "run")
    reports = []
    wd = HeartbeatWatchdog(d, interval_s=0.05, timeout_s=60.0, rank=0,
                           n_ranks=2, on_stall=reports.append)
    with wd:
        # peer beats freshly
        peer = HeartbeatWatchdog(d, interval_s=0.05, timeout_s=60.0,
                                 rank=1, n_ranks=2,
                                 on_stall=reports.append)
        peer.beat()
        import time

        time.sleep(0.3)
    assert reports == []
    assert not any(e["kind"] == "stall_report" for e in obs.events())


def test_heartbeat_single_rank_inert(clean_faults, tmp_path):
    from distributed_matvec_tpu.parallel.heartbeat import HeartbeatWatchdog

    wd = HeartbeatWatchdog(str(tmp_path), rank=0, n_ranks=1)
    wd.start()
    assert wd._thread is None               # nothing to watch
    wd.stop()
