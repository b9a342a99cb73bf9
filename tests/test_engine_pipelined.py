"""Pipelined distributed applies (DESIGN.md §25) vs the sequential truth.

A ``pipeline_depth >= 2`` apply restructures the schedule — plan fetches
prefetched by worker threads, produce/exchange split programs with the
exchange decomposed into ``ppermute`` rounds (streamed), or the in-program
software pipeline (fused) — but NEVER the arithmetic: exchanges retire in
chunk order and the staged exchange reassembles the monolithic
``all_to_all`` layout element-for-element, so every result here is
asserted bit-identical to the sequential schedule (which is itself
bit-identical to fused).  Plus: the depth knob's parsing/auto policy, the
structural counters, the apply_phases pipeline record, and a REAL
2-process leg where pipelining must cut the measured time-at-barrier.
"""

import os

import jax
import numpy as np
import pytest

from distributed_matvec_tpu import obs
from distributed_matvec_tpu.parallel.distributed import (DistributedEngine,
                                                         _staged_all_to_all)
from distributed_matvec_tpu.utils.config import update_config

from test_operator import build_heisenberg


def _ndev() -> int:
    return len(jax.devices())


needs_8 = pytest.mark.skipif("_ndev() < 8", reason="needs 8 virtual devices")
needs_4 = pytest.mark.skipif("_ndev() < 4", reason="needs 4 virtual devices")


PIPE_CONFIGS = [
    # (n, hw, inv, syms, ndev) — a |G|>1 sector, a trivial group on a
    # wider mesh (D−1 = 3 ppermute rounds), and a complex-character
    # sector (c128 on CPU)
    (12, 6, 1, [([*range(1, 12), 0], 0)], 2),
    (10, 5, None, (), 4),
    (10, 5, None, [([*range(1, 10), 0], 1)], 4),
]


def _build(n, hw, inv, syms):
    op = build_heisenberg(n, hw, inv, syms)
    op.basis.build()
    return op


@pytest.mark.parametrize("mode", ["streamed", "fused"])
@pytest.mark.parametrize("n,hw,inv,syms,ndev", PIPE_CONFIGS)
def test_pipelined_bit_identical(mode, n, hw, inv, syms, ndev, rng):
    """Acceptance: pipelined y == sequential y to the BIT — fused and
    streamed, real and complex sectors, multi-round staged exchange."""
    if _ndev() < ndev:
        pytest.skip(f"needs {ndev} devices")
    op = _build(n, hw, inv, syms)
    x = rng.random(op.basis.number_states) - 0.5
    if not op.effective_is_real:
        x = x.astype(np.complex128)
    seq = DistributedEngine(op, n_devices=ndev, mode=mode, batch_size=32,
                            pipeline_depth=0)
    pipe = DistributedEngine(op, n_devices=ndev, mode=mode, batch_size=32,
                             pipeline_depth=4)
    assert seq.pipeline_depth == 0
    assert pipe.pipeline_depth >= 2
    ys = np.asarray(seq.matvec(seq.to_hashed(x)))
    yp = np.asarray(pipe.matvec(pipe.to_hashed(x)))
    np.testing.assert_array_equal(ys, yp)


@needs_8
def test_pipelined_batch_and_wide_batch_bit_identical(rng):
    """k<=4 batches ride one pipelined stream; k=6 splits into column
    groups that each re-stream — both bit-identical to sequential."""
    op = _build(10, 5, None, ())
    n = op.basis.number_states
    seq = DistributedEngine(op, n_devices=8, mode="streamed", batch_size=32,
                            pipeline_depth=0)
    pipe = DistributedEngine(op, n_devices=8, mode="streamed", batch_size=32,
                             pipeline_depth=2)
    for k in (3, 6):
        X = rng.random((n, k)) - 0.5
        Ys = np.asarray(seq.matvec(seq.to_hashed(X)))
        Yp = np.asarray(pipe.matvec(pipe.to_hashed(X)))
        np.testing.assert_array_equal(Ys, Yp)


@needs_4
def test_depth_sweep_and_clamp(rng):
    """Every depth >= 2 gives the same bits; depth is clamped to the
    chunk count (streamed) and to 2 (fused — the in-program pipeline is
    one in-flight exchange deep)."""
    op = _build(10, 5, None, ())
    x = rng.random(op.basis.number_states) - 0.5
    seq = DistributedEngine(op, n_devices=4, mode="streamed", batch_size=32,
                            pipeline_depth=0)
    ys = np.asarray(seq.matvec(seq.to_hashed(x)))
    nchunks = seq._plan_nchunks_v
    assert nchunks >= 2
    for depth in (2, 3, nchunks + 7):
        pipe = DistributedEngine(op, n_devices=4, mode="streamed",
                                 batch_size=32, pipeline_depth=depth)
        assert pipe.pipeline_depth == min(depth, nchunks)
        np.testing.assert_array_equal(
            ys, np.asarray(pipe.matvec(pipe.to_hashed(x))))
    fp = DistributedEngine(op, n_devices=4, mode="fused", batch_size=32,
                           pipeline_depth=6)
    assert fp.pipeline_depth == 2       # reported honestly
    np.testing.assert_array_equal(
        ys, np.asarray(fp.matvec(fp.to_hashed(x))))


@needs_4
def test_counters_preserved_and_overflow_still_raises(rng):
    """Structural overflow/invalid totals are identical between the
    schedules, and a deliberately tiny exchange capacity still fails
    loudly through the pipelined fused program."""
    op = _build(10, 5, None, ())
    seq = DistributedEngine(op, n_devices=4, mode="streamed", batch_size=32,
                            pipeline_depth=0)
    pipe = DistributedEngine(op, n_devices=4, mode="streamed", batch_size=32,
                             pipeline_depth=3)
    assert (pipe._stream_overflow, pipe._stream_invalid) \
        == (seq._stream_overflow, seq._stream_invalid)
    x = rng.random(op.basis.number_states) - 0.5
    cfg = update_config(remote_buffer_size=8)
    try:
        with pytest.warns(RuntimeWarning, match="capacity"):
            eng = DistributedEngine(op, n_devices=4, mode="fused",
                                    batch_size=32, pipeline_depth=2)
        with pytest.raises(RuntimeError, match="overflowed"):
            eng.matvec(eng.to_hashed(x))
    finally:
        update_config(remote_buffer_size=150_000)


def test_knob_parsing_and_mode_applicability():
    """Constructor beats config; junk values are loud; single-program
    plan modes (ell) always resolve depth 0."""
    op = _build(10, 5, None, ())
    cfg = update_config(pipeline="3")
    try:
        eng = DistributedEngine(op, n_devices=2, mode="streamed",
                                batch_size=32)
        assert eng.pipeline_depth == 3
        eng0 = DistributedEngine(op, n_devices=2, mode="streamed",
                                 batch_size=32, pipeline_depth=0)
        assert eng0.pipeline_depth == 0
        ell = DistributedEngine(op, n_devices=2, mode="ell")
        assert ell.pipeline_depth == 0
        with pytest.raises(ValueError, match="pipeline depth"):
            DistributedEngine(op, n_devices=2, mode="streamed",
                              batch_size=32, pipeline_depth="sideways")
    finally:
        update_config(pipeline="off")


def test_auto_depth_policy():
    """`auto` consults the §22 cost model: multi-chunk streamed applies
    (whose plan stream dominates the hideable time) pick the deep
    setting; a single-chunk apply stays off."""
    from distributed_matvec_tpu.obs import roofline as R

    cal = R.default_calibration("cpu")
    counts = {"plan_h2d": {"bytes": 10_000_000},
              "compute": {"bytes": 0, "gathers": 0, "flops": 1_000_000},
              "exchange": {"bytes": 100_000},
              "accumulate": {"gathers": 1000}}
    assert R.choose_pipeline_depth(counts, cal, 1, 2) == 0
    assert R.choose_pipeline_depth(counts, cal, 8, 2) == R.AUTO_PIPELINE_DEEP
    # nothing hideable: no stream, no exchange worth the bookkeeping
    lean = {"plan_h2d": {"bytes": 0},
            "compute": {"gathers": 10_000_000},
            "exchange": {"bytes": 0},
            "accumulate": {"gathers": 1000}}
    assert R.choose_pipeline_depth(lean, cal, 8, 2) == 0
    op = _build(10, 5, None, ())
    eng = DistributedEngine(op, n_devices=2, mode="streamed", batch_size=32,
                            pipeline_depth="auto")
    assert eng.pipeline_depth in (0, 2, R.AUTO_PIPELINE_DEEP)


def test_staged_exchange_equals_all_to_all(rng):
    """The ppermute decomposition reassembles the monolithic all_to_all
    layout element-for-element (the §25 bit-identity cornerstone)."""
    if _ndev() < 4:
        pytest.skip("needs 4 devices")
    from jax.sharding import Mesh, PartitionSpec as P

    from distributed_matvec_tpu.parallel.mesh import SHARD_AXIS

    D, cap = 4, 6
    mesh = Mesh(np.array(jax.devices()[:D]), (SHARD_AXIS,))
    x = rng.random((D, D, cap))

    def mono(a):
        return jax.lax.all_to_all(a[0], SHARD_AXIS, 0, 0, tiled=True)[None]

    def staged(a):
        return _staged_all_to_all(a[0], SHARD_AXIS)[None]

    spec = P(SHARD_AXIS, None, None)
    f_mono = jax.shard_map(mono, mesh=mesh, in_specs=(spec,),
                           out_specs=spec)
    f_staged = jax.shard_map(staged, mesh=mesh, in_specs=(spec,),
                             out_specs=spec)
    np.testing.assert_array_equal(np.asarray(jax.jit(f_mono)(x)),
                                  np.asarray(jax.jit(f_staged)(x)))


@needs_4
def test_apply_phases_pipeline_record(rng):
    """Pipelined applies emit the measured overlap/time-at-barrier split
    (depth, barrier_ms, hidden_ms, overlap_fraction) and a measured
    `exchange` phase; sequential applies don't grow a pipeline record."""
    op = _build(10, 5, None, ())
    x = rng.random(op.basis.number_states) - 0.5
    seq = DistributedEngine(op, n_devices=4, mode="streamed", batch_size=32,
                            pipeline_depth=0)
    pipe = DistributedEngine(op, n_devices=4, mode="streamed", batch_size=32,
                             pipeline_depth=3)
    seq.matvec(seq.to_hashed(x))
    pipe.matvec(pipe.to_hashed(x))
    evs = [e for e in obs.events("apply_phases")
           if e.get("engine") == "distributed"
           and e.get("mode") == "streamed"]
    assert len(evs) >= 2
    assert "pipeline" not in evs[-2]
    p = evs[-1]["pipeline"]
    assert p["depth"] == 3
    assert p["barrier_ms"] >= 0.0
    assert p["hidden_ms"] >= 0.0
    assert p["overlap_fraction"] is None or 0.0 <= p["overlap_fraction"] <= 1.0
    assert evs[-1]["phases"]["exchange"].get("wall_ms") is not None
    # the roofline report groups the two schedules side by side and
    # prices measured-vs-priced
    from distributed_matvec_tpu.obs import roofline as R

    rep = R.roofline_report(evs, R.default_calibration("cpu"))
    assert "distributed/streamed" in rep["groups"]
    pg = rep["groups"].get("distributed/streamed+pipe3")
    assert pg and pg["pipeline_depth"] == 3
    assert pg.get("measured_speedup") is not None
    assert pg.get("priced_speedup") is not None


@needs_4
def test_prefetcher_error_propagates(rng, monkeypatch):
    """A worker-thread fetch failure surfaces on the apply thread as the
    original exception (the sequential degrade contract, not a hang)."""
    op = _build(10, 5, None, ())
    x = rng.random(op.basis.number_states) - 0.5
    pipe = DistributedEngine(op, n_devices=4, mode="streamed", batch_size=32,
                             pipeline_depth=2)
    pipe.matvec(pipe.to_hashed(x))          # healthy warm-up

    def boom(ci, degrade=True):
        raise OSError(f"synthetic fetch failure on chunk {ci}")

    monkeypatch.setattr(pipe, "_fetch_plan_chunk", boom)
    with pytest.raises(OSError, match="synthetic fetch failure"):
        pipe.matvec(pipe.to_hashed(x))


def test_multihost_pipelined_barrier_cut(tmp_path):
    """A REAL 2-process run (multihost worker, DMT_MH_PIPE leg): with a
    deterministic per-chunk staging latency injected on rank 1 only, the
    pipelined run must cut the measured time-at-barrier vs the sequential
    run AND speed up the straggling rank's applies — asserted from the
    recorded telemetry the way `obs_report report --ranks` computes it.
    The bound here is 1.5x: this leg runs inside the (heavily loaded)
    tier-1 suite, where scheduler jitter eats into the cut (CPU runs
    alone read 4-34x).  It is the one place this is asserted:
    `make pipeline-check` compares counts only."""
    import importlib.util
    import re
    import socket
    import subprocess
    import sys as _sys

    spec = importlib.util.spec_from_file_location(
        "obs_report", os.path.join(os.path.dirname(__file__), os.pardir,
                                   "tools", "obs_report.py"))
    rep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(rep)
    worker = os.path.join(os.path.dirname(__file__), "multihost_worker.py")
    base_env = {k: v for k, v in os.environ.items()
                if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    base_env["DMT_FAULT"] = "plan_upload:delay=12:n=1000000:rank=1"
    base_env["DMT_MH_PIPE_APPLIES"] = "6"

    waits, steady = {}, {}
    for leg, depth in (("seq", 0), ("pipe", 4)):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        run = tmp_path / f"run_{leg}"
        env = dict(base_env, DMT_MH_PIPE=str(depth),
                   DMT_OBS_DIR=str(run))
        procs = [subprocess.Popen(
            [_sys.executable, worker, str(pid), "2", str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env) for pid in range(2)]
        outs = []
        try:
            for p in procs:
                out, _ = p.communicate(timeout=300)
                outs.append(out)
        except subprocess.TimeoutExpired:
            for p in procs:
                p.kill()
            raise
        for pid, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"{leg} worker {pid}:\n{out[-2000:]}"
            assert f"[p{pid}] MULTIHOST_OK" in out, out[-2000:]
        m = re.search(r"\[p1\] PIPE_STEADY_MS ([0-9.]+)", outs[1])
        assert m, outs[1][-2000:]
        steady[leg] = float(m.group(1))
        table = rep.rank_table(rep.load_events(str(run)))
        rows = {row["rank"]: row for row in table["rows"]}
        waits[leg] = float(rows[0]["barrier_wait_ms"] or 0.0)
    cut = waits["seq"] / max(waits["pipe"], 1e-9)
    assert cut >= 1.5, (waits, steady)
    assert steady["pipe"] <= steady["seq"], (waits, steady)


def test_pipelined_disk_tier_corrupt_chunk_repairs_on_apply_thread(
        rng, tmp_path, monkeypatch):
    """A corrupt disk-tier sidecar chunk under a PIPELINED apply: the
    prefetch worker only MARKS the read failure (degrade=False), the
    repair (per-chunk rebuild from structure) runs on the apply thread
    exactly as in the sequential schedule, prefetching resumes for the
    chunks still ahead, and the result stays bit-identical."""
    import gc

    import h5py

    from distributed_matvec_tpu.utils.config import get_config

    monkeypatch.setenv("DMT_ARTIFACT_CACHE", "on")
    monkeypatch.setenv("DMT_ARTIFACT_DIR", str(tmp_path / "art"))
    old = get_config().stream_plan_ram_gb
    update_config(stream_plan_ram_gb=0.0)
    try:
        op = _build(12, 6, None, ())
        x = rng.random(op.basis.number_states) - 0.5
        e1 = DistributedEngine(op, n_devices=2, mode="streamed",
                               batch_size=64, pipeline_depth=0)
        y_ref = np.asarray(e1.matvec(e1.to_hashed(x)))
        assert e1._plan_chunks is None, "disk tier must be active"
        path = list(e1._plan_disk.values())[0]
        del e1
        gc.collect()

        e2 = DistributedEngine(op, n_devices=2, mode="streamed",
                               batch_size=64, pipeline_depth=3)
        assert e2.structure_restored and e2._plan_chunks is None
        for fobj in list(e2._plan_files.values()):
            fobj.close()
        e2._plan_files.clear()
        with h5py.File(path, "r+") as f:
            f["engine_structure"]["dest_0_1"][...] = 0   # mid-stream chunk
        y = np.asarray(e2.matvec(e2.to_hashed(x)))
        np.testing.assert_array_equal(y, y_ref)
        assert any(e["kind"] == "plan_chunk_rebuilt" for e in obs.events())
    finally:
        update_config(stream_plan_ram_gb=old)
