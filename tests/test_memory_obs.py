"""Memory observability (obs/memory.py): device-memory ledger, watermark
sampler, compiled-executable analysis, OOM forensics, the capacity planner,
and the ``ell_nbytes`` parity contract.

Runs on the CPU backend, where ``device.memory_stats()`` is None — the
watermark paths are exercised through their soft-fail contract; ledger and
executable analysis carry the load (the advisory mode DESIGN.md §19
documents).
"""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

from distributed_matvec_tpu import obs
from distributed_matvec_tpu.obs import memory as obs_mem

from test_operator import build_heisenberg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def clean_obs():
    obs.reset_all()
    yield
    obs.reset_all()


@pytest.fixture
def obs_off(monkeypatch):
    monkeypatch.setenv("DMT_OBS", "off")


# ---------------------------------------------------------------------------
# ledger


def test_ledger_track_tree_total_release(clean_obs):
    h = obs_mem.track("engine/local:0/structure/idx", 1000, device="hbm")
    obs_mem.track("engine/local:0/structure/coeff", 2000, handle=h)
    obs_mem.track("engine/local:0/diag", 500, handle=h)
    h2 = obs_mem.track("solver/lanczos:0/krylov_basis", 4000)
    assert obs_mem.ledger_total() == 7500
    assert obs_mem.ledger_total("engine/local:0/structure") == 3000
    assert obs_mem.ledger_total("engine") == 3500
    tree = obs_mem.ledger_tree()
    assert tree["bytes"] == 7500
    assert tree["children"]["engine"]["bytes"] == 3500
    assert tree["children"]["engine"]["children"]["local:0"][
        "children"]["structure"]["bytes"] == 3000
    # re-track replaces (a rebuilt table supersedes), set() re-points
    obs_mem.track("engine/local:0/structure/idx", 1500, handle=h)
    assert obs_mem.ledger_total("engine/local:0/structure") == 3500
    h2.set("solver/lanczos:0/krylov_basis", 8000)
    assert obs_mem.ledger_total("solver") == 8000
    h.release()
    assert obs_mem.ledger_total() == 8000
    h.release()                                     # idempotent
    h2.release()
    assert obs_mem.ledger_total() == 0
    # ledger events carry the entry map + total
    obs_mem.track("a/b", 7)
    ev = obs_mem.emit_ledger("unit", n_states=3)
    assert ev["kind"] == "memory_ledger" and ev["total_bytes"] == 7
    assert ev["entries"]["a/b"]["bytes"] == 7 and ev["n_states"] == 3


def test_ledger_track_tree_sums_pytree_leaves(clean_obs):
    import jax.numpy as jnp

    tree = {"a": jnp.zeros(10, jnp.float64),
            "b": (jnp.zeros(4, jnp.int32), jnp.zeros(2, jnp.float64))}
    obs_mem.track_tree("x/t", tree)
    assert obs_mem.ledger_total("x") == 80 + 16 + 16


def test_ledger_disabled_noop(clean_obs, obs_off):
    h = obs_mem.track("a/b", 100)
    assert h is obs_mem.NULL_HANDLE
    assert obs_mem.track_tree("a/c", {}) is obs_mem.NULL_HANDLE
    assert obs_mem.ledger_total() == 0
    assert obs_mem.emit_ledger("unit") is None
    assert obs.events() == []


# ---------------------------------------------------------------------------
# watermark sampler (CPU: soft-fail/advisory contract)


def test_watermark_soft_fail_on_cpu(clean_obs):
    """The CPU client has no memory_stats: the sampler returns None, emits
    nothing, latches unsupported (so the per-apply cadence goes quiet),
    and never raises."""
    assert obs_mem.sample_watermark("unit") is None
    assert obs.events("memory_watermark") == []
    assert obs_mem.last_watermark() is None
    # latched: watermark_due is False even on the cadence boundary
    assert obs_mem.watermark_due(0) is False
    assert obs.snapshot()["gauges"] == {}


def test_watermark_due_cadence_and_disabled(clean_obs, monkeypatch):
    from distributed_matvec_tpu.utils.config import get_config, update_config

    # pretend the backend supports stats (the latch is what CPU flips)
    monkeypatch.setattr(obs_mem, "_wm_unsupported", False)
    saved = get_config().memory_every
    update_config(memory_every=4)
    try:
        assert [i for i in range(9) if obs_mem.watermark_due(i)] == [0, 4, 8]
    finally:
        update_config(memory_every=saved)
    monkeypatch.setenv("DMT_OBS", "off")
    assert obs_mem.watermark_due(0) is False


def test_watermark_event_shape_with_fake_stats(clean_obs, monkeypatch):
    """With stats available (faked — the CPU backend has none), the sample
    publishes rank-tagged events + gauges and feeds last_watermark."""
    rows = [{"device": "tpu:0", "bytes_in_use": 100, "peak_bytes_in_use": 250,
             "bytes_limit": 1000}]
    monkeypatch.setattr(obs_mem, "_device_stats", lambda: rows)
    s = obs_mem.sample_watermark("engine_init/local", extra=1)
    assert s["bytes_in_use"] == 100 and s["peak_bytes"] == 250
    ev = obs.events("memory_watermark")[-1]
    assert ev["tag"] == "engine_init/local" and ev["rank"] == 0
    assert ev["peak_bytes"] == 250 and ev["extra"] == 1
    snap = obs.snapshot()["gauges"]
    assert snap["hbm_bytes_in_use"] == 100
    assert snap["hbm_peak_bytes"] == 250
    assert obs_mem.last_watermark()["peak_bytes"] == 250


# ---------------------------------------------------------------------------
# the sample's layers (PR 37): the fullest device's own row, the ledger on
# that device, ``synced``, the open span; samples where the work happens


class FakeAllocator:
    """``_device_stats`` for the CPU backend, which reports none: a
    device's ``bytes_in_use`` is what ``jax.live_arrays()`` holds on it
    (each buffer once), its peak the highest reading so far."""

    def __init__(self, n_devices=1):
        import jax

        self.devices = jax.local_devices()[:n_devices]
        self.peak = {}

    def __call__(self):
        import jax

        use = {f"{d.platform}:{d.id}": 0 for d in self.devices}
        seen = set()
        for arr in jax.live_arrays():
            for sh in arr.addressable_shards:
                name = f"{sh.device.platform}:{sh.device.id}"
                key = (name, sh.data.unsafe_buffer_pointer())
                if name in use and key not in seen:
                    seen.add(key)
                    use[name] += int(sh.data.nbytes)
        rows = []
        for name, b in use.items():
            self.peak[name] = max(self.peak.get(name, 0), b)
            rows.append({"device": name, "bytes_in_use": b,
                         "peak_bytes_in_use": self.peak[name],
                         "bytes_limit": 16 * 10 ** 9})
        return rows


def _samples_by_span():
    """{span name: [memory_watermark events taken in a span of that name]},
    joined through the envelope's ``span_id`` as a reader does."""
    names = {e["span_id"]: e["name"] for e in obs.events("span")}
    out = {}
    for e in obs.events("memory_watermark"):
        out.setdefault(names.get(e.get("span_id")), []).append(e)
    return out


def test_watermark_sample_layers(clean_obs, monkeypatch):
    """One event says how much is resident on the fullest device, how much
    of that the ledger owns there, whether the device was waited for, and
    in which span it was taken."""
    rows = [{"device": "tpu:0", "bytes_in_use": 700, "peak_bytes_in_use": 900,
             "bytes_limit": 1000},
            {"device": "tpu:1", "bytes_in_use": 800, "peak_bytes_in_use": 950,
             "bytes_limit": 1000}]
    monkeypatch.setattr(obs_mem, "_device_stats", lambda: rows)
    obs_mem.track("engine/x:0/tables", 400, device="device",
                  per_device={"tpu:0": 300, "tpu:1": 100})
    obs_mem.track("solver/lanczos:0/krylov_basis", 800,
                  per_device={"tpu:0": 400, "tpu:1": 400})
    obs_mem.track("plan/p:0/staging", 50)          # no split: whole on each
    obs_mem.track("plan/s:0/host", 10 ** 6, device="host")
    assert obs_mem.ledger_on("tpu:0") == {"engine": 300, "solver": 400,
                                          "plan": 50}
    with obs.span("lanczos/wait", kind="phase") as sp:
        s = obs_mem.sample_watermark("lanczos/wait", synced=True)
    ev = obs.events("memory_watermark")[-1]
    # the fields obs_report reads stay: the sum beside the maximum
    assert ev["bytes_in_use"] == 1500 and ev["peak_bytes"] == 950
    assert ev["fullest"] == rows[1] and s["fullest"] == rows[1]
    assert ev["ledger"] == {"engine": 100, "solver": 400, "plan": 50}
    assert ev["ledger_bytes"] == 550 and ev["synced"] is True
    assert ev["fullest"]["bytes_in_use"] - ev["ledger_bytes"] == 250
    assert ev["span_id"] == sp.sid
    # not synced unless the caller says so; wait_for waits, then says so
    assert obs_mem.sample_watermark("apply/local")["synced"] is False
    import jax.numpy as jnp
    assert obs_mem.sample_watermark(
        "lanczos/start", wait_for=jnp.zeros(4))["synced"] is True
    # a re-pointed entry forgets the split of its old size
    h = obs_mem.track("solver/b:0/block", 100, per_device={"tpu:1": 25})
    assert obs_mem.ledger_on("tpu:1")["solver"] == 425
    h.set("solver/b:0/block", 200)
    assert obs_mem.ledger_on("tpu:1")["solver"] == 600


def test_track_tree_records_what_each_device_holds(clean_obs):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.local_devices()[:4]
    mesh = Mesh(np.array(devs), ("shards",))
    sharded = jax.device_put(jnp.zeros((6, 4, 32)),
                             NamedSharding(mesh, P(None, "shards")))
    whole = jax.device_put(jnp.zeros(8), NamedSharding(mesh, P()))
    one = jnp.zeros(5, jnp.int32)
    obs_mem.track_tree("solver/t:0/krylov_basis", (sharded, whole, one))
    ent = obs_mem.ledger_entries()["solver/t:0/krylov_basis"]
    assert ent["bytes"] == 6 * 4 * 32 * 8 + 64 + 20      # global, as before
    names = [f"{d.platform}:{d.id}" for d in devs]
    first = f"{one.devices().pop().platform}:{one.devices().pop().id}"
    for name in names:
        # a quarter of the sharded buffer, the whole of the replicated one
        assert ent["per_device"][name] == 6 * 32 * 8 + 64 \
            + (20 if name == first else 0)
    # a host array among the leaves: the split is not known
    obs_mem.track_tree("x/t", (np.zeros(3), one))
    assert "per_device" not in obs_mem.ledger_entries()["x/t"]


def test_sharded_krylov_buffer_is_a_quarter_a_device(clean_obs, monkeypatch):
    """On four devices the solver's entry counts what ONE device holds of
    the sharded buffer, so that resident less ledger is not negative."""
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine
    from distributed_matvec_tpu.solve import lanczos

    monkeypatch.setattr(obs_mem, "_device_stats", FakeAllocator(4))
    op = build_heisenberg(12, 6, None, ())
    eng = DistributedEngine(op, n_devices=4, batch_size=64)
    init = [e for e in obs.events("memory_watermark")
            if e["tag"] == "engine_init/distributed"][-1]
    assert init["synced"] and init["ledger"]["engine"] > 0
    assert init["ledger"]["engine"] < obs_mem.ledger_total("engine")
    assert init["fullest"]["bytes_in_use"] >= init["ledger_bytes"]
    lanczos(eng.matvec, k=1, v0=eng.random_hashed(seed=42), tol=1e-10,
            max_iters=64, max_basis_size=32, compute_eigenvectors=True)
    waits = _samples_by_span()["lanczos/wait"]
    rows = -(-(32 + 1) // 8) * 8                       # _buffer_rows(32)
    quarter = rows * eng.shard_size * 8
    assert waits and all(e["synced"] for e in waits)
    for e in waits:
        # V's quarter (alph and bet, 2 x 256 B, sit on one device)
        assert quarter <= e["ledger"]["solver"] <= quarter + 512
        assert e["fullest"]["bytes_in_use"] - e["ledger_bytes"] >= 0
        assert e["fullest"]["bytes_in_use"] < 4 * quarter


def test_lanczos_solve_samples(clean_obs, monkeypatch):
    """One synced sample under every ``lanczos/wait``, one (not synced:
    their programs are in flight) under ``lanczos/start`` (the buffer
    allocated), ``lanczos/restart`` and ``lanczos/epilogue``; the solver's
    entry in each of them."""
    from distributed_matvec_tpu.parallel.engine import LocalEngine
    from distributed_matvec_tpu.solve import lanczos

    monkeypatch.setattr(obs_mem, "_device_stats", FakeAllocator())
    op = build_heisenberg(16, 8, None, ())
    eng = LocalEngine(op, mode="ell")
    eng.matvec(np.ones(op.basis.number_states))     # apply 0: the cadence's
    obs.reset()                      # events only; the ledger keeps the engine
    res = lanczos(eng.matvec, op.basis.number_states, k=1, tol=1e-10,
                  max_iters=200, max_basis_size=32, compute_eigenvectors=True)
    assert res.converged and res.restarts > 0
    spans = {}
    for e in obs.events("span"):
        spans.setdefault(e["name"], []).append(e["span_id"])
    by = _samples_by_span()
    assert sorted(e["span_id"] for e in by["lanczos/wait"]) \
        == sorted(spans["lanczos/wait"])
    assert all(e["synced"] for e in by["lanczos/wait"])
    # the probe apply is in flight there: nothing waits for it
    assert [e["synced"] for e in by["lanczos/start"]] == [False]
    assert len(by["lanczos/epilogue"]) == 1
    assert sorted(e["span_id"] for e in by["lanczos/restart"]) \
        == sorted(spans["lanczos/restart"])
    nbytes = 40 * op.basis.number_states * 8           # _buffer_rows(32)
    for e in by["lanczos/start"] + by["lanczos/wait"] + by["lanczos/restart"]:
        assert nbytes <= e["ledger"]["solver"] <= nbytes + 512
        assert e["ledger"]["engine"] == obs_mem.ledger_total("engine")
        assert e["fullest"]["bytes_in_use"] >= e["ledger_bytes"]
    # no sample anywhere else in the solve (the probe apply is number 1 of
    # this engine: the eager cadence stays what it was)
    assert set(by) == {"lanczos/start", "lanczos/wait", "lanczos/restart",
                       "lanczos/epilogue"}


@pytest.mark.parametrize("build", ["one_pass", "ranges", "two_pass",
                                   "plan"])
def test_build_samples_every_pass(clean_obs, monkeypatch, build):
    """A build takes one sample under every pass it runs and a synced one
    when it closes; the built engine's own sample waits for the device."""
    from distributed_matvec_tpu.parallel import engine as E
    from distributed_matvec_tpu.utils.config import get_config, update_config

    monkeypatch.setattr(obs_mem, "_device_stats", FakeAllocator(
        2 if build == "plan" else 1))
    # 12,870 rows of unequal width: every build cuts a staircase
    op = build_heisenberg(16, 8, None, ())
    op.basis.build()
    budget = get_config().ell_build_budget_gb
    try:
        if build == "plan":
            from distributed_matvec_tpu.parallel.distributed import (
                DistributedEngine)

            DistributedEngine(op, n_devices=2, batch_size=1024)
            passes = {"plan/count", "plan/resolve", "plan/queries",
                      "plan/pack"}
            close, kind = "engine_init/build_plan", "distributed"
        else:
            if build == "ranges":
                n_pad = E.pad_to_multiple(op.basis.number_states, 1024)
                monkeypatch.setattr(E, "GATHER_VMEM_BYTES", 16 * n_pad - 16)
                assert E.gather_table_ranges(n_pad, 3)[0] > 1
            elif build == "two_pass":
                update_config(ell_build_budget_gb=1e-9)
            E.LocalEngine(op, mode="ell", batch_size=1024)
            passes = {"one_pass": {"ell/fill", "ell/count",
                                   "ell/stair_levels"},
                      "ranges": {"ell/fill", "ell/stair_levels"},
                      "two_pass": {"ell/count_rows", "ell/row_order",
                                   "ell/pack", "ell/cut"}}[build]
            close, kind = "engine_init/build_structure", "local"
    finally:
        update_config(ell_build_budget_gb=budget)
    ran = {}
    for e in obs.events("span"):
        if e["name"].startswith(("ell/", "plan/")):
            ran.setdefault(e["name"], []).append(e["span_id"])
    assert set(ran) == passes
    by = _samples_by_span()
    for name, ids in ran.items():
        assert sorted(e["span_id"] for e in by[name]) == sorted(ids), name
    assert [e["synced"] for e in by[close]] == [True]
    built = [e for e in obs.events("memory_watermark")
             if e["tag"] == f"engine_init/{kind}"]
    assert len(built) == 1 and built[0]["synced"]
    # all of the engine on one chip, a shard's share of it on a mesh
    held, total = built[0]["ledger"]["engine"], obs_mem.ledger_total("engine")
    assert held == total if kind == "local" else total / 2 <= held < total
    assert built[0]["fullest"]["bytes_in_use"] >= built[0]["ledger_bytes"]
    # the passes that end in a wait say so
    for name in ("ell/fill", "ell/count", "ell/count_rows", "ell/pack",
                 "plan/count"):
        assert all(e["synced"] for e in by.get(name, []))


def test_samples_disabled_noop(clean_obs, monkeypatch):
    """``DMT_OBS=off``: a build and a solve take no sample and wait for
    nothing on the sampler's account; ``span`` is the shared null context."""
    from distributed_matvec_tpu.obs import trace as obs_trace
    from distributed_matvec_tpu.parallel.engine import LocalEngine
    from distributed_matvec_tpu.solve import lanczos

    monkeypatch.setenv("DMT_OBS", "off")
    obs.reset_all()

    def explode(*a, **k):
        raise AssertionError("memory layer touched while disabled")

    monkeypatch.setattr(obs_mem, "_device_stats", explode)
    monkeypatch.setattr(obs_mem, "ledger_on", explode)
    assert obs.span("lanczos/wait", kind="phase") is obs_trace._NULL_CM
    assert obs_mem.sample_watermark("x", wait_for=object()) is None
    op = build_heisenberg(10, 5, None, ())
    eng = LocalEngine(op, mode="ell")
    lanczos(eng.matvec, op.basis.number_states, k=1, max_iters=32,
            tol=1e-10, compute_eigenvectors=True)
    assert obs.events() == [] and obs_mem.ledger_total() == 0


# ---------------------------------------------------------------------------
# ell_nbytes parity: reported totals == summed nbytes of live table leaves
# for EVERY engine mode (the hand-maintained totals this PR derives from
# structure_arrays(); these tests hand-enumerate the expected leaves so a
# new table added without registration fails loudly)


def _leaf_bytes(tree):
    import jax

    return sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("mode", ["ell", "ell_staircase", "compact",
                                  "fused"])
def test_local_ell_nbytes_parity(clean_obs, mode):
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    # 252 rows keep the plain table; 12,870 take the staircase and its
    # row-order array
    stair = mode == "ell_staircase"
    op = build_heisenberg(*((16, 8) if stair else (10, 5)), None, ())
    mode = "ell" if stair else mode
    eng = LocalEngine(op, mode=mode)
    if mode == "ell":
        assert (eng._ell_pos_of is not None) == stair
        expected = sum(i.nbytes + c.nbytes for i, c in eng._ell_levels)
        if stair:
            expected += eng._ell_pos_of.nbytes
    elif mode == "compact":
        expected = (eng._c_idx.nbytes + eng._c_inv_n.nbytes
                    + eng._c_n_parts.nbytes)
        if eng._c_tail is not None:
            expected += sum(a.nbytes for a in eng._c_tail)
    else:
        expected = 0
    assert eng.ell_nbytes == expected
    assert _leaf_bytes(eng.structure_arrays()) == expected
    # and the ledger registered exactly those bytes under structure/
    assert obs.ledger_total(
        f"engine/{eng._mem_instance}/structure") == expected


@pytest.mark.parametrize("mode", ["ell", "compact", "fused"])
def test_distributed_ell_nbytes_parity(clean_obs, mode):
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine

    op = build_heisenberg(10, 5, None, ())
    eng = DistributedEngine(op, n_devices=4, mode=mode, batch_size=64)
    if mode == "ell":
        expected = (eng._ell_idx.nbytes + eng._ell_coeff.nbytes
                    + eng._qin.nbytes)
        if eng._ell_tail is not None:
            expected += sum(a.nbytes for a in eng._ell_tail)
    elif mode == "compact":
        # includes the derived norm tables the pre-PR hand-maintained
        # total silently dropped (it reported 0 for compact)
        expected = (eng._c_idx.nbytes + eng._qin.nbytes
                    + eng._c_inv_n.nbytes + eng._c_n_parts.nbytes
                    + eng._c_norms.nbytes)
        if eng._c_tail is not None:
            expected += sum(a.nbytes for a in eng._c_tail)
    else:
        expected = 0
    assert eng.ell_nbytes == expected
    assert _leaf_bytes(eng.structure_arrays()) == expected
    assert obs.ledger_total(
        f"engine/{eng._mem_instance}/structure") == expected


# ---------------------------------------------------------------------------
# engine integration: ledger registration, planner context, analyses


def test_engine_init_emits_ledger_with_planner_context(clean_obs):
    from distributed_matvec_tpu.parallel.engine import (LocalEngine,
                                                        clear_program_cache)

    op = build_heisenberg(10, 5, None, ())
    clear_program_cache()           # deterministic cold compile → analyses
    eng = LocalEngine(op, mode="ell")
    led = obs.events("memory_ledger")
    assert led, "engine init emitted no memory_ledger event"
    ev = led[-1]
    assert ev["context"] == "engine_init/local"
    assert ev["mode"] == "ell" and ev["engine"] == "local"
    assert ev["n_states"] == op.basis.number_states
    assert ev["table_bytes"] == eng.ell_nbytes
    assert ev["T0"] == eng.ell_width and ev["num_terms"] == eng.num_terms
    assert eng.ell_width == -(-sum(
        i.shape[0] * i.shape[1] for i, _ in eng._ell_levels) // eng.n_padded)
    assert ev["total_bytes"] >= ev["table_bytes"]
    # every resident group is attributed under this engine instance
    base = f"engine/{eng._mem_instance}"
    for part in ("operator_tables", "lookup", "basis_rows", "diag"):
        assert obs_mem.ledger_entries().get(f"{base}/{part}"), part
    # the cold build captured executable analyses for the AOT programs
    anas = obs.events("memory_analysis")
    assert anas and all("argument_bytes" in a and "temp_bytes" in a
                        for a in anas)
    assert any(a["program"] == "ell_fill_chunk" for a in anas)
    # table-bytes gauge mirrors the property
    assert obs.snapshot()["gauges"][
        "engine_table_bytes{engine=local}"] == eng.ell_nbytes


def test_engine_ledger_released_on_gc(clean_obs):
    import gc

    from distributed_matvec_tpu.parallel.engine import LocalEngine

    op = build_heisenberg(10, 5, None, ())
    eng = LocalEngine(op, mode="ell")
    base = f"engine/{eng._mem_instance}"
    assert obs.ledger_total(base) > 0
    del eng
    gc.collect()
    assert obs.ledger_total(base) == 0


def test_apply_memory_analysis_reconciles_with_ledger(clean_obs, rng):
    """The acceptance reconciliation: the apply executable's compile-time
    argument accounting equals the ledger's bytes for what the apply
    consumes (x + structure tables + diag) within 5%."""
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    op = build_heisenberg(10, 5, None, ())
    eng = LocalEngine(op, mode="ell")
    n = op.basis.number_states
    x = np.asarray(rng.random(n) - 0.5)
    ana = eng.apply_memory_analysis(x)
    assert ana is not None and ana["program"] == "local_ell_apply"
    expected = x.nbytes + eng.ell_nbytes + eng._diag.nbytes
    assert abs(ana["argument_bytes"] - expected) \
        <= 0.05 * ana["argument_bytes"]
    # recorded in the registry + stream + gauge; repeat call is cached
    assert obs.events("memory_analysis")[-1]["program"] == "local_ell_apply"
    assert eng.apply_memory_analysis(x) == ana


def test_solver_registers_and_releases_workspace(clean_obs):
    from distributed_matvec_tpu.parallel.engine import LocalEngine
    from distributed_matvec_tpu.solve import lanczos, lanczos_block

    op = build_heisenberg(10, 5, None, ())
    eng = LocalEngine(op, mode="ell")
    seen = {}
    orig = obs_mem.track

    def spy(path, nbytes, **kw):
        seen[path] = nbytes
        return orig(path, nbytes, **kw)

    try:
        obs_mem.track = spy
        lanczos(eng.matvec, op.basis.number_states, k=1, max_iters=32,
                tol=1e-10, seed=3)
        lanczos_block(eng.matvec, op.basis.number_states, k=1, max_iters=8,
                      seed=3)
    finally:
        obs_mem.track = orig
    ks = list(seen)
    assert any(p.startswith("solver/lanczos:") for p in ks), ks
    assert any(p.startswith("solver/lanczos_block:") for p in ks), ks
    assert all(v > 0 for v in seen.values())
    # completed solves release their workspace entries
    assert obs_mem.ledger_total("solver") == 0


# ---------------------------------------------------------------------------
# OOM forensics


_OOM_MSG = ("RESOURCE_EXHAUSTED: Out of memory allocating 11906150400 "
            "bytes (allocated so far: 4295852032 bytes)")


def test_oom_fault_injection_report_shape(clean_obs, rng):
    """A fault-injected RESOURCE_EXHAUSTED on the apply surfaces as a typed
    OomError with the structured MemoryReport attached and one critical
    memory_report event — without a real OOM."""
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    op = build_heisenberg(10, 5, None, ())
    eng = LocalEngine(op, mode="ell")
    x = rng.random(op.basis.number_states) - 0.5

    def boom(_x):
        raise RuntimeError(_OOM_MSG)

    eng._matvec = boom
    with pytest.raises(obs.OomError) as exc_info:
        eng.matvec(x)
    err = exc_info.value
    assert isinstance(err.__cause__, RuntimeError)
    rep = err.report
    assert rep["context"] == {"engine": "local", "mode": "ell",
                              "phase": "apply",
                              "n_states": op.basis.number_states}
    assert rep["ledger_total_bytes"] == obs.ledger_total() > 0
    assert rep["ledger"]["children"]["engine"]["bytes"] > 0
    assert rep["watermark"] is None            # CPU: advisory mode
    fixes = "\n".join(rep["remediation"])
    assert "fused" in fixes and "batch" in fixes and "shard" in fixes
    assert "capacity.py" in fixes
    assert "remediation" in str(err)           # message names the levers
    ev = obs.events("memory_report")[-1]
    assert ev["level"] == "critical" and ev["rank"] == 0
    assert ev["context"]["engine"] == "local"
    assert ev["remediation"] == rep["remediation"]
    assert "RESOURCE_EXHAUSTED" in ev["error"]
    assert obs.snapshot()["counters"]["oom_events"] == 1


def test_oom_init_phase_remediation(clean_obs, monkeypatch):
    """An OOM during the structure build carries phase=init and suggests
    the two-pass low-memory build."""
    from distributed_matvec_tpu.parallel import engine as E

    op = build_heisenberg(10, 5, None, ())
    monkeypatch.setattr(E.LocalEngine, "_build_ell",
                        lambda self: (_ for _ in ()).throw(
                            RuntimeError(_OOM_MSG)))
    with pytest.raises(obs.OomError) as exc_info:
        E.LocalEngine(op, mode="ell")
    rep = exc_info.value.report
    assert rep["context"]["phase"] == "init"
    assert any("ell_build_budget_gb" in r for r in rep["remediation"])


def test_non_oom_errors_pass_through_unwrapped(clean_obs, rng):
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    op = build_heisenberg(10, 5, None, ())
    eng = LocalEngine(op, mode="ell")
    x = rng.random(op.basis.number_states) - 0.5

    def boom(_x):
        raise ValueError("plain bug, not memory")

    eng._matvec = boom
    with pytest.raises(ValueError, match="plain bug"):
        eng.matvec(x)
    assert obs.events("memory_report") == []


def test_oom_guard_disabled_noop(clean_obs, rng, monkeypatch):
    """DMT_OBS=off: the original error propagates untouched, nothing is
    emitted, and the forensics builder is provably never invoked."""
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    op = build_heisenberg(10, 5, None, ())
    eng = LocalEngine(op, mode="ell")
    x = rng.random(op.basis.number_states) - 0.5
    monkeypatch.setenv("DMT_OBS", "off")
    obs.reset_all()

    def explode(**ctx):
        raise AssertionError("forensics built while obs disabled")

    monkeypatch.setattr(obs_mem, "build_memory_report", explode)

    def boom(_x):
        raise RuntimeError(_OOM_MSG)

    eng._matvec = boom
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        eng.matvec(x)
    assert obs.events() == []


def test_engine_apply_disabled_zero_memory_overhead(clean_obs, rng,
                                                    monkeypatch):
    """The PR-2 guard extended to the memory pillar: with the layer off an
    engine apply samples no watermark, touches no ledger, and returns
    bit-identical results."""
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    op = build_heisenberg(10, 5, None, ())
    eng = LocalEngine(op, mode="ell")
    x = rng.random(op.basis.number_states) - 0.5
    y_on = np.asarray(eng.matvec(x))

    monkeypatch.setenv("DMT_OBS", "off")
    obs.reset_all()

    def explode(*a, **k):
        raise AssertionError("memory layer touched while disabled")

    monkeypatch.setattr(obs_mem, "_device_stats", explode)
    monkeypatch.setattr(obs_mem, "emit_ledger", explode)
    y_off = np.asarray(eng.matvec(x))
    np.testing.assert_array_equal(y_on, y_off)
    assert obs.events() == []
    assert obs_mem.ledger_total() == 0


def test_is_resource_exhausted_matching(clean_obs):
    assert obs_mem.is_resource_exhausted(RuntimeError(_OOM_MSG))
    assert obs_mem.is_resource_exhausted(
        Exception("jaxlib.xla_extension.XlaRuntimeError: "
                  "RESOURCE_EXHAUSTED: ..."))
    assert obs_mem.is_resource_exhausted(MemoryError("Out of memory"))
    assert not obs_mem.is_resource_exhausted(ValueError("shape mismatch"))
    assert not obs_mem.is_resource_exhausted(
        RuntimeError("INVALID_ARGUMENT: bad operand"))


# ---------------------------------------------------------------------------
# capacity planner


def _write_snapshot(tmp_path, **ledger_fields):
    run = tmp_path / "rank_0"
    run.mkdir(parents=True, exist_ok=True)
    ev = {"seq": 0, "ts": 0.0, "proc": 0, "rank": 0, "n_ranks": 1,
          "kind": "memory_ledger", "context": "engine_init/local",
          "total_bytes": 2_000_000, "entries": {},
          "engine": "local", "mode": "ell", "n_states": 100_000,
          "n_padded": 100_352, "T0": 12, "num_terms": 16, "pair": False,
          "table_bytes": 1_600_000}
    ev.update(ledger_fields)
    ana = {"seq": 1, "ts": 0.0, "proc": 0, "rank": 0, "n_ranks": 1,
           "kind": "memory_analysis", "key": "local_ell_apply@x",
           "program": "local_ell_apply", "argument_bytes": 2_000_000,
           "output_bytes": 800_000, "temp_bytes": 50_000,
           "peak_estimate_bytes": 2_850_000}
    with open(run / "events.jsonl", "w") as f:
        f.write(json.dumps(ev) + "\n" + json.dumps(ana) + "\n")
    return str(tmp_path)


def test_capacity_plan_from_snapshot(tmp_path, capsys):
    cap = _load_tool("capacity")
    run = _write_snapshot(tmp_path)
    assert cap.main(["--snapshot", run, "--hbm-gb", "16"]) == 0
    out = capsys.readouterr().out
    assert "calibrated from a measured ell engine" in out
    assert "max rows/device" in out
    for mode in ("ell", "compact", "fused"):
        assert mode in out
    # measured calibration wins over the analytic formula for ell
    snap = cap.load_snapshot(run)
    led = snap["ledger"]
    rep = cap.plan(led["n_states"], led["num_terms"], led["T0"],
                   led["pair"], 16.0, 1, 3, 1,
                   measured={k: led[k] for k in
                             ("mode", "n_states", "n_padded", "T0",
                              "table_bytes")})
    assert rep["modes"]["ell"]["structure_bytes_per_row"] == pytest.approx(
        1_600_000 / 100_352, abs=0.01)    # report rounds to 2 decimals
    assert rep["modes"]["fused"]["structure_bytes_per_row"] == 0
    # per-device max scales with the budget (same calibration both sides)
    rep32 = cap.plan(led["n_states"], led["num_terms"], led["T0"],
                     led["pair"], 32.0, 1, 3, 1,
                     measured={k: led[k] for k in
                               ("mode", "n_states", "n_padded", "T0",
                                "table_bytes")})
    assert rep32["modes"]["ell"]["max_rows_per_device"] == \
        2 * rep["modes"]["ell"]["max_rows_per_device"]


def test_capacity_recommendation_modes_and_shards(tmp_path):
    cap = _load_tool("capacity")
    rep = cap.plan(63_000_000, 36, 24, False, 16.0, 8, 3, 1)
    rec = cap.recommend(rep, None)
    assert rec["recommended_mode"] == "ell"
    assert rec["recommended_devices"] <= 8
    # a basis too big for the mesh names the minimal-shard mode
    rec_big = cap.recommend(rep, 10_000_000_000)
    assert rec_big["recommended_mode"] == "fused"
    assert rec_big["recommended_devices"] > 8


def test_capacity_explicit_params_json(capsys):
    cap = _load_tool("capacity")
    assert cap.main(["--n-states", "1e6", "--num-terms", "20", "--t0", "12",
                     "--pair", "--json"]) == 0
    data = json.loads(capsys.readouterr().out)
    m = data["report"]["modes"]
    assert m["ell"]["structure_bytes_per_row"] == 12 * 20   # pair: 16 B cf
    assert data["recommendation"]["recommended_mode"] == "ell"


def test_capacity_snapshot_without_ledger_fails_loudly(tmp_path):
    cap = _load_tool("capacity")
    run = tmp_path / "rank_0"
    run.mkdir(parents=True)
    (run / "events.jsonl").write_text(
        json.dumps({"kind": "engine_init", "n_states": 5}) + "\n")
    with pytest.raises(ValueError, match="memory_ledger"):
        cap.load_snapshot(str(tmp_path))


# ---------------------------------------------------------------------------
# obs_report: memory sections + memory regression gate


def test_obs_report_summarize_memory_section(clean_obs, tmp_path,
                                             monkeypatch):
    rep = _load_tool("obs_report")
    run = tmp_path / "run"
    monkeypatch.setenv("DMT_OBS_DIR", str(run))
    obs.emit("memory_ledger", context="engine_init/local", engine="local",
             mode="ell", n_states=100, T0=6, table_bytes=9000,
             total_bytes=12000,
             entries={"engine/local:0/structure/idx": {"bytes": 6000},
                      "engine/local:0/structure/coeff": {"bytes": 3000},
                      "engine/local:0/diag": {"bytes": 3000}})
    obs.emit("memory_watermark", tag="apply/local", bytes_in_use=5000,
             peak_bytes=8000, bytes_limit=100000, devices=[])
    obs.emit("memory_watermark", tag="apply/local", bytes_in_use=4000,
             peak_bytes=9000, bytes_limit=100000, devices=[])
    obs.emit("memory_analysis", key="local_ell_apply@x",
             program="local_ell_apply", argument_bytes=9000,
             output_bytes=800, temp_bytes=123, generated_code_bytes=0,
             peak_estimate_bytes=9923)
    obs.emit("memory_report", level="critical",
             context={"engine": "local", "mode": "ell"},
             ledger_total_bytes=12000, error="RESOURCE_EXHAUSTED",
             remediation=["switch to mode='fused'"])
    obs.flush()
    obs.reset()

    s = rep.run_summary(rep.load_events(str(run)))
    mem = s["memory"]
    assert mem["ledger_total_bytes"][0] == 12000
    assert mem["peak_hbm_bytes"][0] == 9000            # max over samples
    top = mem["top_allocations"][0]
    assert top[0]["path"] == "engine/local:0/structure/idx"
    assert [t["bytes"] for t in top] == [6000, 3000, 3000]
    assert mem["ledger_context"][0]["T0"] == 6
    exe = mem["executables"]["local_ell_apply@x"]
    assert exe["temp_bytes"] == 123
    assert len(mem["oom_events"]) == 1
    assert mem["oom_events"][0]["remediation"] == ["switch to mode='fused'"]
    rep.print_summary(s)                 # renderer must not throw
    # report --memory renders the same digest
    assert rep.main(["report", str(run), "--memory"]) == 0


def test_obs_report_memory_digest_reads_the_fullest_device(clean_obs,
                                                           tmp_path,
                                                           monkeypatch,
                                                           capsys):
    """Where a sample names its fullest device the digest and the watch
    frame show that device's in-use beside its peak, not the sum over
    devices beside the maximum."""
    rep = _load_tool("obs_report")
    run = tmp_path / "run"
    monkeypatch.setenv("DMT_OBS_DIR", str(run))
    rows = [{"device": "tpu:0", "bytes_in_use": 3000,
             "peak_bytes_in_use": 5000, "bytes_limit": 16000},
            {"device": "tpu:1", "bytes_in_use": 2500,
             "peak_bytes_in_use": 9000, "bytes_limit": 16000}]
    monkeypatch.setattr(obs_mem, "_device_stats", lambda: rows)
    obs_mem.track("solver/lanczos:0/krylov_basis", 4000,
                  per_device={"tpu:0": 2000, "tpu:1": 2000})
    obs_mem.sample_watermark("lanczos/wait", synced=True)
    obs.flush()
    events = rep.load_events(str(run))
    obs.reset()
    mem = rep.run_summary(events)["memory"]
    assert mem["peak_hbm_bytes"][0] == 9000
    assert mem["fullest"][0]["device"] == "tpu:1"
    assert mem["fullest"][0]["bytes_in_use"] == 2500
    assert mem["fullest"][0]["ledger_bytes"] == 2000
    rep.print_memory_section(mem)
    out = capsys.readouterr().out
    assert "fullest device tpu:1 at lanczos/wait" in out
    assert "work in flight" not in out
    state = rep.watch_state(events)
    assert state["per_rank"][0]["hbm"] == 2500          # not the sum, 5500
    assert state["per_rank"][0]["hbm_peak"] == 9000


def test_obs_report_rank_table_peak_hbm_column(tmp_path):
    rep = _load_tool("obs_report")
    run = tmp_path / "run"
    for r, peak in ((0, 111), (1, 222)):
        d = run / f"rank_{r}"
        d.mkdir(parents=True)
        evs = [{"seq": 0, "ts": 1000.0, "proc": r, "rank": r, "n_ranks": 2,
                "kind": "memory_watermark", "tag": "apply",
                "bytes_in_use": 1, "peak_bytes": peak, "bytes_limit": 10},
               {"seq": 1, "ts": 1001.0, "proc": r, "rank": r, "n_ranks": 2,
                "kind": "memory_watermark", "tag": "apply",
                "bytes_in_use": 1, "peak_bytes": peak - 1,
                "bytes_limit": 10}]
        with open(d / "events.jsonl", "w") as f:
            for ev in evs:
                f.write(json.dumps(ev) + "\n")
    table = rep.rank_table(rep.load_events(str(run)))
    rows = {row["rank"]: row for row in table["rows"]}
    assert rows[0]["peak_hbm"] == 111 and rows[1]["peak_hbm"] == 222
    rep.print_rank_report(table, show_ranks=True)


def _mem_detail(path, table_bytes, temp_bytes=1000, device_ms=10.0):
    detail = {"chain_16": {"config": "heisenberg_chain_16",
                           "device_ms": device_ms,
                           "table_bytes": table_bytes,
                           "executable_temp_bytes": temp_bytes}}
    path.write_text(json.dumps(detail))
    return str(path)


def test_obs_report_diff_memory_gate(tmp_path):
    rep = _load_tool("obs_report")
    base = _mem_detail(tmp_path / "base.json", table_bytes=1_000_000)
    grown = _mem_detail(tmp_path / "grown.json", table_bytes=1_500_000)
    shrunk = _mem_detail(tmp_path / "shrunk.json", table_bytes=700_000)
    # +50% tables beyond the 20% gate → regression, but ONLY when the
    # memory gate is requested
    assert rep.main(["diff", base, grown, "--threshold", "0.2"]) == 0
    assert rep.main(["diff", base, grown, "--threshold", "0.2",
                     "--memory"]) == 1
    # direction-aware: shrinking tables is an improvement
    assert rep.main(["diff", base, shrunk, "--threshold", "0.2",
                     "--memory"]) == 0
    # temp-bytes growth gates too
    hot = _mem_detail(tmp_path / "hot.json", table_bytes=1_000_000,
                      temp_bytes=5000)
    assert rep.main(["diff", base, hot, "--threshold", "0.2",
                     "--memory"]) == 1
    # --memory composes with an explicit perf gate
    slow = _mem_detail(tmp_path / "slow.json", table_bytes=1_000_000,
                       device_ms=20.0)
    assert rep.main(["diff", base, slow, "--threshold", "0.2",
                     "--memory"]) == 1


# ---------------------------------------------------------------------------
# executable-analysis registry


def test_record_executable_analysis_registry_and_gauge(clean_obs):
    import jax
    import jax.numpy as jnp

    ex = jax.jit(lambda a: a @ a).lower(jnp.ones((32, 32))).compile()
    ana = obs_mem.record_executable_analysis("unit@1", ex, program="unit")
    assert ana["argument_bytes"] == 32 * 32 * 8
    assert ana["output_bytes"] == 32 * 32 * 8
    assert ana["peak_estimate_bytes"] >= ana["argument_bytes"]
    assert obs_mem.executable_analyses()["unit@1"]["program"] == "unit"
    assert obs.snapshot()["gauges"][
        "executable_temp_bytes{program=unit}"] == ana["temp_bytes"]
    ev = obs.events("memory_analysis")[-1]
    assert ev["key"] == "unit@1" and ev["program"] == "unit"


def test_record_executable_analysis_disabled_and_soft_fail(clean_obs,
                                                           monkeypatch):
    class _Broken:
        def memory_analysis(self):
            raise NotImplementedError("backend has none")

    assert obs_mem.record_executable_analysis("b@1", _Broken()) is None
    assert obs.events("memory_analysis") == []
    monkeypatch.setenv("DMT_OBS", "off")

    class _Explodes:
        def memory_analysis(self):
            raise AssertionError("touched while disabled")

    assert obs_mem.record_executable_analysis("c@1", _Explodes()) is None
