"""Phase-level performance attribution: apply_phases events, the roofline
cost model and rate-calibration sidecars.

The exactness contract (ISSUE 7 satellite): per-phase bytes/gathers/flops
sum to the event's whole-apply totals EXACTLY, and cross-check against
independent engine quantities (``plan_bytes``, ``_exchange_nbytes``); the
roofline model's attributed phase walls sum to the measured apply wall
exactly by construction; one recorded streamed CPU run reconciles against
the model to a documented tolerance.
"""

import importlib.util
import json
import os
import sys

import jax
import numpy as np
import pytest

from distributed_matvec_tpu import obs
from distributed_matvec_tpu.obs import phases as obs_phases
from distributed_matvec_tpu.obs import roofline as R
from distributed_matvec_tpu.parallel.distributed import DistributedEngine
from distributed_matvec_tpu.parallel.engine import LocalEngine

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


def _ndev() -> int:
    return len(jax.devices())


def _phase_event(engine):
    evs = [e for e in obs.events("apply_phases")
           if e.get("engine") == engine]
    assert evs, f"no apply_phases event from {engine}"
    return evs[-1]


def _assert_totals_exact(ev):
    """The exactness invariant: per-phase counts sum to the totals."""
    for field, total in (("bytes", ev["bytes_total"]),
                         ("gathers", ev["gathers_total"]),
                         ("flops", ev["flops_total"])):
        assert sum(p[field] for p in ev["phases"].values()) == total


# ---------------------------------------------------------------------------
# engine instrumentation


@pytest.mark.parametrize("sites, stair", [(10, False), (16, True)],
                         ids=["plain_table", "staircase"])
def test_local_ell_phases_exact(clean_obs, rng, sites, stair):
    op = build_heisenberg(sites, sites // 2, None, ())
    eng = LocalEngine(op, mode="ell")
    assert (eng._ell_pos_of is not None) == stair
    x = rng.random(op.basis.number_states) - 0.5
    eng.matvec(x)
    # satellite: LocalEngine now emits matvec_apply (engine="local")
    ma = [e for e in obs.events("matvec_apply")
          if e.get("engine") == "local"]
    assert ma and ma[-1]["bytes"] == 0 and ma[-1]["wall_ms"] > 0
    ev = _phase_event("local")
    assert ev["mode"] == "ell" and ev["columns"] == 1
    _assert_totals_exact(ev)
    # structural gather count: one gather per table slot (every level's),
    # and under ``accumulate`` the gather back to basis order, if any
    g_levels = sum(int(i.shape[0] * i.shape[1]) for i, _ in eng._ell_levels)
    assert ev["phases"]["compute"]["gathers"] == g_levels
    assert ev["phases"]["accumulate"]["gathers"] \
        == (eng.n_padded if eng._ell_pos_of is not None else 0)
    assert ev["gathers_total"] == eng._ell_counts["gather_slots"]
    assert ev["phases"]["exchange"]["bytes"] == 0
    assert ev["phases"]["plan_h2d"]["bytes"] == 0


def test_local_batch_columns_scale_bytes(clean_obs, rng):
    """A k-column batch gathers k× the vector bytes but the same slots."""
    op = build_heisenberg(10, 5, None, ())
    eng = LocalEngine(op, mode="ell")
    n = op.basis.number_states
    eng.matvec(rng.random(n) - 0.5)
    ev1 = _phase_event("local")
    eng.matvec(rng.random((n, 3)) - 0.5)
    ev3 = _phase_event("local")
    assert ev3["columns"] == 3
    assert ev3["phases"]["compute"]["gathers"] \
        == ev1["phases"]["compute"]["gathers"]
    assert ev3["phases"]["compute"]["flops"] \
        == 3 * ev1["phases"]["compute"]["flops"]


def test_local_fused_phases(clean_obs, rng):
    op = build_heisenberg(10, 5, None, ())
    eng = LocalEngine(op, mode="fused", batch_size=64)
    eng.matvec(rng.random(op.basis.number_states) - 0.5)
    ev = _phase_event("local")
    assert ev["mode"] == "fused" and ev["chunks"] == eng.num_chunks
    _assert_totals_exact(ev)
    # the orbit scan rides the flops term: strictly more work per entry
    # than the pure multiply-add of ell mode
    g = ev["phases"]["compute"]["gathers"]
    assert g == eng.n_padded * eng.num_terms
    assert ev["phases"]["compute"]["flops"] > 2 * g


def test_distributed_streamed_phase_cross_checks(clean_obs, rng):
    """plan_h2d bytes == the engine's own plan_bytes, exchange bytes ==
    _exchange_nbytes, the chunk timeline covers every streamed chunk, and
    the measured plan_h2d wall is the summed chunk stalls."""
    if _ndev() < 4:
        pytest.skip("needs 4 virtual devices")
    op = build_heisenberg(10, 5, None, ())
    op.basis.build()
    eng = DistributedEngine(op, n_devices=4, mode="streamed",
                            batch_size=32)
    xh = eng.to_hashed(rng.random(op.basis.number_states) - 0.5)
    eng.matvec(xh)
    ev = _phase_event("distributed")
    assert ev["mode"] == "streamed"
    _assert_totals_exact(ev)
    assert ev["phases"]["plan_h2d"]["bytes"] == int(eng.plan_bytes)
    assert ev["phases"]["exchange"]["bytes"] == eng._exchange_nbytes(xh)
    assert ev["chunks"] == eng._plan_nchunks_v
    tl = ev["chunk_timeline"]
    assert [c["chunk"] for c in tl] == list(range(eng._plan_nchunks_v))
    stalls = sum(c.get("stall_ms", 0.0) for c in tl)
    assert ev["phases"]["plan_h2d"]["wall_ms"] == pytest.approx(
        stalls, abs=1e-3)
    # the timeline is drained per apply, not accumulated across applies
    eng.matvec(xh)
    ev2 = _phase_event("distributed")
    assert len(ev2["chunk_timeline"]) == eng._plan_nchunks_v


def test_distributed_ell_phase_exchange_bytes(clean_obs, rng):
    if _ndev() < 4:
        pytest.skip("needs 4 virtual devices")
    op = build_heisenberg(10, 5, None, ())
    op.basis.build()
    eng = DistributedEngine(op, n_devices=4, mode="ell")
    xh = eng.to_hashed(rng.random(op.basis.number_states) - 0.5)
    eng.matvec(xh)
    ev = _phase_event("distributed")
    _assert_totals_exact(ev)
    assert ev["phases"]["exchange"]["bytes"] == eng._exchange_nbytes(xh)
    assert ev["phases"]["exchange"]["bytes"] \
        == [e for e in obs.events("matvec_apply")
            if e.get("engine") == "distributed"][-1]["bytes"]
    assert ev["phases"]["plan_h2d"]["bytes"] == 0


def test_phases_disabled_no_events_bit_identical(clean_obs, rng,
                                                 monkeypatch):
    """DMT_PHASES=off: no apply_phases events, results bit-identical,
    matvec_apply still flows (phases off is narrower than obs off)."""
    op = build_heisenberg(10, 5, None, ())
    eng = LocalEngine(op, mode="ell")
    x = rng.random(op.basis.number_states) - 0.5
    y_on = np.asarray(eng.matvec(x))
    assert obs.events("apply_phases")
    obs.reset_all()
    monkeypatch.setenv("DMT_PHASES", "off")
    assert not obs.phases_enabled()
    y_off = np.asarray(eng.matvec(x))
    np.testing.assert_array_equal(y_on, y_off)
    assert obs.events("apply_phases") == []
    assert obs.events("matvec_apply")


def test_phases_imply_obs(monkeypatch):
    monkeypatch.setenv("DMT_OBS", "off")
    assert not obs.phases_enabled()


# ---------------------------------------------------------------------------
# roofline model


def _synthetic_streamed_event(wall_ms, plan_bytes, stall_ms, nchunks,
                              exch_bytes=1 << 20, seg=1 << 16):
    return {"kind": "apply_phases", "engine": "distributed",
            "mode": "streamed", "apply": 1, "wall_ms": wall_ms,
            "chunks": nchunks, "columns": 1,
            "phases": {
                "plan_h2d": {"bytes": plan_bytes, "gathers": 0, "flops": 0,
                             "wall_ms": stall_ms},
                "compute": {"bytes": 1 << 20, "gathers": 0,
                            "flops": 1 << 22},
                "exchange": {"bytes": exch_bytes, "gathers": 0, "flops": 0},
                "accumulate": {"bytes": seg * 8, "gathers": seg,
                               "flops": seg}},
            "bytes_total": 0, "gathers_total": 0, "flops_total": 0}


def test_attribution_sums_to_wall_exactly():
    cal = R.default_calibration("cpu")
    phases = {"plan_h2d": {"bytes": 10 << 20, "wall_ms": 1.5},
              "compute": {"gathers": 5_000_000, "flops": 10_000_000},
              "exchange": {"bytes": 4 << 20},
              "accumulate": {"gathers": 250_000}}
    att = R.attribute_phases(phases, 300.0, cal)
    total = sum(a["wall_ms"] for a in att.values())
    assert total == pytest.approx(300.0, rel=1e-12)
    assert att["plan_h2d"]["measured"] and att["plan_h2d"]["wall_ms"] == 1.5
    for p, a in att.items():
        if a["wall_ms"] > 0 and a["bound_ms"] > 0:
            assert 0 < a["achieved_fraction"] <= 1.0 + 1e-9


def test_roofline_report_binding_and_pipeline():
    evs = [_synthetic_streamed_event(100.0, 50 << 20, 2.0, 8)
           for _ in range(4)]
    rep = R.roofline_report(evs, R.default_calibration("cpu"))
    grp = rep["groups"]["distributed/streamed"]
    assert grp["binding_phase"] in obs_phases.PHASES
    assert grp["binding_resource"] \
        == obs_phases.PHASE_RESOURCE[grp["binding_phase"]]
    assert R.reconcile_error(rep) < 1e-3
    # 8 chunks with nonzero compute AND exchange → a real overlap window
    assert grp["pipelined_speedup_estimate"] > 1.0


def test_roofline_first_apply_dropped():
    """The compile-bearing first apply must not pollute the steady mean."""
    evs = [_synthetic_streamed_event(1000.0, 1 << 20, 0.1, 2),
           _synthetic_streamed_event(10.0, 1 << 20, 0.1, 2),
           _synthetic_streamed_event(10.0, 1 << 20, 0.1, 2)]
    rep = R.roofline_report(evs, R.default_calibration("cpu"))
    assert rep["groups"]["distributed/streamed"]["wall_ms"] \
        == pytest.approx(10.0)


def test_roofline_reconciles_a_recorded_streamed_apply():
    """Satellite: model vs one RECORDED chain_24_symm streamed apply (a
    round-5 CPU run: steady apply wall in ms, plan bytes, plan-stream
    stall in ms; the three numbers are the test's input and no statement
    about speed).

    Documented tolerance: (a) attributed phase walls reconcile with the
    recorded steady apply wall to <10% (exact by construction here); (b)
    the calibrated CPU-rig bound total never exceeds the measured wall —
    a run cannot beat the roofline (the recorded 75.1 ms apply moves
    11.8 MB of plan + exchange in well under its wall at CPU rates); (c)
    the recorded near-zero plan-stream stall is consistent with the
    model's fully-overlapped H2D reading (measured plan_h2d wall ≪ its
    un-overlapped bound would be at several GB/s)."""
    wall = 75.146
    ev = _synthetic_streamed_event(wall, 11854848, 0.0216, nchunks=1)
    rep = R.roofline_report([ev, ev], R.default_calibration("cpu"))
    grp = rep["groups"]["distributed/streamed"]
    phase_sum = sum(p["wall_ms"] for p in grp["phases"].values())
    assert abs(phase_sum - wall) / wall < 0.10          # (a)
    bound_total = sum(p["bound_ms"] for p in grp["phases"].values())
    assert bound_total <= wall                          # (b)
    h2d = grp["phases"]["plan_h2d"]
    assert h2d["wall_ms"] < 1.0 and h2d["bound_ms"] > h2d["wall_ms"]  # (c)
    assert grp["binding_resource"]


# ---------------------------------------------------------------------------
# calibration sidecar


def test_unknown_backend_has_no_default_calibration():
    """A backend the table does not know is an error — it is never handed
    the CPU's (or anyone's) rates."""
    assert R.default_calibration("cpu")["source"] == "default"
    assert R.default_calibration("tpu")["gather_rows_per_s"] > 0
    with pytest.raises(ValueError, match="no default calibration"):
        R.default_calibration("gpu")


def test_calibration_roundtrip_content_addressed(tmp_path, monkeypatch):
    monkeypatch.setenv("DMT_ARTIFACT_CACHE", "on")
    monkeypatch.setenv("DMT_ARTIFACT_DIR", str(tmp_path))
    p1 = R.calibration_path()
    assert p1 and str(tmp_path) in p1 and "calibration" in p1
    assert R.calibration_path() == p1          # stable (content-addressed)
    assert R.load_calibration() is None
    cal = dict(R.default_calibration("cpu"), gather_rows_per_s=123e6,
               device_kind=jax.devices()[0].device_kind)
    saved = R.save_calibration(cal)
    assert saved == p1 and os.path.exists(saved)
    got = R.load_calibration()
    assert got["gather_rows_per_s"] == 123e6
    assert got["source"] == "measured"
    # resolve: measured sidecar wins over defaults
    assert R.resolve_calibration()["gather_rows_per_s"] == 123e6
    # explicit path wins over everything
    other = tmp_path / "cal.json"
    other.write_text(json.dumps(dict(cal, gather_rows_per_s=9e6)))
    assert R.resolve_calibration(str(other))["gather_rows_per_s"] == 9e6
    # an explicit path that is missing raises — never a silent re-price
    with pytest.raises(FileNotFoundError):
        R.resolve_calibration(str(tmp_path / "nope.json"))


def test_calibration_disabled_artifact_layer(monkeypatch):
    monkeypatch.setenv("DMT_ARTIFACT_CACHE", "off")
    assert R.calibration_path() is None
    assert R.save_calibration(R.default_calibration("cpu")) is None
    # the model still works from defaults
    assert R.resolve_calibration()["source"] == "default"


def test_capacity_consumes_calibration():
    capacity = _load_tool("capacity")
    rates = dict(R.default_calibration("cpu"))
    rep = capacity.plan(1_000_000, 36, 24, False, 16.0, 4, 3, 1,
                        rates=rates)
    m = rep["modes"]["ell"]
    assert m["est_apply_ms"] == pytest.approx(
        (1_000_000 / 4) * 24 / rates["gather_rows_per_s"] * 1e3, rel=1e-6)
    assert "est_apply_ms" in rep["modes"]["streamed"]
    assert rep["rates"]["source"] == "default"
    # without rates the column is absent (pre-calibration behavior intact)
    rep0 = capacity.plan(1_000_000, 36, 24, False, 16.0, 4, 3, 1)
    assert "est_apply_ms" not in rep0["modes"]["ell"]


# ---------------------------------------------------------------------------
# obs_report surfaces


def _load_obs_report():
    return _load_tool("obs_report")


def test_obs_report_phases_summary_and_diff_gate(tmp_path):
    orep = _load_obs_report()
    evs = [_synthetic_streamed_event(50.0, 1 << 20, 0.5, 4)
           for _ in range(3)]
    ph = orep.phases_summary(evs)
    grp = ph["distributed/streamed"]
    assert grp["applies"] == 3 and grp["chunks"] == 4
    assert grp["phases"]["plan_h2d"]["measured_wall_ms"] == 0.5
    orep.print_phases_section(ph)              # renders without error

    # diff --phases: phase bytes growth gates (prefix match), flat passes
    base = {"cfg": {"device_ms": 1.0, "phase_plan_h2d_bytes": 100.0,
                    "phase_compute_gathers": 1000.0}}
    new = {"cfg": {"device_ms": 1.0, "phase_plan_h2d_bytes": 200.0,
                   "phase_compute_gathers": 1000.0}}
    rows, regressions, common = orep.diff_runs(
        base, new, 0.2, gate_metrics=list(orep._PHASE_GATE))
    assert common and regressions
    assert regressions[0][1] == "phase_plan_h2d_bytes"
    rows, regressions, _ = orep.diff_runs(
        base, dict(base), 0.2, gate_metrics=list(orep._PHASE_GATE))
    assert not regressions


def test_obs_report_roofline_subcommand(tmp_path, capsys):
    orep = _load_obs_report()
    run = tmp_path / "events.jsonl"
    with open(run, "w") as f:
        for ev in [_synthetic_streamed_event(80.0, 8 << 20, 1.0, 4)] * 3:
            f.write(json.dumps(ev) + "\n")
    rc = orep.main(["roofline", str(run)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "binding resource" in out and "pipelined-apply estimate" in out
    rc = orep.main(["roofline", str(run), "--json"])
    out = capsys.readouterr().out
    rep = json.loads(out)
    assert "distributed/streamed" in rep["groups"]
    # no apply_phases events → explicit exit 2, not a crash
    empty = tmp_path / "empty.jsonl"
    empty.write_text(json.dumps({"kind": "engine_init"}) + "\n")
    assert orep.main(["roofline", str(empty)]) == 2


def test_obs_report_report_phases_flag(tmp_path, capsys):
    orep = _load_obs_report()
    run = tmp_path / "run"
    (run / "rank_0").mkdir(parents=True)
    with open(run / "rank_0" / "events.jsonl", "w") as f:
        ev = dict(_synthetic_streamed_event(10.0, 1 << 10, 0.1, 2),
                  seq=0, ts=1.0, proc=0, rank=0)
        f.write(json.dumps(ev) + "\n")
    rc = orep.main(["report", str(run), "--phases"])
    out = capsys.readouterr().out
    assert rc == 0 and "phase attribution" in out
