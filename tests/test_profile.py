"""Continuous-profiling plane (obs/hlo.py + obs/profile.py, DESIGN.md
§32): HLO cost attribution at compile, sampled trace windows with the
measured-overhead guard, triggered deep capture, and differential
profiling.

Unit tests fake ``jax.profiler.trace`` where only the plumbing is under
test (capture cadence, overhead ledger, latch); the real profiler — and
the real <2% overhead acceptance — is exercised by ``make
profile-check`` (tools/profile_check.py), and the 2-process artifact
agreement by the DMT_MH_PROF worker leg here.
"""

import contextlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from distributed_matvec_tpu import obs
from distributed_matvec_tpu.obs import hlo as H
from distributed_matvec_tpu.obs import profile as P
from distributed_matvec_tpu.utils.config import update_config

from test_operator import build_heisenberg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def clean_obs():
    obs.reset_all()
    yield
    obs.reset_all()


# a small synthetic optimized-HLO module covering every phase bucket
SYNTH_HLO = """\
HloModule synth, entry_computation_layout={(f64[64]{0})->f64[64]{0}}

ENTRY %main (x: f64[64]) -> f64[64] {
  %x = f64[64]{0} parameter(0)
  %c = f64[] constant(2)
  %fused = f64[128]{0} fusion(%x), kind=kLoop, metadata={op_name="jit(apply)/gather"}
  %perm = f64[128]{0} collective-permute(%fused), metadata={op_name="jit(apply)/ppermute"}
  %dotp = f64[64]{0} dot(%fused, %fused), metadata={op_name="jit(apply)/dot_general"}
  %scat = f64[64]{0} scatter(%dotp, %perm), metadata={op_name="jit(apply)/scatter-add"}
  ROOT %out = f64[64]{0} add(%scat, %dotp)
}
"""


def _totals(byts=1.0e6, flops=3.0e5):
    return {"bytes": byts, "flops": flops, "transcendentals": 0.0}


# ---------------------------------------------------------------------------
# attribution (pure)


def test_classify_and_parse_synthetic_hlo():
    ops = {o["name"]: o for o in H.parse_hlo_ops(SYNTH_HLO)}
    assert ops["x"]["phase"] == "plan_h2d"
    assert ops["c"]["phase"] == "overhead"
    assert ops["perm"]["phase"] == "exchange"
    assert ops["scat"]["phase"] == "accumulate"
    assert ops["dotp"]["phase"] == "compute"
    assert ops["fused"]["phase"] == "compute"   # gather: no refinement
    assert ops["dotp"]["shape_bytes"] == 64 * 8
    # op_name metadata refines a compute-bucketed fusion
    assert H.classify_op("fusion", "jit(f)/ppermute/foo") == "exchange"
    assert H.classify_op("fusion", "jit(f)/segment_sum") == "accumulate"
    assert H.classify_op("weird-new-opcode") == "compute"


def test_phase_buckets_sum_to_program_totals_exactly():
    att = H.attribute_costs(SYNTH_HLO, _totals())
    for axis in ("bytes", "flops"):
        assert sum(r[axis] for r in att["phases"].values()) \
            == pytest.approx(_totals()[axis], abs=0.5)
        assert sum(o[axis] for o in att["ops"]) \
            == pytest.approx(_totals()[axis], abs=0.5)
    # flops only land on flop-capable opcodes (never on parameter/copy)
    per_op = {o["name"]: o for o in att["ops"]}
    assert per_op["x"]["flops"] == 0.0
    assert per_op["perm"]["flops"] == 0.0
    assert per_op["dotp"]["flops"] > 0.0


def test_profile_fingerprint_is_content_address():
    p1 = H.build_profile("k", SYNTH_HLO, _totals(), program="prog")
    p2 = H.build_profile("k2", SYNTH_HLO, _totals(2e6), program="prog")
    assert p1["fingerprint"] == p2["fingerprint"]     # same program text
    p3 = H.build_profile("k", SYNTH_HLO + "\n// x", _totals())
    assert p3["fingerprint"] != p1["fingerprint"]     # any change re-keys


# ---------------------------------------------------------------------------
# compile-time recording + artifact round-trip


def test_record_executable_costs_roundtrip(clean_obs, tmp_path,
                                           monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("DMT_ARTIFACT_DIR", str(tmp_path / "art"))
    monkeypatch.setenv("DMT_ARTIFACT_CACHE", "on")
    ex = jax.jit(lambda a, b: a @ b + b).lower(
        jnp.ones((16, 16)), jnp.ones((16, 16))).compile()
    prof = H.record_executable_costs("k@1", ex, program="unit_prog")
    assert prof is not None and prof["program"] == "unit_prog"
    t = prof["totals"]
    assert t["bytes"] > 0
    assert sum(r["bytes"] for r in prof["phases"].values()) \
        == pytest.approx(t["bytes"], abs=0.5)
    # content-addressed artifact next to the XLA cache, round-tripping
    art = prof["artifact"]
    fp = prof["fingerprint"]
    assert art.endswith(os.path.join("hlo-profile", fp[:2], fp + ".json"))
    assert H.load_profile(art)["totals"] == t
    # registry + event + counter
    assert H.executable_costs()["k@1"] == prof
    ev = obs.events("hlo_cost")[-1]
    assert ev["program"] == "unit_prog" and ev["fingerprint"] == fp
    assert ev["phase_bytes_compute"] >= 0
    assert obs.snapshot()["counters"][
        "hlo_profile_count{program=unit_prog}"] == 1
    # a DIFFERENT program content-addresses to a DIFFERENT artifact
    ex2 = jax.jit(lambda a, b: a @ b - 2.0 * b).lower(
        jnp.ones((16, 16)), jnp.ones((16, 16))).compile()
    prof2 = H.record_executable_costs("k@2", ex2, program="unit_prog2")
    assert prof2["fingerprint"] != fp
    assert prof2["artifact"] != art


def test_record_costs_obs_off_noop(clean_obs, monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("DMT_OBS", "off")
    ex = jax.jit(lambda a: a + 1.0).lower(jnp.ones(8)).compile()
    assert H.record_executable_costs("k@off", ex) is None
    assert H.executable_costs() == {}


# ---------------------------------------------------------------------------
# sampled windows: cadence, ledger, latch, off-mode no-op


@contextlib.contextmanager
def _fake_trace(calls, fail=False, cost_s=0.0):
    """Stand-in for jax.profiler.trace: records targets, optionally
    burns time on entry (to exercise the overhead guard) or refuses."""
    import jax

    class _Trace:
        def __init__(self, target):
            if fail:
                raise RuntimeError("profiler unavailable")
            calls.append(target)

        def __enter__(self):
            if cost_s:
                import time
                time.sleep(cost_s)
            return self

        def __exit__(self, *a):
            return False

    orig = jax.profiler.trace
    jax.profiler.trace = _Trace
    try:
        yield
    finally:
        jax.profiler.trace = orig


def test_sample_window_off_mode_is_noop(clean_obs, monkeypatch,
                                        tmp_path):
    monkeypatch.setenv("DMT_OBS", "off")
    monkeypatch.setenv("DMT_PROFILE", "sampled")   # obs off wins
    assert P.profile_mode() == "off"
    with P.sample_window("local", 64) as captured:
        pass
    assert captured is False
    assert P.overhead_snapshot()["applies"] == 0   # no ledger, provable
    assert P.profile_due(64) is False
    assert P.trigger_capture("anything") is None


def test_sample_window_cadence_and_capture(clean_obs, monkeypatch,
                                           tmp_path):
    monkeypatch.setenv("DMT_OBS_DIR", str(tmp_path / "run"))
    monkeypatch.setenv("DMT_PROFILE", "sampled")
    obs.reset()                       # re-point the sink
    update_config(profile_every=4)
    assert not P.profile_due(0)       # apply 0 pays compile
    assert not P.profile_due(3)
    assert P.profile_due(4) and P.profile_due(8)
    calls = []
    with _fake_trace(calls):
        for idx in range(9):
            with P.sample_window("local", idx) as captured:
                pass
            assert captured == (idx in (4, 8))
    snap = P.overhead_snapshot()
    assert snap["applies"] == 9 and snap["profiled"] == 2
    assert len(calls) == 2 and calls[0].endswith("local-apply4")
    # captured dirs are stamped with their identity
    meta = json.load(open(os.path.join(calls[-1], "PROFILE_META.json")))
    assert meta["capture"] == "sampled" and meta["engine"] == "local"
    assert meta["apply"] == 8
    evs = [e for e in obs.events("profile_captured")
           if e.get("capture") == "sampled"]
    assert [e["apply"] for e in evs] == [4, 8]
    assert snap["last_dir"] == calls[-1]
    # a refused trace start degrades to an unprofiled apply, no event
    with _fake_trace(calls, fail=True):
        with P.sample_window("local", 12) as captured:
            pass
    assert captured is False
    assert P.overhead_snapshot()["profiled"] == 2


def test_overhead_guard_latches_and_says_so(clean_obs, monkeypatch,
                                            tmp_path):
    monkeypatch.setenv("DMT_OBS_DIR", str(tmp_path / "run"))
    monkeypatch.setenv("DMT_PROFILE", "sampled")
    obs.reset()
    update_config(profile_every=2, profile_overhead_pct=1.0)
    calls = []
    with _fake_trace(calls, cost_s=0.004):   # 4 ms burned per capture
        for idx in range(5):
            with P.sample_window("local", idx):
                pass
    assert P.overhead_latched()
    assert P.measured_overhead_pct() > 1.0
    assert not P.profile_due(6)              # latched: sampling stays off
    ev = obs.events("profile_overhead_latch")[-1]
    assert ev["budget_pct"] == 1.0 and ev["overhead_pct"] > 1.0
    assert obs.snapshot()["counters"]["profile_overhead_latch_count"] == 1
    update_config(profile_overhead_pct=2.0)  # restore the default
    P.reset_profile()
    assert not P.overhead_latched()


# ---------------------------------------------------------------------------
# triggered deep capture


def test_triggered_capture_on_slo_burn(clean_obs, monkeypatch, tmp_path):
    from distributed_matvec_tpu.obs.slo import SloSpec

    monkeypatch.setenv("DMT_OBS_DIR", str(tmp_path / "run"))
    monkeypatch.setenv("DMT_PROFILE", "triggered")
    obs.reset()
    spec = SloSpec("steady_apply_ms", kind="matvec_apply",
                   field="wall_ms", target=10.0)
    bad = [{"kind": "matvec_apply", "ts": 1000.0 + i, "wall_ms": 100.0}
           for i in range(10)]
    obs.check_slos([spec], events=bad)       # ok -> firing: triggers
    caps = [e for e in obs.events("profile_captured")
            if e.get("capture") == "triggered"]
    assert len(caps) == 1
    bundle = caps[0]["bundle"]
    assert os.path.exists(bundle)
    assert "profile_slo_burn_steady_apply_ms" in os.path.basename(bundle)
    payload = json.load(open(bundle))
    assert "overhead" in payload["profile"]
    assert payload["slo"] == "steady_apply_ms"
    # steady firing does not re-trigger (one bundle per reason)
    obs.check_slos([spec], events=bad)
    assert len([e for e in obs.events("profile_captured")
                if e.get("capture") == "triggered"]) == 1


def test_trigger_capture_sanitizes_reason_and_snapshots_hlo(
        clean_obs, monkeypatch, tmp_path):
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("DMT_OBS_DIR", str(tmp_path / "run"))
    monkeypatch.setenv("DMT_PROFILE", "sampled")
    monkeypatch.setenv("DMT_ARTIFACT_CACHE", "off")
    obs.reset()
    ex = jax.jit(lambda a: a * 2.0).lower(jnp.ones(8)).compile()
    H.record_executable_costs("k@t", ex, program="trig_prog")
    path = P.trigger_capture("trend gate: cfg/x regressed!",
                             regressions=[{"metric": "device_ms"}])
    assert path and os.path.exists(path)
    assert "profile_trend_gate_cfg_x_regressed" in os.path.basename(path)
    payload = json.load(open(path))
    hot = payload["profile"]["hlo"]
    assert any(p["program"] == "trig_prog" and p["top_ops"] for p in hot)
    assert payload["regressions"] == [{"metric": "device_ms"}]


# ---------------------------------------------------------------------------
# differential profiling


def test_diff_names_regressed_op_and_direction():
    base = H.build_profile("k", SYNTH_HLO, _totals(), program="p")
    worse = json.loads(json.dumps(base))
    victim = max(worse["ops"], key=lambda o: o["bytes"])
    victim["bytes"] *= 10.0
    d = H.diff_profiles(base, worse, threshold=0.25)
    assert d["regressions"]
    assert d["regressions"][0]["name"] == victim["name"]
    assert d["regressions"][0]["axis"] == "bytes"
    assert d["same_program"] is True
    # direction-aware: the same 10x change in the OTHER direction is an
    # improvement, not a regression
    d_rev = H.diff_profiles(worse, base, threshold=0.25)
    assert d_rev["regressions"] == []
    # renamed-but-identical ops still align via opcode#ordinal
    renamed = json.loads(json.dumps(base))
    for o in renamed["ops"]:
        o["name"] = "renamed." + o["name"]
    d_ren = H.diff_profiles(base, renamed, threshold=0.25)
    assert d_ren["regressions"] == [] and d_ren["appeared"] == []


def test_profile_diff_cli_and_obs_report_profile(tmp_path):
    base = H.build_profile("k", SYNTH_HLO, _totals(), program="p")
    bpath = tmp_path / "base.json"
    bpath.write_text(json.dumps(base))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "profile_diff.py"),
         str(bpath), str(bpath)], capture_output=True, text=True)
    assert r.returncode == 0 and "no per-op regression" in r.stdout, \
        r.stdout + r.stderr
    worse = json.loads(json.dumps(base))
    victim = max(worse["ops"], key=lambda o: o["bytes"])
    victim["bytes"] *= 10.0
    wpath = tmp_path / "worse.json"
    wpath.write_text(json.dumps(worse))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "profile_diff.py"),
         str(bpath), str(wpath)], capture_output=True, text=True)
    assert r.returncode == 1 and "REGRESSION" in r.stdout, r.stdout
    assert victim["name"] in r.stdout
    # obs_report renders a single artifact (exit 0) and a run with no
    # profile exits 2
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
         "profile", str(bpath)], capture_output=True, text=True)
    assert r.returncode == 0 and "hottest ops" in r.stdout, \
        r.stdout + r.stderr
    empty = tmp_path / "empty_run"
    (empty / "rank_0").mkdir(parents=True)
    (empty / "rank_0" / "events.jsonl").write_text("")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
         "profile", str(empty)], capture_output=True, text=True)
    assert r.returncode == 2


# ---------------------------------------------------------------------------
# live reconciliation: hlo third column vs measured apply walls


def test_roofline_hlo_column_reconciles(clean_obs, monkeypatch, tmp_path):
    import jax

    from distributed_matvec_tpu.obs import roofline as R
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    monkeypatch.setenv("DMT_OBS_DIR", str(tmp_path / "run"))
    monkeypatch.setenv("DMT_ARTIFACT_CACHE", "off")
    obs.reset()
    op = build_heisenberg(10, 5, None, ())
    eng = LocalEngine(op, mode="ell")
    n = op.basis.number_states
    x = np.random.default_rng(3).standard_normal(n)
    eng.apply_memory_analysis(x)      # records the apply's cost profile
    for _ in range(4):
        y = eng.matvec(x)
    jax.block_until_ready(y)
    obs.flush()
    rep = R.roofline_report(obs.events())
    grp = rep["groups"]["local/ell"]
    assert grp["hlo"]["program"] == "local_ell_apply"
    hlo_sum = sum(float(a.get("hlo_ms") or 0.0)
                  for a in grp["phases"].values())
    wall = float(grp["wall_ms"])
    # the documented tolerance: Σ hlo_ms is normalized to the measured
    # wall; only 4-decimal rounding across the buckets can separate them
    assert hlo_sum == pytest.approx(wall, rel=0.02)
    assert any((a.get("hlo_ms") or 0.0) > 0.0
               for a in grp["phases"].values())


# ---------------------------------------------------------------------------
# 2-process agreement


def test_multihost_profile_ranks_agree(tmp_path):
    """A REAL 2-process run (DMT_MH_PROF leg): both ranks record the
    same rank-local apply program's cost profile and must agree on its
    fingerprint, totals, and content-addressed artifact name."""
    import socket

    worker = os.path.join(os.path.dirname(__file__),
                          "multihost_worker.py")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    run = tmp_path / "prof_run"
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["DMT_MH_PROF"] = "1"
    env["DMT_OBS_DIR"] = str(run)
    env["DMT_ARTIFACT_DIR"] = str(tmp_path / "art")
    env["DMT_ARTIFACT_CACHE"] = "on"   # conftest turns it off globally
    procs = [subprocess.Popen(
        [sys.executable, worker, str(pid), "2", str(port)],
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
    lines = []
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid}:\n{out[-2000:]}"
        assert f"[p{pid}] MULTIHOST_OK" in out, out[-2000:]
        l, = [ln for ln in out.splitlines()
              if ln.startswith(f"[p{pid}] PROF_OK ")]
        lines.append(l.split()[2:])          # [fp, flops, bytes, artifact]
    assert lines[0] == lines[1], lines       # ranks agree, per-field
    # both ranks resolved the SAME content-addressed artifact, and the
    # shared root holds exactly that one profile for the apply program
    fp, _, _, artname = lines[0]
    assert artname == fp + ".json"
    art = tmp_path / "art" / "hlo-profile" / fp[:2] / artname
    assert art.exists()
    assert H.load_profile(str(art))["program"] == "distributed_ell_apply"
