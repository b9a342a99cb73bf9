"""End-to-end solve tracing (obs/trace.py) + the trace/watch readers.

Covers: trace-id resolution (env pin, run-dir file agreement, random
fallback), span stack nesting + envelope stamping, the provable-no-op
contracts (DMT_OBS=off, DMT_TRACE=off), engine apply spans, the
stall-report span attachment, the Perfetto export's B/E pairing +
nesting, a golden `watch --once` frame, and the REAL 2-process spawned
leg asserting cross-rank trace agreement and
a Perfetto round-trip.
"""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

from distributed_matvec_tpu import obs
from distributed_matvec_tpu.obs import trace as obs_trace

from test_operator import build_heisenberg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def clean_trace():
    obs.reset_all()
    yield
    obs.reset_all()


# ---------------------------------------------------------------------------
# identity


def test_trace_id_lazy_and_stable(clean_trace):
    a = obs.trace_id()
    assert a and len(a) == 16
    assert obs.trace_id() == a              # cached for the process
    assert obs.job_id() == a                # defaults to the trace id
    obs.reset_all()
    assert obs.trace_id() != a              # reset re-keys


def test_trace_id_env_pin(clean_trace, monkeypatch):
    monkeypatch.setenv("DMT_TRACE_ID", "cafef00d")
    assert obs.trace_id() == "cafef00d"


def test_job_id_env_and_config(clean_trace, monkeypatch):
    monkeypatch.setenv("DMT_JOB_ID", "job-42")
    assert obs.job_id() == "job-42"
    ev = obs.emit("x")
    assert ev["job_id"] == "job-42"
    assert ev["trace_id"] != "job-42"       # trace identity stays its own


def test_trace_id_file_agreement(tmp_path):
    """First rank's O_EXCL create wins; later ranks read the winner."""
    d = str(tmp_path / "run")
    a = obs_trace._agree_trace_id(d, "aaaa")
    b = obs_trace._agree_trace_id(d, "bbbb")
    assert a == "aaaa" and b == "aaaa"
    with open(os.path.join(d, "trace_id")) as f:
        assert f.read().strip() == "aaaa"


def test_trace_id_agreement_via_run_dir(clean_trace, tmp_path, monkeypatch):
    monkeypatch.setenv("DMT_OBS_DIR", str(tmp_path / "run"))
    tid = obs.trace_id()
    with open(tmp_path / "run" / "trace_id") as f:
        assert f.read().strip() == tid


# ---------------------------------------------------------------------------
# spans + stamping


def test_span_nesting_and_envelope(clean_trace):
    with obs.span("solve", kind="solve", solver="t") as sp_solve:
        with obs.span("iteration", kind="iteration", iter=0):
            assert obs.span_path() == "solve>iteration"
            with obs.span("apply", kind="apply", apply=0) as sp_apply:
                deep = obs.deepest_span()
                assert deep["name"] == "apply" and deep["apply"] == 0
                ev = obs.emit("matvec_apply", wall_ms=1.0)
                assert ev["span_id"] == sp_apply.sid
                assert ev["trace_id"] == obs.trace_id()
    assert obs.open_spans() == []
    spans = obs.events("span")
    assert [e["name"] for e in spans] == ["apply", "iteration", "solve"]
    by_id = {e["span_id"]: e for e in spans}
    # span events stamp their OWN id (emitted before the pop) and carry
    # the parent link; the chain roots at the solve span
    apply_ev = next(e for e in spans if e["name"] == "apply")
    it_ev = by_id[apply_ev["parent_span_id"]]
    assert it_ev["name"] == "iteration"
    assert by_id[it_ev["parent_span_id"]]["name"] == "solve"
    assert by_id[it_ev["parent_span_id"]]["parent_span_id"] is None
    assert spans[-1]["span_id"] == sp_solve.sid
    for e in spans:
        assert e["dur_ms"] >= 0 and e["t0"] <= e["ts"]


def test_span_payload_cannot_spoof_envelope(clean_trace):
    with obs.span("s", kind="solve") as sp:
        ev = obs.emit("x", span_id="forged", trace_id="forged")
    assert ev["span_id"] == sp.sid
    assert ev["trace_id"] == obs.trace_id()


def test_obs_off_is_noop(clean_trace, monkeypatch):
    monkeypatch.setenv("DMT_OBS", "off")
    from contextlib import nullcontext
    assert isinstance(obs.span("x"), nullcontext)
    assert obs.trace_id() is None and obs.job_id() is None
    with obs.span("x"):
        assert obs.emit("y") is None
    monkeypatch.delenv("DMT_OBS")
    assert obs.events("span") == []         # nothing leaked through


def test_trace_off_keeps_events_unstamped(clean_trace, monkeypatch):
    monkeypatch.setenv("DMT_TRACE", "off")
    with obs.span("x", kind="solve"):
        ev = obs.emit("y")
    assert ev is not None
    assert "trace_id" not in ev and "span_id" not in ev
    assert obs.events("span") == []


def test_exception_closes_span(clean_trace):
    with pytest.raises(RuntimeError):
        with obs.span("solve", kind="solve"):
            raise RuntimeError("boom")
    assert obs.open_spans() == []
    assert [e["name"] for e in obs.events("span")] == ["solve"]


# ---------------------------------------------------------------------------
# engine + solver integration


def test_local_engine_apply_span(clean_trace, rng):
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    op = build_heisenberg(10)
    eng = LocalEngine(op, mode="ell")
    x = rng.standard_normal(op.basis.number_states)
    eng.matvec(x)
    eng.matvec(x)
    spans = [e for e in obs.events("span") if e["cat"] == "apply"]
    assert [e["apply"] for e in spans] == [0, 1]
    assert all(e["engine"] == "local" for e in spans)
    applies = obs.events("matvec_apply")
    # the matvec_apply event is emitted INSIDE its apply span
    assert [e["span_id"] for e in applies] == [e["span_id"] for e in spans]
    phases = obs.events("apply_phases")
    assert [e["span_id"] for e in phases] == [e["span_id"] for e in spans]


def test_solver_spans_root_and_nest(clean_trace, rng):
    from distributed_matvec_tpu.parallel.engine import LocalEngine
    from distributed_matvec_tpu.solve import lanczos

    op = build_heisenberg(10)
    eng = LocalEngine(op, mode="ell")
    built = len(obs.events("span"))     # the engine build's own spans
    lanczos(eng.matvec, op.basis.number_states, k=1, tol=1e-8,
            max_iters=48)
    spans = obs.events("span")[built:]
    solves = [e for e in spans if e["cat"] == "solve"]
    iters = [e for e in spans if e["cat"] == "iteration"]
    assert len(solves) == 1 and solves[0]["name"] == "lanczos"
    assert iters and all(
        e["parent_span_id"] == solves[0]["span_id"] for e in iters)
    # acyclic + rooted at the solve span
    by_id = {e["span_id"]: e for e in spans}
    for e in spans:
        seen = set()
        cur = e
        while cur.get("parent_span_id"):
            assert cur["span_id"] not in seen
            seen.add(cur["span_id"])
            cur = by_id[cur["parent_span_id"]]
        assert cur["span_id"] == solves[0]["span_id"]
    # the lanczos_trace convergence events attribute to iteration or solve
    for ev in obs.events("lanczos_trace"):
        assert ev.get("trace_id") == obs.trace_id()


def test_stall_report_carries_deepest_span(clean_trace, tmp_path):
    from distributed_matvec_tpu.parallel.heartbeat import HeartbeatWatchdog

    d = str(tmp_path / "run")
    hb = os.path.join(d, "heartbeat")
    os.makedirs(hb)
    stale = os.path.join(hb, "rank_1.hb")
    with open(stale, "w") as f:
        f.write("0\n")
    os.utime(stale, (1.0, 1.0))
    reports = []
    with obs.span("solve", kind="solve"), \
            obs.span("apply", kind="apply", apply=7), \
            obs.span("chunk", kind="chunk", chunk=3):
        wd = HeartbeatWatchdog(d, interval_s=0.05, timeout_s=0.4, rank=0,
                               n_ranks=2, on_stall=reports.append)
        wd.start()
        wd._thread.join(timeout=10)
        wd.stop()
    assert len(reports) == 1
    rep = reports[0]
    # the watchdog names what THIS rank was doing: the deepest open span
    # (phase/chunk granule) plus the full ancestry
    assert rep["span"]["kind"] == "chunk" and rep["span"]["chunk"] == 3
    assert rep["span_path"] == "solve>apply>chunk"
    ev = obs.events("stall_report")[0]
    assert ev["span"]["chunk"] == 3
    assert ev["span_id"] == rep["span"]["span_id"]


# ---------------------------------------------------------------------------
# Perfetto export + watch (reader side, synthetic streams)


def _synthetic_run(tmp_path):
    """A deterministic 2-rank recorded run exercising every watch/trace
    section: spans (solve > iteration > apply > chunk), apply_phases,
    lanczos_trace, watermarks, drift, a straggling rank 1."""
    t0 = 1_700_000_000.0
    evs = {0: [], 1: []}
    for r in (0, 1):
        seq = 0

        def E(kind, ts, **f):
            nonlocal seq
            ev = {"seq": seq, "ts": round(ts, 6), "proc": r, "rank": r,
                  "n_ranks": 2, "kind": kind, "trace_id": "feedc0de",
                  "job_id": "job-7", **f}
            seq += 1
            evs[r].append(ev)
            return ev

        solve_id = "1-solve"
        for i in range(3):
            # rank 1's lag GROWS per apply: genuine compute straggle that
            # survives the constant-offset skew correction
            lag = 0.0 if r == 0 else 0.010 * i
            it_id = f"{2 + 2 * i}-iter"
            ap_id = f"{3 + 2 * i}-appl"
            ts_a = t0 + 1.0 * i + lag
            E("matvec_apply", ts_a + 0.050, engine="distributed",
              apply=i, wall_ms=50.0, bytes=1 << 20, span_id=ap_id)
            E("apply_phases", ts_a + 0.050, engine="distributed",
              mode="streamed", apply=i, wall_ms=50.0, span_id=ap_id,
              chunks=2, columns=1,
              phases={"plan_h2d": {"bytes": 1 << 20, "gathers": 0,
                                   "flops": 0, "wall_ms": 10.0},
                      "compute": {"bytes": 3 << 20, "gathers": 100,
                                  "flops": 100},
                      "exchange": {"bytes": 1 << 20, "gathers": 0,
                                   "flops": 0},
                      "accumulate": {"bytes": 1 << 18, "gathers": 10,
                                     "flops": 10}},
              bytes_total=0, gathers_total=0, flops_total=0)
            E("span", ts_a + 0.020, name="chunk", cat="chunk", chunk=0,
              span_id=f"c{i}0", parent_span_id=ap_id, t0=ts_a,
              dur_ms=20.0)
            E("span", ts_a + 0.045, name="chunk", cat="chunk", chunk=1,
              span_id=f"c{i}1", parent_span_id=ap_id, t0=ts_a + 0.022,
              dur_ms=23.0)
            E("span", ts_a + 0.050, name="apply", cat="apply",
              engine="distributed", mode="streamed", apply=i,
              span_id=ap_id, parent_span_id=it_id, t0=ts_a, dur_ms=50.0)
            E("lanczos_trace", ts_a + 0.060, solver="lanczos_block",
              iter=2 * (i + 1), basis_size=2 * (i + 1),
              ritz=[-21.0 - i], residual=[10.0 ** -(i + 2)],
              span_id=it_id)
            E("span", ts_a + 0.070, name="iteration", cat="iteration",
              solver="lanczos_block", iter=2 * i, span_id=it_id,
              parent_span_id=solve_id, t0=ts_a - 0.005, dur_ms=75.0)
        lag = 0.0 if r == 0 else 0.020
        E("memory_watermark", t0 + 3.0 + lag, bytes_in_use=1 << 30,
          peak_bytes=(3 << 29) + (r << 20))
        E("compress_drift", t0 + 3.0 + lag, rel_err=2.5e-7, tier="bf16",
          engine="distributed", apply=2, chunk=0)
        E("solver_end", t0 + 3.2 + lag, solver="lanczos_block", iters=6,
          converged=True, eigenvalues=[-23.0], span_id=solve_id)
        E("span", t0 + 3.2 + lag, name="lanczos_block", cat="solve", k=1,
          span_id=solve_id, parent_span_id=None, t0=t0 + lag - 0.5,
          dur_ms=3700.0)
        d = tmp_path / f"rank_{r}"
        d.mkdir(parents=True, exist_ok=True)
        with open(d / "events.jsonl", "w") as f:
            for ev in evs[r]:
                f.write(json.dumps(ev) + "\n")
    return str(tmp_path)


def test_perfetto_export_nests_and_balances(tmp_path):
    rep = _load_tool("obs_report")
    run = _synthetic_run(tmp_path / "run")
    events = rep.load_events(run)
    trace = rep.perfetto_trace(events)
    # round-trips through json, loadable by Perfetto
    trace = json.loads(json.dumps(trace))
    te = trace["traceEvents"]
    rep.validate_trace_events(te)
    assert trace["otherData"]["trace_id"] == "feedc0de"
    assert trace["otherData"]["ranks"] == [0, 1]
    for pid in (0, 1):
        # track 0: B/E stack order solve > iteration > apply > chunk
        stack, seen = [], []
        for ev in te:
            if ev.get("pid") != pid or ev.get("tid") != 0:
                continue
            if ev.get("ph") == "B":
                stack.append(ev["cat"])
                seen.append(list(stack))
            elif ev.get("ph") == "E":
                stack.pop()
        assert ["solve"] in seen
        assert ["solve", "iteration", "apply", "chunk"] in seen
        # track 1: phases nested inside the per-apply wrapper slice
        stack, phase_depths = [], set()
        for ev in te:
            if ev.get("pid") != pid or ev.get("tid") != 1:
                continue
            if ev.get("ph") == "B":
                stack.append(ev["cat"])
                if ev["cat"] == "phase":
                    phase_depths.add(tuple(stack[:-1]))
            elif ev.get("ph") == "E":
                stack.pop()
        assert phase_depths == {("apply",)}
        # counter tracks landed
        names = {ev["name"] for ev in te
                 if ev.get("ph") == "C" and ev.get("pid") == pid}
        assert {"hbm_bytes_in_use", "ritz0", "residual_max",
                "compress_rel_err"} <= names


def test_watch_golden_frame(tmp_path):
    rep = _load_tool("obs_report")
    run = _synthetic_run(tmp_path / "run")
    frame = rep.watch_frame(rep.load_events(run))
    expected = """\
obs watch | trace feedc0de | job job-7 | 2 rank(s) | 50 events
--------------------------------------------------------------
applies   rank0: 3 (0.05/s, last 50.0 ms)   rank1: 3 (0.05/s, last 50.0 ms)
phases    distributed/streamed: plan_h2d 20% | compute 56% | exchange 19% | accumulate 5%  (50.0 ms/apply)
solver    lanczos_block: iter 6, basis 6, ritz0 -23.00000000, max res 1.00e-04  [converged]
skew      rank1 waits 6.67 ms/apply at the barrier over 3 aligned applies (worst apply #0 rank 0 +7.5 ms)
health    warn 0, critical 0 | faults 0, io_retries 0, stalls 0 | drift 2.50e-07
memory    rank0: hbm 1.0 GB (peak 1.5 GB, host ledger -) | rank1: hbm 1.0 GB (peak 1.5 GB, host ledger -)"""
    assert frame == expected


def test_watch_once_cli(tmp_path, capsys):
    rep = _load_tool("obs_report")
    run = _synthetic_run(tmp_path / "run")
    assert rep.main(["watch", run, "--once"]) == 0
    out = capsys.readouterr().out
    assert "obs watch | trace feedc0de" in out
    assert "solver    lanczos_block" in out


def test_trace_cli_writes_export(tmp_path, capsys):
    rep = _load_tool("obs_report")
    run = _synthetic_run(tmp_path / "run")
    out_json = str(tmp_path / "trace.json")
    assert rep.main(["trace", run, "-o", out_json]) == 0
    with open(out_json) as f:
        trace = json.load(f)
    rep.validate_trace_events(trace["traceEvents"])


def test_trace_cli_pre_trace_stream(tmp_path, capsys):
    """Backward compat: a pre-trace event stream (no span events, no
    trace_id) exports an empty-but-valid trace and exits 2."""
    rep = _load_tool("obs_report")
    d = tmp_path / "run" / "rank_0"
    d.mkdir(parents=True)
    with open(d / "events.jsonl", "w") as f:
        f.write(json.dumps({"seq": 0, "ts": 1.0, "proc": 0, "rank": 0,
                            "kind": "engine_init"}) + "\n")
    assert rep.main(["trace", str(tmp_path / "run")]) == 2


def test_deepest_span_lock_timeout(clean_trace):
    """The watchdog-facing readers must not block forever on a held
    trace lock (a wedged main thread must still be abortable)."""
    with obs.span("solve", kind="solve"):
        assert obs.deepest_span(timeout=1.0)["name"] == "solve"
        obs_trace._lock.acquire()
        try:
            assert obs.deepest_span(timeout=0.05) is None
            assert obs.span_path(timeout=0.05) == ""
        finally:
            obs_trace._lock.release()


def test_watch_fold_carries_totals(tmp_path):
    """A live watch that trims its window still reports exact lifetime
    totals via the carried base aggregates."""
    rep = _load_tool("obs_report")
    old = [{"seq": i, "ts": 1.0 + i, "rank": 0, "n_ranks": 1,
            "kind": "matvec_apply", "apply": i, "wall_ms": 1.0,
            "bytes": 100} for i in range(5)]
    old.append({"seq": 5, "ts": 6.0, "rank": 0, "n_ranks": 1,
                "kind": "health", "check": "x", "level": "warn"})
    new = [{"seq": 6, "ts": 7.0, "rank": 0, "n_ranks": 1,
            "kind": "matvec_apply", "apply": 5, "wall_ms": 2.0,
            "bytes": 100}]
    base = rep.watch_fold(rep.empty_watch_base(), old)
    st = rep.watch_state(new, base=base)
    assert st["per_rank"][0]["applies"] == 6        # 5 folded + 1 live
    assert st["per_rank"][0]["bytes"] == 600
    assert st["health"]["warn"] == 1                # folded
    assert st["n_events"] == 7
    # without the base only the retained tail counts
    assert rep.watch_state(new)["per_rank"][0]["applies"] == 1


def test_watch_seed_consumes_exact_offsets(tmp_path):
    """The live-mode seed records the byte offset it actually read, so an
    append landing between seed and first poll is neither dropped nor
    double-counted — and a torn final line completes on the next poll."""
    rep = _load_tool("obs_report")
    f = str(tmp_path / "events.jsonl")
    full = json.dumps({"seq": 0, "ts": 1.0, "rank": 0, "kind": "a"})
    torn = json.dumps({"seq": 1, "ts": 2.0, "rank": 0, "kind": "b"})
    with open(f, "w") as fh:
        fh.write(full + "\n" + torn[:10])           # torn mid-write
    events, state, partial = rep._watch_seed([f])
    assert [e["kind"] for e in events] == ["a"]
    assert partial[f] == torn[:10]
    with open(f, "a") as fh:                        # writer finishes + one more
        fh.write(torn[10:] + "\n"
                 + json.dumps({"seq": 2, "ts": 3.0, "rank": 0,
                               "kind": "c"}) + "\n")
    got = rep._follow_poll([f], state, partial)
    assert [e["kind"] for e in got] == ["b", "c"]


# ---------------------------------------------------------------------------
# the REAL 2-process spawned leg


def test_multihost_trace_two_ranks(tmp_path):
    """2-process run (multihost worker harness, trace leg): trace ids
    agree across ranks, parent links are acyclic and rooted at the solve
    span on each rank, and the Perfetto export round-trips with balanced,
    correctly nested B/E pairs on both rank tracks."""
    import socket
    import subprocess

    rep = _load_tool("obs_report")
    worker = os.path.join(os.path.dirname(__file__), "multihost_worker.py")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    run = tmp_path / "trace_run"
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["DMT_MH_TRACE"] = "1"
    env["DMT_OBS_DIR"] = str(run)
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
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid}:\n{out[-2000:]}"
        assert f"[p{pid}] MULTIHOST_OK" in out, out[-2000:]

    events = rep.load_events(str(run))
    ranks = sorted({e["rank"] for e in events})
    assert ranks == [0, 1]
    # ONE trace id across both ranks (file-agreed through the run dir),
    # stamped on every event
    tids = {e.get("trace_id") for e in events}
    assert len(tids) == 1 and None not in tids
    assert all(e.get("job_id") == next(iter(tids)) for e in events)

    for r in ranks:
        spans = [e for e in events
                 if e["rank"] == r and e["kind"] == "span"]
        by_id = {e["span_id"]: e for e in spans}
        solves = [e for e in spans if e["cat"] == "solve"]
        assert len(solves) == 1
        kinds = {e["cat"] for e in spans}
        assert {"solve", "iteration", "apply", "chunk"} <= kinds
        # acyclic, rooted at the solve span; the engine build's spans
        # closed before the solve opened and root at themselves
        for e in spans:
            seen = set()
            cur = e
            while cur.get("parent_span_id"):
                assert cur["span_id"] not in seen
                seen.add(cur["span_id"])
                cur = by_id[cur["parent_span_id"]]
            assert cur["span_id"] == solves[0]["span_id"] or (
                cur["cat"] in ("build", "phase")
                and cur["ts"] <= solves[0]["t0"])
        # every event of a traced run carries trace_id; in-span events
        # carry span_id pointing at a recorded span
        for e in events:
            if e["rank"] == r and e["kind"] in ("matvec_apply",
                                                "apply_phases"):
                assert e["span_id"] in by_id

    trace = json.loads(json.dumps(rep.perfetto_trace(events)))
    te = trace["traceEvents"]
    rep.validate_trace_events(te)
    for pid in ranks:
        seen = []
        stack = []
        for ev in te:
            if ev.get("pid") != pid or ev.get("tid") != 0:
                continue
            if ev.get("ph") == "B":
                stack.append(ev["cat"])
                seen.append(tuple(stack))
            elif ev.get("ph") == "E":
                stack.pop()
        assert ("solve", "iteration", "apply", "chunk") in seen, \
            f"rank {pid} track never nested solve>iteration>apply>chunk"
