#!/usr/bin/env python
"""slo-check — CI gate for the production telemetry plane (`make slo-check`).

Asserts, on the CPU rig (isolated scratch run dirs, artifact cache off):

1. **Export parity on a clean run** — a chain-12 block-Lanczos solve
   with the obs layer on; the registry snapshot, the OpenMetrics text
   scraped over a REAL ephemeral-port HTTP endpoint, the textfile
   written next to ``events.jsonl``, and the ``metrics_snapshot``
   recovered from the rank's events.jsonl must all agree EXACTLY
   (the repr-float round-trip contract of ``obs/export.py``).  A
   ``check_slos()`` pass over the finished ring emits ZERO alerts and
   ``obs_report slo`` exits 0.
2. **DMT_OBS=off is a provable no-op** — subprocess: the exporter
   refuses to bind even with an explicit port request, ``flight_dump``
   writes nothing, the event ring stays empty, and the would-be run
   directory is never created.
3. **An injected latency fault burns the latency SLO** — the same
   6-job spool drained twice through ``SolveService``: clean (the
   pinned ``serve_p99_latency_ms`` target passes, zero alerts in the
   stream), then with ``DMT_FAULT=solver_block:delay=800:skip=2``
   stretching every later solver block; the SAME pinned target now
   exits 1 from ``obs_report slo`` with ``serve_p99_latency_ms``
   firing, and the worker's in-process ``check_slos`` left
   ``slo_alert`` events in the stream.
4. **A forced exit-76 leaves one valid post-mortem bundle** — a
   subprocess wedged inside a solve>iteration>apply>chunk span stack
   against a fabricated stale peer heartbeat: the watchdog exits 76,
   exactly one content-addressed ``stall`` bundle lands in
   ``rank_0/postmortem/`` naming the stuck chunk span, and
   ``obs_report postmortem`` verifies it (exit 0).

Deterministic: the SLOs that grade a wall clock against the run's own
first quartile are pinned out of reach in the clean legs, so only what
the gate injects can burn (the injected delay dwarfs scheduler noise).
~140 s on the CPU rig.
"""

import json
import os
import subprocess
import sys
import time

_WORKER = len(sys.argv) > 1 and sys.argv[1].startswith("worker-")

# platform pins BEFORE any jax import (same discipline as tests/conftest)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_X64"] = "true"
if not _WORKER:
    # the gate asserts DEFAULT enablement against its own scratch dirs —
    # inherited telemetry/fault state must not leak in (workers instead
    # receive exactly the env the gate composes for them)
    for var in ("DMT_OBS", "DMT_OBS_DIR", "DMT_OBS_PORT", "DMT_FAULT",
                "DMT_TRACE_ID", "DMT_JOB_ID", "DMT_FLIGHT_RING"):
        os.environ.pop(var, None)
os.environ["DMT_ARTIFACT_CACHE"] = "off"

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tools"))

_CHAIN = {"number_spins": 10, "hamming_weight": 5}
_N_JOBS = 6
#: Self-baselined SLOs fire on scheduler noise in a run of a few seconds
#: (one slow iteration against the median of the first quartile): the
#: clean legs count alerts of the other SLOs only, and pin these two out
#: of reach where `obs_report slo` grades a clean stream.
_SELF_BASELINED = ("steady_apply_ms", "solver_iteration_ms",
                   "serve_p99_latency_ms", "serve_solves_per_min")
_WALLS_OFF = ["--target", "steady_apply_ms=1e12",
              "--target", "solver_iteration_ms=1e12"]


def _alerts(events):
    return [e for e in events if e.get("kind") == "slo_alert"
            and e.get("slo") not in _SELF_BASELINED]


# ---------------------------------------------------------------------------
# workers (run in subprocesses with the env the gate composes)


def worker_obs_off() -> int:
    """With DMT_OBS=off every telemetry surface is inert: no socket, no
    ring, no bundle, no run directory."""
    assert os.environ.get("DMT_OBS") == "off"
    from distributed_matvec_tpu import obs
    from distributed_matvec_tpu.obs.flight import flight_dump, postmortem_dir

    assert not obs.obs_enabled()
    # an explicit port request must still refuse to bind
    assert obs.start_exporter(port=0) is None
    assert obs.write_textfile() is None
    assert flight_dump("gate_probe", exit_code=1) is None
    assert postmortem_dir() is None
    obs.emit("probe", x=1)
    assert obs.events() == []
    assert obs.check_slos() == []
    print("OBS_OFF_OK")
    return 0


def worker_serve() -> int:
    """Submit a spool of identical chain-10 jobs and drain it; the gate
    runs this twice — clean, then under DMT_FAULT=solver_block:delay.
    Ends with the closing SLO pass + export artifacts every service
    process writes, and prints the max terminal latency so the gate can
    pin one target across both runs."""
    serve_dir = sys.argv[2]
    from distributed_matvec_tpu import obs
    from distributed_matvec_tpu.serve import JobQueue, Scheduler, SolveService
    from distributed_matvec_tpu.serve.queue import submit_to_spool
    from distributed_matvec_tpu.serve.spec import JobSpec

    for i in range(_N_JOBS):
        submit_to_spool(serve_dir, JobSpec(
            job_id=f"job{i}", basis=dict(_CHAIN), k=1, tol=1e-8,
            max_iters=200))
    sched = Scheduler(queue=JobQueue(serve_dir), rates=None, block_width=1)
    rc = SolveService(serve_dir, scheduler=sched).run(drain=True)
    assert rc == 0, f"drain exited {rc}"
    obs.check_slos()
    obs.emit("metrics_snapshot", metrics=obs.snapshot())
    obs.write_textfile()
    obs.flush()
    done = [e for e in obs.events() if e.get("kind") == "job_event"
            and e.get("status") == "done" and "latency_ms" in e]
    assert len(done) == _N_JOBS, f"{len(done)}/{_N_JOBS} jobs done"
    print(f"MAX_LATENCY_MS={max(e['latency_ms'] for e in done):.3f}")
    print("SERVE_WORKER_OK")
    return 0


def worker_stall() -> int:
    """Wedge inside a chunk span against a fabricated stale peer: the
    heartbeat watchdog must bundle a post-mortem and abort with 76."""
    from distributed_matvec_tpu import obs
    from distributed_matvec_tpu.parallel.heartbeat import HeartbeatWatchdog

    run_dir = obs.run_dir()
    assert run_dir, "worker needs DMT_OBS_DIR"
    with obs.span("lanczos_block", kind="solve", k=1):
        with obs.span("iteration", kind="iteration", iter=3):
            with obs.span("apply", kind="apply", apply=12):
                with obs.span("chunk", kind="chunk", chunk=3):
                    hb_dir = os.path.join(run_dir, "heartbeat")
                    os.makedirs(hb_dir, exist_ok=True)
                    stale = os.path.join(hb_dir, "rank_1.hb")
                    with open(stale, "w") as f:
                        f.write("1.0\n")
                    os.utime(stale, (1.0, 1.0))   # beat predates the run
                    wd = HeartbeatWatchdog(run_dir, interval_s=0.05,
                                           timeout_s=0.3, rank=0, n_ranks=2)
                    wd.start()
                    time.sleep(20)   # the watchdog os._exit(76)s us
    print("STALL_WORKER_NOT_KILLED")
    return 3


_WORKERS = {"worker-obs-off": worker_obs_off,
            "worker-serve": worker_serve,
            "worker-stall": worker_stall}


# ---------------------------------------------------------------------------
# the gate


def _run_worker(name: str, *args, env=None, expect_rc=0):
    cmd = [sys.executable, os.path.abspath(__file__), name, *args]
    proc = subprocess.run(cmd, env=env, stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True, timeout=600)
    if proc.returncode != expect_rc:
        print(proc.stdout)
        raise AssertionError(
            f"{name} exited {proc.returncode}, wanted {expect_rc}")
    return proc.stdout


def _read_events(run_dir: str):
    import obs_report
    return obs_report.load_events(run_dir)


def main() -> int:
    if _WORKER:
        return _WORKERS[sys.argv[1]]()

    import tempfile
    import urllib.request

    scratch = tempfile.mkdtemp(prefix="dmt_slo_check_")
    clean_dir = os.path.join(scratch, "clean")
    os.environ["DMT_OBS_DIR"] = clean_dir

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)

    import numpy as np  # noqa: F401  (env sanity: the rig has numpy)

    import obs_report
    from distributed_matvec_tpu import obs
    from distributed_matvec_tpu.models.basis import SpinBasis
    from distributed_matvec_tpu.models.lattices import (
        chain_edges, heisenberg_from_edges)
    from distributed_matvec_tpu.parallel.engine import LocalEngine
    from distributed_matvec_tpu.solve.lanczos import lanczos_block

    # -- 1. clean run: export parity + zero alerts ------------------------
    ns = 12
    basis = SpinBasis(number_spins=ns, hamming_weight=ns // 2)
    op = heisenberg_from_edges(basis, chain_edges(ns))
    basis.build()
    eng = LocalEngine(op, mode="ell")
    res = lanczos_block(eng.matvec, basis.number_states, k=1, tol=1e-8,
                        max_iters=120)
    print(f"[slo-check] chain_{ns} E0={res.eigenvalues[0]:.8f} "
          f"({res.num_iters} iters)")

    snap = obs.snapshot()
    assert snap["counters"] or snap["histograms"], "no metrics recorded?"
    # render -> parse round trip must be EXACT (repr floats)
    assert obs.parse_openmetrics(obs.render_openmetrics(snap)) == snap
    # a REAL scrape over HTTP agrees with the registry
    server = obs.start_exporter(port=0)
    assert server is not None, "exporter refused an ephemeral port"
    url = f"http://127.0.0.1:{server.port}/metrics"
    scraped = obs.parse_openmetrics(
        urllib.request.urlopen(url, timeout=10).read().decode())
    assert scraped == snap, "HTTP scrape != registry snapshot"
    health = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{server.port}/healthz", timeout=10)
        .read().decode())
    assert health.get("status") == "ok"
    # the scrape-less textfile path agrees too
    tf = obs.write_textfile()
    with open(tf) as f:
        assert obs.parse_openmetrics(f.read()) == snap
    obs.stop_exporter()
    print("[slo-check] OpenMetrics parity OK (render/scrape/textfile)")

    # zero alerts on the clean stream, and the snapshot recovered from
    # events.jsonl equals what was scraped (the ISSUE parity acceptance)
    obs.check_slos()
    alerts = _alerts(obs.events())
    assert not alerts, f"clean run fired alerts: {alerts}"
    obs.emit("metrics_snapshot", metrics=snap)
    obs.flush()
    recovered = [e for e in _read_events(clean_dir)
                 if e.get("kind") == "metrics_snapshot"][-1]["metrics"]
    assert recovered == scraped, "events.jsonl snapshot != scraped metrics"
    assert obs_report.main(["slo", clean_dir, *_WALLS_OFF]) == 0
    print("[slo-check] clean run: zero alerts, `obs_report slo` exit 0")

    # -- 2. DMT_OBS=off no-op ---------------------------------------------
    off_dir = os.path.join(scratch, "off")
    out = _run_worker("worker-obs-off",
                      env=dict(os.environ, DMT_OBS="off",
                               DMT_OBS_DIR=off_dir))
    assert "OBS_OFF_OK" in out
    assert not os.path.exists(off_dir), "obs-off run created a sink dir"
    print("[slo-check] DMT_OBS=off: no port, no ring, no bundles, no dir")

    # -- 3. injected latency burns the p99 SLO ----------------------------
    serve_clean = os.path.join(scratch, "serve_clean")
    out = _run_worker("worker-serve", os.path.join(scratch, "spool_clean"),
                      env=dict(os.environ, DMT_OBS_DIR=serve_clean))
    assert "SERVE_WORKER_OK" in out
    max_ms = float([ln for ln in out.splitlines()
                    if ln.startswith("MAX_LATENCY_MS=")][0].split("=")[1])
    clean_events = _read_events(serve_clean)
    assert not _alerts(clean_events), "clean serve drain fired alerts"
    # the pinned objective: generous over the measured clean worst case,
    # so only the injected delay — never scheduler noise — can burn it
    target = f"serve_p99_latency_ms={1.5 * max_ms:.3f}"
    assert obs_report.main(["slo", serve_clean, "--target", target,
                            *_WALLS_OFF]) == 0
    print(f"[slo-check] clean drain p99 <= {max_ms:.0f} ms; "
          f"pinned target {target}")

    serve_burn = os.path.join(scratch, "serve_burn")
    out = _run_worker(
        "worker-serve", os.path.join(scratch, "spool_burn"),
        env=dict(os.environ, DMT_OBS_DIR=serve_burn,
                 DMT_FAULT="solver_block:delay=800:skip=2:n=100000"))
    assert "SERVE_WORKER_OK" in out
    burn_events = _read_events(serve_burn)
    assert [e for e in burn_events if e.get("kind") == "fault_injected"], \
        "delay site never fired"
    # the worker's in-process check_slos left alerts in the stream ...
    assert [e for e in burn_events if e.get("kind") == "slo_alert"
            and e.get("state") == "firing"], "no slo_alert in burn stream"
    # ... and the SAME pinned target now fails the CI reader
    rc = obs_report.main(["slo", serve_burn, "--target", target])
    assert rc == 1, f"burned run graded clean (rc {rc})"
    statuses = {s["name"]: s for s in _load_slo_statuses(serve_burn, target)}
    assert statuses["serve_p99_latency_ms"]["state"] == "firing"
    print("[slo-check] injected solver_block delay burns "
          "serve_p99_latency_ms: `obs_report slo` exit 1 + slo_alert "
          "in stream")

    # -- 4. forced exit-76 leaves one valid post-mortem -------------------
    stall_dir = os.path.join(scratch, "stall")
    _run_worker("worker-stall",
                env=dict(os.environ, DMT_OBS_DIR=stall_dir), expect_rc=76)
    entries = obs_report.scan_postmortems(stall_dir)
    assert len(entries) == 1, f"expected 1 bundle, found {len(entries)}"
    assert entries[0]["valid"], "bundle failed content-address check"
    b = entries[0]["bundle"]
    assert b["reason"] == "stall" and b["exit_code"] == 76
    assert b["report"]["stalled"] == [1], b["report"]
    assert "chunk" in (b["span_path"] or ""), \
        f"bundle does not name the stuck chunk: {b['span_path']!r}"
    assert (b["span"] or {}).get("kind") == "chunk"
    assert obs_report.main(["postmortem", stall_dir]) == 0
    print(f"[slo-check] exit-76 left one valid bundle naming "
          f"[{b['span_path']}]")

    print("[slo-check] PASS")
    return 0


def _load_slo_statuses(run_dir: str, *targets: str):
    import obs_report
    slo_mod = obs_report._load_slo()
    pins = {}
    for t in targets:
        name, _, val = t.partition("=")
        pins[name] = float(val)
    return slo_mod.evaluate(obs_report.load_events(run_dir),
                            slo_mod.default_slos(pins))


if __name__ == "__main__":
    sys.exit(main())
