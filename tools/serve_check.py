#!/usr/bin/env python
"""serve-check — CI gate for the solve service (`make serve-check`).

Asserts, on the CPU rig:

1. **Burst correctness + sharing** — 8 mixed jobs (3 bases, one shared
   by 4) submitted as one burst and drained through the scheduler in
   this process: every job done, eigenvalues matching sequential solo
   ``lanczos_block`` runs at rtol 1e-12, engine-pool sharing (engine
   builds < jobs) and batching (batches < jobs, one of them four wide)
   read off the pool's and the records' own counts.
2. **Watch panel** — ``obs_report watch --once`` over the load-gen run
   renders the queue panel (jobs by status, admission verdicts, pool
   occupancy).
3. **SIGTERM drain** — a spool-backed ``apps/solve_service.py`` process,
   slowed deterministically via the PR 6 fault registry
   (``DMT_FAULT=solver_block:delay=…``), is SIGTERMed mid-solve: it must
   exit 75 with every unfinished job respooled as queued (the job-level
   checkpoint contract), and a relaunch must drain them all.
"""

import json
import os
import signal
import subprocess
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_X64"] = "true"

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def _log(msg):
    print(f"[serve-check] {msg}", flush=True)


def _fail(msg):
    print(f"[serve-check] FAIL: {msg}", flush=True)
    return 1


def _run(argv, timeout, **kw):
    return subprocess.run(argv, timeout=timeout, text=True,
                          capture_output=True, **kw)


def _serve_job_specs():
    """The mixed load of eight jobs: >=2 distinct bases with >=3 jobs sharing one (the
    ISSUE 11 acceptance shape), heterogeneous (k, tol) per job.  All
    tolerances <= 1e-8: the Lanczos eigenvalue error is quadratic in the
    residual bound, so batched and solo runs agree at rtol 1e-12 even
    though their start columns differ."""
    from distributed_matvec_tpu.serve import JobSpec

    A = dict(number_spins=12, hamming_weight=6)      # shared by 4 jobs
    B = dict(number_spins=10, hamming_weight=5)      # shared by 3
    C = dict(number_spins=8, hamming_weight=4)
    protos = (("a0", A, 1, 1e-10), ("a1", A, 2, 1e-9),
              ("a2", A, 1, 1e-8), ("a3", A, 1, 1e-10),
              ("b0", B, 1, 1e-10), ("b1", B, 1, 1e-9),
              ("b2", B, 2, 1e-8), ("c0", C, 1, 1e-10))
    return [JobSpec(job_id=f"{tag}_{i}", basis=dict(basis), k=k, tol=tol,
                    max_iters=400)
            for i, (tag, basis, k, tol) in enumerate(protos)]


def leg_burst(scratch: str):
    """One burst through the scheduler against sequential solo solves of
    the same job list: parity, sharing and batching by count, then the
    watch panel over the burst's telemetry."""
    from distributed_matvec_tpu import obs
    from distributed_matvec_tpu.serve import EnginePool, JobQueue, Scheduler
    from distributed_matvec_tpu.serve.pool import build_engine
    from distributed_matvec_tpu.solve import lanczos_block

    specs = _serve_job_specs()
    n_jobs = len(specs)
    obs_dir = os.path.join(scratch, "run")
    os.environ["DMT_OBS_DIR"] = obs_dir
    obs.reset()                        # point the sink at the run dir
    try:
        queue, pool = JobQueue(), EnginePool()
        sched = Scheduler(queue=queue, pool=pool)
        for s in specs:
            sched.submit(s)
        sched.drain(scan_spool=False)
        obs.flush()
    finally:
        del os.environ["DMT_OBS_DIR"]
        obs.reset()

    recs = {s.job_id: queue.result(s.job_id) for s in specs}
    not_done = [j for j, r in recs.items()
                if not r or r["status"] != "done"]
    if not_done:
        return _fail(f"jobs not done: {not_done}")
    e0_err = 0.0
    for s in specs:
        eng = build_engine(s)
        solo = lanczos_block(eng.matvec, n=eng.n_states, k=s.k, tol=s.tol,
                             max_iters=s.max_iters, seed=s.column_seed())
        for w, ws in zip(recs[s.job_id]["eigenvalues"], solo.eigenvalues):
            e0_err = max(e0_err, abs(w - float(ws))
                         / max(abs(float(ws)), 1e-300))
    if e0_err > 1e-12:
        return _fail(f"batched-vs-solo E0 rel err {e0_err:.2e} > 1e-12")
    if not pool.builds < n_jobs:
        return _fail(f"no engine sharing: {pool.builds} builds for "
                     f"{n_jobs} jobs")
    # a batch of width w stamps w records with batch_width=w
    widths = [int(r["batch_width"]) for r in recs.values()]
    batches = sum(widths.count(w) // w for w in set(widths))
    if not (batches < n_jobs and max(widths) == 4):
        return _fail(f"burst was not batched: widths {widths}")
    _log(f"burst: {n_jobs} jobs done in {batches} batches (widest "
         f"{max(widths)}), {pool.builds} engine builds, {pool.hits} pool "
         f"hits, E0 rel err vs solo {e0_err:.1e}")
    r = _run([sys.executable, os.path.join(_REPO, "tools", "obs_report.py"),
              "watch", obs_dir, "--once"], timeout=120)
    if r.returncode != 0:
        return _fail(f"watch --once failed:\n{r.stderr}")
    if "serve " not in r.stdout or "pool " not in r.stdout:
        return _fail("watch frame lacks the serve/pool queue "
                     f"panel:\n{r.stdout}")
    _log("watch --once renders the queue panel")
    return 0


def leg_sigterm(scratch: str):
    """SIGTERM drain: exit 75, unfinished jobs respooled, relaunch
    completes them."""
    from distributed_matvec_tpu.serve import JobSpec, submit_to_spool

    spool = os.path.join(scratch, "spool")
    n_jobs = 4
    for i in range(n_jobs):
        submit_to_spool(spool, JobSpec(
            job_id=f"sig{i}",
            basis={"number_spins": 12, "hamming_weight": 6},
            k=1, tol=1e-10, max_iters=400))
    obs_dir = os.path.join(scratch, "sig_run")
    # ~10 s of deterministic per-block-step latency: the SIGTERM always
    # lands mid-solve, never in the post-drain epilogue
    env = dict(os.environ, DMT_OBS_DIR=obs_dir,
               DMT_FAULT="solver_block:delay=400:n=10000")
    argv = [sys.executable, os.path.join(_REPO, "apps", "solve_service.py"),
            spool, "--drain"]
    p = subprocess.Popen(argv, env=env, text=True,
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    # wait for the first job to actually be RUNNING (its lifecycle event
    # reaches the sink), then preempt
    deadline = time.time() + 240
    ev_glob = os.path.join(obs_dir, "rank_0", "events.jsonl")
    running = False
    while time.time() < deadline and not running:
        if os.path.exists(ev_glob):
            with open(ev_glob) as f:
                running = any('"job_event"' in ln and '"running"' in ln
                              for ln in f)
        if p.poll() is not None:
            out = p.stdout.read()
            return _fail(f"service exited {p.returncode} before the "
                         f"signal:\n{out[-2000:]}")
        time.sleep(0.3)
    if not running:
        p.kill()
        return _fail("no job reached RUNNING within the deadline")
    p.send_signal(signal.SIGTERM)
    try:
        out, _ = p.communicate(timeout=180)
    except subprocess.TimeoutExpired:
        p.kill()
        return _fail("service did not exit after SIGTERM")
    if p.returncode != 75:
        return _fail(f"expected exit 75 after SIGTERM, got "
                     f"{p.returncode}:\n{out[-2000:]}")
    queued = sorted(os.listdir(os.path.join(spool, "queue")))
    done = sorted(os.listdir(os.path.join(spool, "done")))
    if len(queued) + len(done) != n_jobs or not queued:
        return _fail(f"respool broken after drain: queue={queued} "
                     f"done={done}")
    _log(f"SIGTERM drain: exit 75, {len(done)} done, {len(queued)} "
         "respooled as queued")
    # relaunch WITHOUT the injected latency: the respooled jobs drain
    env2 = dict(os.environ)
    env2.pop("DMT_FAULT", None)
    r = _run(argv, timeout=600, env=env2)
    if r.returncode != 0:
        return _fail(f"relaunch exited {r.returncode}:\n"
                     f"{r.stdout[-2000:]}")
    done = sorted(os.listdir(os.path.join(spool, "done")))
    if len(done) != n_jobs:
        return _fail(f"relaunch left jobs behind: done={done}")
    for name in done:
        with open(os.path.join(spool, "done", name)) as f:
            rec = json.load(f)
        if rec["status"] != "done" or not rec.get("converged"):
            return _fail(f"{name}: {rec['status']}, converged="
                         f"{rec.get('converged')}")
    _log(f"relaunch drained all {n_jobs} jobs clean")
    return 0


def main() -> int:
    import tempfile

    t0 = time.time()
    with tempfile.TemporaryDirectory(prefix="dmt_serve_check_") as scratch:
        rc = leg_burst(scratch) or leg_sigterm(scratch)
        if rc:
            return rc
    _log(f"OK ({time.time() - t0:.0f}s): parity at 1e-12, engine sharing, "
         "batching, watch panel, SIGTERM drain + resume")
    return 0


if __name__ == "__main__":
    sys.exit(main())
