#!/usr/bin/env python
"""elastic-check — the chaos gate for topology-portable solves
(`make elastic-check`).

PR 6's fault-check gate proves a solve survives a kill and resumes on the
SAME device count; this gate proves the elastic contract on the 2↔4
CPU-device rig (virtual devices — the same oversubscription rig every
other gate uses):

1. **Shrink (kill at 4, resume at 2)** — a delay-stretched chain_12
   solve on a 4-device mesh is SIGKILLed mid-iteration once a checkpoint
   generation exists; a relaunch with ``--devices 2`` must RESHARD the
   snapshot (``solver_checkpoint{status=resharded, d_from=4, d_to=2}``),
   print ``resumed from``, and land E0 within rtol 1e-12 of an
   uninterrupted run.
2. **Grow (kill at 2, resume at 4)** — the reverse direction, same
   assertions.
3. **Shrink+grow cycle, no operator intervention** — a chain_16 solve
   (the CPU-rig stand-in for the ROADMAP's chain_28-class rung) is
   driven by a dumb supervisor loop: kill at 4 → resume at 2 (killed
   again) → resume at 4 → completion.  Both reshard directions fire and
   the final E0 matches the uninterrupted reference at rtol 1e-12.
4. **Matching-D restore unchanged** — rerunning the baseline argv
   resumes from its own checkpoint with NO reshard event (the fixed-D
   fast path is untouched; the byte-level v1-format compatibility is
   pinned in tests/test_elastic.py).
5. **Torn reshard degrades** — ``DMT_FAULT=ckpt_reshard`` injected into
   a D→D′ relaunch: the restore must degrade to a FRESH solve
   (``solver_checkpoint{status=reshard_failed}``, no ``resumed from``)
   that still lands the right E0 — never a half-redistributed basis.
6. **Serve-layer elasticity** — a spool-backed solve service running on
   2 devices is SIGTERMed mid-solve (exit 75, jobs respooled) and
   relaunched on 1 device: the respooled jobs re-admit against the LIVE
   capacity (``admission{live_devices=1}``), engines build clamped, and
   the queue drains with every job converged.
7. **Plan re-fingerprinting** — a streamed engine rebuilt at D′ next to
   a D-era sidecar emits ``plan_reshard`` with the rebuild wall.

Deterministic seeds/faults throughout; ~90 s warm on the CPU rig
(up to ~4 min cold).
"""

import json
import os
import signal
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# platform pins BEFORE any jax import (parent process runs the in-process
# plan-reshard leg on up to 4 virtual devices)
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_X64"] = "true"
os.environ["DMT_ARTIFACT_CACHE"] = "off"

RTOL = 1e-12

_YAML_12 = """\
basis:
  number_spins: 12
  hamming_weight: 6
hamiltonian:
  name: heisenberg_chain_12
  terms:
    - expression: "σˣ₀ σˣ₁ + σʸ₀ σʸ₁ + σᶻ₀ σᶻ₁"
      sites: [[0,1],[1,2],[2,3],[3,4],[4,5],[5,6],[6,7],[7,8],[8,9],
              [9,10],[10,11],[11,0]]
"""

_YAML_16 = """\
basis:
  number_spins: 16
  hamming_weight: 8
hamiltonian:
  name: heisenberg_chain_16
  terms:
    - expression: "σˣ₀ σˣ₁ + σʸ₀ σʸ₁ + σᶻ₀ σᶻ₁"
      sites: [[0,1],[1,2],[2,3],[3,4],[4,5],[5,6],[6,7],[7,8],[8,9],
              [9,10],[10,11],[11,12],[12,13],[13,14],[14,15],[15,0]]
"""


def _log(msg):
    print(f"[elastic-check] {msg}", flush=True)


def _driver_env(devices, **extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("DMT_FAULT", None)
    # each child gets its OWN virtual-device pool — the resize under test
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices}")
    env.update(extra)
    return env


def _run_driver(scratch, yaml_name, tag, devices, fault=None, wait=True,
                obs_tag=None):
    args = [sys.executable, os.path.join(_REPO, "apps", "diagonalize.py"),
            os.path.join(scratch, yaml_name),
            "-o", os.path.join(scratch, f"{tag}.h5"), "-k", "1",
            "--tol", "1e-12", "--max-iters", "600",
            "--devices", str(devices),
            "--solver-checkpoint", os.path.join(scratch, f"ck_{tag}.h5"),
            "--checkpoint-every", "1", "--no-eigenvectors",
            "--obs-dir", os.path.join(scratch, f"obs_{obs_tag or tag}")]
    env = _driver_env(devices, **({"DMT_FAULT": fault} if fault else {}))
    p = subprocess.Popen(args, env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
    if not wait:
        return p
    out, _ = p.communicate(timeout=600)
    return p.returncode, out


def _e0(scratch, tag):
    import h5py

    with h5py.File(os.path.join(scratch, f"{tag}.h5"), "r") as f:
        return float(f["hamiltonian/eigenvalues"][0])


def _events(scratch, obs_tag):
    path = os.path.join(scratch, f"obs_{obs_tag}", "rank_0", "events.jsonl")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _assert_close(got, want, what):
    rel = abs(got - want) / max(abs(want), 1.0)
    assert rel <= RTOL, (f"{what}: E0 {got!r} vs reference {want!r} "
                         f"(rel {rel:.2e} > {RTOL})")
    _log(f"{what}: E0 matches to rel {rel:.2e}")


def _kill_once_checkpointed(scratch, yaml_name, tag, devices, obs_tag):
    """Launch a delay-stretched solve at ``devices`` and SIGKILL it once
    a checkpoint generation WRITTEN BY THIS RUN exists (a relaunch mid-
    cycle starts next to its predecessor's file — the kill must wait for
    the resumed run to restore and write its own generation, or it lands
    before the restore the next phase depends on)."""
    ck = os.path.join(scratch, f"ck_{tag}.h5")
    try:
        before = os.stat(ck).st_mtime_ns
    except OSError:
        before = None
    p = _run_driver(scratch, yaml_name, tag, devices,
                    fault="solver_block:delay=500:n=10000", wait=False,
                    obs_tag=obs_tag)
    t0 = time.time()
    while time.time() - t0 < 240:
        try:
            if os.stat(ck).st_mtime_ns != before:
                break
        except OSError:
            pass
        if p.poll() is not None:
            out = p.communicate()[0]
            raise AssertionError(
                f"{tag}: solve finished before the kill landed "
                f"(rc={p.returncode}):\n{out[-2000:]}")
        time.sleep(0.05)
    else:
        p.kill()
        raise AssertionError(f"{tag}: no checkpoint appeared within 240 s")
    p.send_signal(signal.SIGKILL)
    p.communicate(timeout=120)
    assert p.returncode == -signal.SIGKILL, p.returncode


def _reshard_events(scratch, obs_tag, status="resharded"):
    return [e for e in _events(scratch, obs_tag)
            if e.get("kind") == "solver_checkpoint"
            and e.get("status") == status]


def leg_resize(scratch, d_kill, d_resume, tag, e0_ref):
    """Kill at ``d_kill``, resume at ``d_resume``."""
    _kill_once_checkpointed(scratch, "chain12.yaml", tag, d_kill,
                            obs_tag=f"{tag}_kill")
    rc, out = _run_driver(scratch, "chain12.yaml", tag, d_resume,
                          obs_tag=f"{tag}_resume")
    assert rc == 0, f"{tag}: resume at D={d_resume} failed (rc={rc}):\n" \
                    f"{out[-2000:]}"
    assert "resumed from" in out, \
        f"{tag}: relaunch did not resume:\n{out[-800:]}"
    evs = _reshard_events(scratch, f"{tag}_resume")
    assert evs, f"{tag}: no solver_checkpoint{{status=resharded}} event"
    ev = evs[-1]
    assert ev["d_from"] == d_kill and ev["d_to"] == d_resume, ev
    _assert_close(_e0(scratch, tag), e0_ref,
                  f"{tag} (kill@{d_kill} → resume@{d_resume})")


def leg_cycle(scratch, e0_ref16):
    """chain_16 through a full shrink+grow cycle with no operator
    intervention: a dumb supervisor relaunches on every nonzero exit,
    following the fleet's device schedule 4 → 2 → 4."""
    tag = "cycle"
    schedule = [(4, True), (2, True), (4, False)]
    for phase, (devices, kill) in enumerate(schedule):
        if kill:
            _kill_once_checkpointed(scratch, "chain16.yaml", tag, devices,
                                    obs_tag=f"{tag}_{phase}")
            _log(f"cycle phase {phase}: killed at D={devices}")
        else:
            rc, out = _run_driver(scratch, "chain16.yaml", tag, devices,
                                  obs_tag=f"{tag}_{phase}")
            assert rc == 0, f"cycle final phase rc={rc}:\n{out[-2000:]}"
            assert "resumed from" in out, out[-800:]
    # both directions actually resharded: 4→2 in phase 1, 2→4 in phase 2
    ev12 = _reshard_events(scratch, f"{tag}_1")
    ev24 = _reshard_events(scratch, f"{tag}_2")
    assert ev12 and ev12[-1]["d_from"] == 4 and ev12[-1]["d_to"] == 2, ev12
    assert ev24 and ev24[-1]["d_from"] == 2 and ev24[-1]["d_to"] == 4, ev24
    _assert_close(_e0(scratch, tag), e0_ref16, "shrink+grow cycle")


def leg_matching_d(scratch):
    """Rerunning the baseline argv resumes its own checkpoint with NO
    reshard event — the fixed-D fast path stays untouched."""
    rc, out = _run_driver(scratch, "chain12.yaml", "base", 2,
                          obs_tag="base_rerun")
    assert rc == 0, out[-2000:]
    assert "resumed from" in out, out[-800:]
    assert not _reshard_events(scratch, "base_rerun"), \
        "matching-D restore emitted a reshard event"
    _log("matching-D restore: resumed, no reshard")


def leg_reshard_fault(scratch, e0_ref):
    """ckpt_reshard injected into a D→D′ relaunch: the restore degrades
    to a fresh solve (never a torn basis) that still lands E0."""
    tag = "chaos"
    _kill_once_checkpointed(scratch, "chain12.yaml", tag, 4,
                            obs_tag=f"{tag}_kill")
    args_env = {"DMT_FAULT": "ckpt_reshard:n=1"}
    p = subprocess.Popen(
        [sys.executable, os.path.join(_REPO, "apps", "diagonalize.py"),
         os.path.join(scratch, "chain12.yaml"),
         "-o", os.path.join(scratch, f"{tag}.h5"), "-k", "1",
         "--tol", "1e-12", "--max-iters", "600", "--devices", "2",
         "--solver-checkpoint", os.path.join(scratch, f"ck_{tag}.h5"),
         "--checkpoint-every", "1", "--no-eigenvectors",
         "--obs-dir", os.path.join(scratch, f"obs_{tag}_resume")],
        env=_driver_env(2, **args_env), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    out, _ = p.communicate(timeout=600)
    assert p.returncode == 0, f"chaos resume rc={p.returncode}:\n" \
                              f"{out[-2000:]}"
    assert "resumed from" not in out, \
        f"torn reshard still resumed:\n{out[-800:]}"
    evs = _events(scratch, f"{tag}_resume")
    kinds = [(e.get("kind"), e.get("status")) for e in evs]
    assert ("solver_checkpoint", "reshard_failed") in kinds, \
        "no solver_checkpoint{status=reshard_failed} event"
    assert any(e.get("kind") == "fault_injected"
               and e.get("site") == "ckpt_reshard" for e in evs), \
        "ckpt_reshard fault never fired"
    _assert_close(_e0(scratch, tag), e0_ref, "torn-reshard fresh solve")


def leg_serve(scratch):
    """SIGTERM a 2-device solve service mid-batch, drain on 1 device:
    respooled jobs re-admit against the LIVE capacity and finish."""
    sys.path.insert(0, _REPO)
    from distributed_matvec_tpu.serve import JobSpec, submit_to_spool

    spool = os.path.join(scratch, "spool")
    n_jobs = 3
    for i in range(n_jobs):
        submit_to_spool(spool, JobSpec(
            job_id=f"el{i}",
            basis={"number_spins": 12, "hamming_weight": 6},
            k=1, tol=1e-10, max_iters=400, mode="ell", n_devices=2))
    argv = [sys.executable, os.path.join(_REPO, "apps", "solve_service.py"),
            spool, "--drain"]
    obs_dir = os.path.join(scratch, "obs_serve_d2")
    env = _driver_env(2, DMT_OBS_DIR=obs_dir,
                      DMT_FAULT="solver_block:delay=400:n=10000")
    p = subprocess.Popen(argv, env=env, text=True, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT)
    deadline = time.time() + 240
    ev_path = os.path.join(obs_dir, "rank_0", "events.jsonl")
    running = False
    while time.time() < deadline and not running:
        if os.path.exists(ev_path):
            with open(ev_path) as f:
                running = any('"job_event"' in ln and '"running"' in ln
                              for ln in f)
        if p.poll() is not None:
            out = p.stdout.read()
            raise AssertionError(f"service exited {p.returncode} before "
                                 f"the signal:\n{out[-2000:]}")
        time.sleep(0.3)
    assert running, "no job reached RUNNING before the deadline"
    p.send_signal(signal.SIGTERM)
    out, _ = p.communicate(timeout=240)
    assert p.returncode == 75, f"SIGTERM drain rc={p.returncode}:\n" \
                               f"{out[-2000:]}"
    queued = sorted(os.listdir(os.path.join(spool, "queue")))
    assert queued, "no jobs respooled after the SIGTERM at D=2"
    _log(f"service killed at D=2: {len(queued)} job(s) respooled")

    # relaunch on ONE device: the respooled jobs must re-admit and run
    obs_dir2 = os.path.join(scratch, "obs_serve_d1")
    env2 = _driver_env(1, DMT_OBS_DIR=obs_dir2)
    r = subprocess.run(argv, env=env2, text=True, capture_output=True,
                       timeout=600)
    assert r.returncode == 0, f"drain at D=1 rc={r.returncode}:\n" \
                              f"{r.stdout[-2000:]}"
    done = sorted(os.listdir(os.path.join(spool, "done")))
    assert len(done) == n_jobs, f"relaunch left jobs behind: {done}"
    for name in done:
        with open(os.path.join(spool, "done", name)) as f:
            rec = json.load(f)
        assert rec["status"] == "done" and rec.get("converged"), rec
    with open(os.path.join(obs_dir2, "rank_0", "events.jsonl")) as f:
        evs = [json.loads(ln) for ln in f if ln.strip()]
    adm = [e for e in evs if e.get("kind") == "admission"]
    assert adm and all(e.get("live_devices") == 1 for e in adm), \
        f"admission did not price against the live capacity: {adm[:2]}"
    assert any(e.get("kind") == "engine_clamp"
               and e.get("live_devices") == 1 for e in evs), \
        "engine build was not clamped to the live topology"
    _log(f"drain at D=1: {n_jobs} jobs re-admitted at live capacity and "
         "converged")


def leg_plan_rebuild(scratch):
    """In-process: a streamed engine rebuilt at D′ next to a D-era
    sidecar emits plan_reshard with the rebuild wall."""
    from distributed_matvec_tpu import obs
    from distributed_matvec_tpu.models.yaml_io import load_config_from_yaml
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine

    cfg = load_config_from_yaml(os.path.join(scratch, "chain12.yaml"))
    cfg.basis.build()
    cache = os.path.join(scratch, "plan_cache.h5")
    DistributedEngine(cfg.hamiltonian, n_devices=2, mode="streamed",
                      structure_cache=cache)
    assert not obs.events("plan_reshard"), \
        "cold streamed build emitted plan_reshard"
    DistributedEngine(cfg.hamiltonian, n_devices=4, mode="streamed",
                      structure_cache=cache)
    evs = obs.events("plan_reshard")
    assert evs and evs[-1]["d_from"] == [2] and evs[-1]["d_to"] == 4, evs
    assert "rebuild_s" in evs[-1], evs[-1]
    _log("plan_reshard: per-D′ rebuild observable "
         f"({float(evs[-1]['rebuild_s']):.3f} s)")


def main() -> int:
    t_start = time.time()
    scratch = tempfile.mkdtemp(prefix="dmt_elastic_check_")
    with open(os.path.join(scratch, "chain12.yaml"), "w") as f:
        f.write(_YAML_12)
    with open(os.path.join(scratch, "chain16.yaml"), "w") as f:
        f.write(_YAML_16)

    # uninterrupted references
    rc, out = _run_driver(scratch, "chain12.yaml", "base", 2)
    assert rc == 0, f"chain_12 baseline failed (rc={rc}):\n{out[-2000:]}"
    e0_ref = _e0(scratch, "base")
    _log(f"chain_12 baseline E0 = {e0_ref:.12f}")
    rc, out = _run_driver(scratch, "chain16.yaml", "base16", 4)
    assert rc == 0, f"chain_16 baseline failed (rc={rc}):\n{out[-2000:]}"
    e0_ref16 = _e0(scratch, "base16")
    _log(f"chain_16 baseline E0 = {e0_ref16:.12f}")

    leg_resize(scratch, 4, 2, "shrink", e0_ref)
    leg_resize(scratch, 2, 4, "grow", e0_ref)
    leg_cycle(scratch, e0_ref16)
    leg_matching_d(scratch)
    leg_reshard_fault(scratch, e0_ref)
    leg_serve(scratch)
    leg_plan_rebuild(scratch)

    _log(f"PASS ({time.time() - t_start:.1f} s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
