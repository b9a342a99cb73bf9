#!/usr/bin/env python
"""profile-check — CI gate for the continuous-profiling plane
(`make profile-check`, DESIGN.md §32).

Asserts, on the CPU rig (2 virtual devices, chain_<spins>_symm):

1. **HLO cost attribution at compile** — every `precompile()` miss
   records a per-op cost profile whose phase buckets sum EXACTLY to the
   executable's whole-program `cost_analysis()` totals, persisted as a
   content-addressed artifact (`hlo-profile/<fp2>/<fp>.json`) that
   round-trips through `load_profile`.
2. **HLO byte-identity** — the local ell and distributed fused apply
   programs are byte-identical with `DMT_PROFILE=sampled` vs off:
   `jax.profiler.trace` observes the program, it never alters it.
3. **Sampled windows on the cadence** — `profile_every=8` over 16 eager
   applies captures exactly two trace windows (the overhead ledger
   counts 16 applies, 2 profiled), the newest capture directory stamped
   with PROFILE_META.json.  What the windows cost is a time and is not
   gated here; the latch that turns sampling off over budget is
   `tests/test_profile.py::test_overhead_guard_latches_and_says_so`.
4. **HLO-vs-measured reconciliation** — `obs_report roofline` carries a
   third per-phase column (`hlo ms`) whose sum equals the measured
   apply wall (the normalization contract; the signal is the split).
5. **Triggered deep capture** — `obs.trigger_capture` on an incident
   dumps a flight-recorder bundle naming the hottest ops.
6. **Differential profiling** — `tools/profile_diff.py` passes on an
   artifact diffed against itself, then FIRES (exit 1) naming the op
   whose bytes were synthetically grown 10x, in the top regression row.
"""

import os
import subprocess
import sys

# platform pins BEFORE any jax import (same discipline as tests/conftest)
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_X64"] = "true"
# the gate asserts DEFAULT enablement with its own scratch sinks —
# inherited telemetry/profile state must not leak in or out
for var in ("DMT_PROFILE", "DMT_PROFILE_EVERY", "DMT_PHASES",
            "DMT_OBS", "DMT_OBS_DIR", "DMT_ARTIFACT_DIR",
            "DMT_ARTIFACT_CACHE"):
    os.environ.pop(var, None)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tools"))

RECONCILE_TOL = 0.02        # sum(hlo_ms) vs wall: normalization + rounding


def main() -> int:
    import argparse
    import json
    import math
    import tempfile

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--spins", type=int, default=16,
                    help="chain length of the gate config (default 16)")
    args = ap.parse_args()

    scratch = tempfile.mkdtemp(prefix="dmt_profile_check_")
    run_dir = os.path.join(scratch, "run")
    os.environ["DMT_OBS_DIR"] = run_dir
    # fresh artifact root => every compile is a miss => every program's
    # cost profile is recorded and content-addressed right here
    os.environ["DMT_ARTIFACT_DIR"] = os.path.join(scratch, "artifacts")

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)

    import jax.numpy as jnp
    import numpy as np

    from distributed_matvec_tpu import obs
    from distributed_matvec_tpu.models.basis import SpinBasis
    from distributed_matvec_tpu.models.lattices import (chain_edges,
                                                        heisenberg_from_edges)
    from distributed_matvec_tpu.obs import hlo as H
    from distributed_matvec_tpu.obs import profile as P
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine
    from distributed_matvec_tpu.parallel.engine import LocalEngine
    from distributed_matvec_tpu.utils.config import update_config

    ns = args.spins
    basis = SpinBasis(number_spins=ns, hamming_weight=ns // 2,
                      spin_inversion=1,
                      symmetries=[([*range(1, ns), 0], 0),
                                  ([*reversed(range(ns))], 0)])
    op = heisenberg_from_edges(basis, chain_edges(ns))
    basis.build()
    n = basis.number_states
    print(f"[profile-check] chain_{ns}_symm: N={n}, 2 shards")
    rng = np.random.default_rng(7)
    x = rng.standard_normal(n)
    x /= np.linalg.norm(x)

    el = LocalEngine(op, mode="ell")
    ef = DistributedEngine(op, n_devices=2, mode="fused")
    xj = jnp.asarray(x)
    xh = ef.to_hashed(x)
    # the apply programs record their cost profiles through the offline
    # AOT analysis path (analyze_bound_apply)
    el.apply_memory_analysis(xj)
    ef.apply_memory_analysis(xh)
    jax.block_until_ready(el.matvec(xj))
    jax.block_until_ready(ef.matvec(xh))

    # -- 1. HLO attribution at compile: exact phase sums + artifact ------
    profs = H.executable_costs()
    assert profs, "no HLO cost profiles recorded at compile time"
    programs = {p["program"] for p in profs.values()}
    assert "local_ell_apply" in programs, programs
    assert "distributed_fused_apply" in programs, programs
    for prof in profs.values():
        t = prof["totals"]
        for axis in ("bytes", "flops"):
            s = sum(row[axis] for row in prof["phases"].values())
            assert math.isclose(s, t[axis], rel_tol=0, abs_tol=0.5), \
                (f"{prof['program']}: phase {axis} sum {s} != "
                 f"whole-program {t[axis]}")
        art = prof.get("artifact")
        assert art and os.path.exists(art), \
            f"{prof['program']}: no content-addressed artifact ({art})"
        fp = prof["fingerprint"]
        assert art.endswith(os.path.join(fp[:2], fp + ".json")), art
        loaded = H.load_profile(art)
        assert loaded["fingerprint"] == fp
        assert loaded["totals"] == t, "artifact round-trip drifted"
    n_hlo_events = len(obs.events("hlo_cost"))
    assert n_hlo_events >= len(profs), "hlo_cost events missing"
    print(f"[profile-check] attribution: {len(profs)} program(s), phase "
          f"sums exact, artifacts content-addressed: OK")

    # -- 2. HLO byte-identity, DMT_PROFILE sampled vs off ----------------
    def apply_hlo(eng, xarg):
        return jax.jit(eng._apply_fn).lower(
            xarg, eng._operands).compile().as_text()

    assert P.profile_mode() == "off", "profiling should default off"
    hlo_local_off = apply_hlo(el, xj)
    hlo_dist_off = apply_hlo(ef, xh)
    os.environ["DMT_PROFILE"] = "sampled"
    assert P.profile_mode() == "sampled"
    assert apply_hlo(el, xj) == hlo_local_off, \
        "local apply HLO changed with DMT_PROFILE=sampled"
    assert apply_hlo(ef, xh) == hlo_dist_off, \
        "distributed fused apply HLO changed with DMT_PROFILE=sampled"
    print("[profile-check] HLO byte-identity (profile sampled/off): OK")

    # -- 3. sampled windows on the cadence ---------------------------------
    # 2 * every consecutive applies hold exactly two indices divisible by
    # `every`; the overhead guard can only latch AFTER the second window,
    # so the count does not depend on what the captures cost
    every = 8
    update_config(profile_every=every)
    P.reset_profile()
    for _ in range(2 * every):
        y = el.matvec(xj)
    jax.block_until_ready(y)
    snap = P.overhead_snapshot()
    assert (snap["applies"], snap["profiled"]) == (2 * every, 2), snap
    # the newest capture directory is stamped with its identity
    assert snap["last_dir"], "no sampled capture directory recorded"
    meta = os.path.join(snap["last_dir"], "PROFILE_META.json")
    assert os.path.exists(meta), f"capture dir not stamped: {meta}"
    stamp = json.load(open(meta))
    assert stamp["capture"] == "sampled" and stamp["engine"] == "local"
    print(f"[profile-check] sampling: {snap['profiled']} captures in "
          f"{snap['applies']} applies at profile_every={every}, "
          f"PROFILE_META stamped: OK")

    # -- 4. roofline third column: sum(hlo ms) == measured wall ----------
    for _ in range(4):
        yh = ef.matvec(xh)
    jax.block_until_ready(yh)
    obs.flush()
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "obs_report.py"),
         "roofline", run_dir, "--json"],
        capture_output=True, text=True)
    assert r.returncode == 0, f"obs_report roofline failed: {r.stderr}"
    grp = json.loads(r.stdout)["groups"].get("distributed/fused")
    assert grp and grp.get("hlo"), f"no hlo identity on the group: {grp}"
    assert grp["hlo"]["program"] == "distributed_fused_apply"
    hlo_sum = sum(float(a.get("hlo_ms") or 0.0)
                  for a in grp["phases"].values())
    wall = float(grp["wall_ms"])
    err = abs(hlo_sum - wall) / max(wall, 1e-9)
    assert err <= RECONCILE_TOL, \
        (f"hlo_ms sums to {hlo_sum:.4f} vs measured wall {wall:.4f} "
         f"({err:.2%} > {RECONCILE_TOL:.0%})")
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "obs_report.py"),
         "roofline", run_dir], capture_output=True, text=True)
    assert r.returncode == 0 and "hlo ms" in r.stdout \
        and "hlo:" in r.stdout, r.stdout
    print(f"[profile-check] reconciliation: sum(hlo_ms) {hlo_sum:.3f} vs "
          f"wall {wall:.3f} ms ({err:.2%} <= {RECONCILE_TOL:.0%}): OK")

    # -- 5. triggered deep capture ----------------------------------------
    bundle = obs.trigger_capture("incident")
    assert bundle and os.path.exists(bundle), \
        f"no flight bundle from the triggered capture: {bundle}"
    assert "profile_incident" in os.path.basename(bundle), bundle
    payload = json.load(open(bundle))
    hot = payload["profile"]["hlo"]
    assert any(p["program"] == "local_ell_apply" and p["top_ops"]
               for p in hot), "bundle names no hottest ops"
    trig = [e for e in obs.events("profile_captured")
            if e.get("capture") == "triggered"]
    assert trig and trig[-1]["bundle"] == bundle
    print(f"[profile-check] triggered capture -> "
          f"{os.path.basename(bundle)}: OK")

    # -- 6. differential profiling: pass, then FIRE on a 10x op ----------
    base_art = next(p["artifact"] for p in H.executable_costs().values()
                    if p["program"] == "local_ell_apply")
    diff_py = os.path.join(_REPO, "tools", "profile_diff.py")
    r = subprocess.run([sys.executable, diff_py, base_art, base_art],
                       capture_output=True, text=True)
    assert r.returncode == 0 and "no per-op regression" in r.stdout, \
        f"self-diff should pass: rc={r.returncode}\n{r.stdout}{r.stderr}"
    prof = json.load(open(base_art))
    victim = max(prof["ops"], key=lambda o: o["bytes"])
    victim["bytes"] *= 10.0
    bad_art = os.path.join(scratch, "regressed.json")
    json.dump(prof, open(bad_art, "w"))
    r = subprocess.run([sys.executable, diff_py, base_art, bad_art,
                        "--json"], capture_output=True, text=True)
    assert r.returncode == 1, \
        f"diff missed a 10x op regression: rc={r.returncode}\n{r.stdout}"
    d = json.loads(r.stdout)
    top3 = [row["name"] for row in d["regressions"][:3]]
    assert victim["name"] in top3, \
        f"10x op {victim['name']!r} not in top-3 regressions: {top3}"
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "obs_report.py"),
         "profile", run_dir], capture_output=True, text=True)
    assert r.returncode == 0, f"obs_report profile failed: {r.stderr}"
    print(f"[profile-check] diff: self-diff passes, FIRES on 10x "
          f"{victim['name']!r} (top-3): OK")

    print("[profile-check] PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
