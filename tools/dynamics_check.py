#!/usr/bin/env python
"""dynamics-check — CI gate for the dynamics subsystem (`make
dynamics-check`, DESIGN.md §29).

Asserts, on the CPU rig (~25 s):

1. **KPM vs dense** — Chebyshev moments on a chain_12 STREAMED engine
   match the dense matrix's own recurrence on the same seeded block at
   1e-12, the Jackson-kernel DOS matches the exact spectrum pushed
   through the SAME kernel within the stochastic-trace tolerance, and
   the engine's plan is provably built ONCE for the whole run
   (``engine_init`` counted once across the bounds pass and every
   moment apply).
2. **Evolve unitarity + dense parity** — ``exp(-iHt)`` on chain_12
   matches dense ``expm`` at rtol 1e-10 with norm drift < 1e-12 per
   accepted step.
3. **Thick-restart parity** — the ``max_basis_size``-capped
   ``lanczos_block`` reaches the full-memory solve's E0 at rtol 1e-12
   with every restart event inside the configured cap.
4. **SIGTERM mid-evolution** — an ``apps/dynamics.py --solver evolve``
   run slowed via the PR 6 fault registry is SIGTERMed mid-trajectory:
   exit 75, and the relaunch (same argv) resumes from the checkpoint
   and lands a trajectory matching the uninterrupted run at rtol 1e-12
   (times bit-equal — the §29 bit-consistency acceptance).
"""

import os
import signal
import subprocess
import sys
import tempfile
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_X64"] = "true"
os.environ.setdefault("DMT_ARTIFACT_CACHE", "off")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import numpy as np  # noqa: E402

_YAML = """\
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


def _log(msg):
    print(f"[dynamics-check] {msg}", flush=True)


def _fail(msg):
    print(f"[dynamics-check] FAIL: {msg}", flush=True)
    return 1


def _build_chain12():
    from distributed_matvec_tpu.models.basis import SpinBasis
    from distributed_matvec_tpu.models.lattices import (chain_edges,
                                                        heisenberg_from_edges)
    basis = SpinBasis(12, 6, 1, [([*range(1, 12), 0], 0)])
    basis.build()
    return heisenberg_from_edges(basis, chain_edges(12))


def _dense(op, n, block=64):
    """Dense H via batched identity applies through a local ell engine
    (an independent APPLY path from the streamed engine under test)."""
    import jax.numpy as jnp

    from distributed_matvec_tpu.parallel.engine import LocalEngine

    leng = LocalEngine(op)
    H = np.empty((n, n))
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        I = np.zeros((n, hi - lo))
        I[np.arange(lo, hi), np.arange(hi - lo)] = 1.0
        H[:, lo:hi] = np.asarray(leng.matvec(jnp.asarray(I))).real
    return (H + H.T) / 2


def leg_kpm(op, h, eng):
    from distributed_matvec_tpu import obs
    from distributed_matvec_tpu.solve import kpm_moments, reconstruct_dos

    n = h.shape[0]
    obs.reset()
    res = kpm_moments(eng.matvec, n_moments=96, n_vectors=4, seed=2)
    inits = [e for e in obs.events("engine_init")]
    if len(inits) != 0:
        return _fail(f"{len(inits)} engine builds INSIDE the kpm run — "
                     "the warm plan must be reused across all moments")
    # same-vector dense recurrence (kpm draws per-shard via
    # random_hashed on the 1-device mesh == the flat global draw)
    a, b = res.scale
    V0h = eng.random_hashed(2, cols=4)
    V0 = np.stack([eng.from_hashed(np.asarray(V0h)[..., i])
                   for i in range(4)], axis=1)
    Ht = (h - b * np.eye(n)) / a
    t0, t1 = V0, Ht @ V0
    mu = np.zeros((96, 4))
    mu[0] = (t0 * t0).sum(0)
    mu[1] = (t0 * t1).sum(0)
    j, filled = 1, 2
    while filled < 96:
        if 2 * j - 1 >= filled:
            mu[2 * j - 1] = 2 * (t1 * t0).sum(0) - mu[1]
            filled += 1
        if 2 * j < 96 and 2 * j >= filled:
            mu[2 * j] = 2 * (t1 * t1).sum(0) - mu[0]
            filled += 1
        if filled < 96:
            t0, t1 = t1, 2 * Ht @ t1 - t0
            j += 1
    err = np.abs(res.moments - mu.mean(1)).max()
    if err > 1e-12:
        return _fail(f"streamed KPM moments off the dense recurrence by "
                     f"{err:.2e} (> 1e-12)")
    # broadening-aware DOS: exact spectrum through the SAME kernel
    from distributed_matvec_tpu.solve import exact_moments
    w = np.linalg.eigvalsh(h)
    mu_exact = exact_moments(w, res.scale, 96)
    _, rho = reconstruct_dos(res.moments, res.scale, npoints=512)
    _, rho_ref = reconstruct_dos(mu_exact, res.scale, npoints=512)
    rel = float(np.linalg.norm(rho - rho_ref) / np.linalg.norm(rho_ref))
    if rel > 0.35:
        return _fail(f"KPM DOS vs dense spectrum rel err {rel:.3f} "
                     "(> 0.35 — beyond the R=4 stochastic tolerance)")
    _log(f"kpm: moments at {err:.1e} vs dense, DOS rel err {rel:.3f}, "
         "plan built once")
    return 0


def leg_evolve(op, h, eng):
    from scipy.linalg import expm

    from distributed_matvec_tpu.solve import krylov_evolve
    from distributed_matvec_tpu.solve.lanczos import _rand_like

    n = h.shape[0]
    psi0 = _rand_like((n,), np.float64, 7)
    psi0 /= np.linalg.norm(psi0)
    res = krylov_evolve(eng.matvec, psi0=eng.to_hashed(psi0),
                        t_final=2.0, tol=1e-12, krylov_dim=20)
    drift_per_step = res.norm_drift / max(res.num_steps, 1)
    if drift_per_step >= 1e-12:
        return _fail(f"evolve unitarity drift {drift_per_step:.2e}/step "
                     "(>= 1e-12)")
    ref = expm(-2.0j * h) @ psi0
    got = eng.from_hashed(np.asarray(res.psi))
    err = np.abs(got - ref).max() / np.abs(ref).max()
    if err > 1e-10:
        return _fail(f"evolve vs dense expm rel err {err:.2e} (> 1e-10)")
    _log(f"evolve: {res.num_steps} steps, expm parity {err:.1e}, "
         f"norm drift {drift_per_step:.1e}/step")
    return 0


def leg_thick_restart(op, h, eng):
    from distributed_matvec_tpu import obs
    from distributed_matvec_tpu.solve import lanczos_block

    obs.reset()
    full = lanczos_block(eng.matvec, k=1, tol=1e-13, max_iters=260,
                         seed=3)
    thick = lanczos_block(eng.matvec, k=1, tol=1e-13, max_iters=600,
                          seed=3, max_basis_size=16)
    if not thick.converged or thick.restarts < 1:
        return _fail(f"capped solve: converged={thick.converged}, "
                     f"restarts={thick.restarts}")
    evs = [e for e in obs.events("solver_restart_thick")]
    if any(e["basis_size"] > e["cap"] for e in evs):
        return _fail("a thick restart fired ABOVE the configured cap")
    rel = abs(thick.eigenvalues[0] - full.eigenvalues[0]) \
        / abs(full.eigenvalues[0])
    if rel > 1e-12:
        return _fail(f"thick-restart E0 off full-memory E0 by {rel:.2e} "
                     "(> 1e-12)")
    _log(f"thick restart: E0 parity {rel:.1e} over {thick.restarts} "
         f"restarts, workspace <= 16 columns")
    return 0


def leg_sigterm_evolve(scratch):
    """SIGTERM mid-evolution -> exit 75 -> resumed trajectory matches
    the uninterrupted one at rtol 1e-12 (times bit-equal)."""
    import h5py

    yaml_path = os.path.join(scratch, "chain12.yaml")
    with open(yaml_path, "w") as f:
        f.write(_YAML)

    def run(tag, fault=None, wait=True):
        args = [sys.executable, os.path.join(_REPO, "apps", "dynamics.py"),
                yaml_path, "--solver", "evolve", "--t-final", "2.0",
                "--krylov-dim", "16", "--tol", "1e-12", "--mode", "ell",
                "-o", os.path.join(scratch, f"{tag}.h5"),
                "--checkpoint", os.path.join(scratch, f"ck_{tag}.h5"),
                "--checkpoint-every", "1",
                "--obs-dir", os.path.join(scratch, f"obs_{tag}")]
        env = dict(os.environ)
        env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
        env.pop("DMT_FAULT", None)
        if fault:
            env["DMT_FAULT"] = fault
        p = subprocess.Popen(args, env=env, stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT, text=True)
        if not wait:
            return p
        out, _ = p.communicate(timeout=300)
        return p.returncode, out

    rc, out = run("base")
    if rc != 0:
        return _fail(f"baseline evolve exited {rc}:\n{out[-2000:]}")
    # stretch each accepted step by 400 ms so the SIGTERM lands
    # mid-trajectory deterministically
    p = run("term", fault="solver_block:delay=400:n=10000", wait=False)
    time.sleep(8)
    p.send_signal(signal.SIGTERM)
    out, _ = p.communicate(timeout=120)
    if p.returncode != 75:
        return _fail(f"SIGTERMed evolve exited {p.returncode}, want 75:"
                     f"\n{out[-2000:]}")
    rc, out = run("term")                      # SAME argv resumes
    if rc != 0:
        return _fail(f"resume exited {rc}:\n{out[-2000:]}")
    if "resumed from" not in out:
        return _fail(f"relaunch did not resume:\n{out[-800:]}")
    with h5py.File(os.path.join(scratch, "base.h5"), "r") as f:
        t_base = f["evolve/times"][...]
        e_base = f["evolve/energies"][...]
    with h5py.File(os.path.join(scratch, "term.h5"), "r") as f:
        t_term = f["evolve/times"][...]
        e_term = f["evolve/energies"][...]
    if not np.array_equal(t_base, t_term):
        return _fail("resumed trajectory took DIFFERENT steps than the "
                     "uninterrupted run")
    rel = np.abs(e_base - e_term).max() / max(np.abs(e_base).max(), 1e-300)
    if rel > 1e-12:
        return _fail(f"resumed energies off uninterrupted by {rel:.2e} "
                     "(> 1e-12)")
    _log("sigterm: exit 75 mid-trajectory, resumed run matches "
         f"uninterrupted (energy parity {rel:.1e}, steps bit-equal)")
    return 0


def main() -> int:
    t0 = time.time()
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine

    op = _build_chain12()
    h = _dense(op, op.basis.number_states)
    eng = DistributedEngine(op, n_devices=1, mode="streamed")
    with tempfile.TemporaryDirectory(prefix="dmt_dyn_check_") as scratch:
        for leg in (lambda: leg_kpm(op, h, eng),
                    lambda: leg_evolve(op, h, eng),
                    lambda: leg_thick_restart(op, h, eng),
                    lambda: leg_sigterm_evolve(scratch)):
            rc = leg()
            if rc:
                return rc
    _log(f"OK ({time.time() - t0:.0f}s): KPM vs dense + plan built once, "
         "evolve unitarity + expm parity, thick-restart parity, SIGTERM "
         "75 -> bit-consistent resume")
    return 0


if __name__ == "__main__":
    sys.exit(main())
