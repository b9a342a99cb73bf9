"""Chebyshev / kernel-polynomial spectral densities (KPM) over the engines.

Everything the repo computed before this module is extremal eigenpairs;
the kernel polynomial method opens the FULL spectrum for the same matvec
cost model: the density of states (and any spectral function) is
reconstructed from Chebyshev moments ``mu_n = Tr[T_n(H~)]`` where ``H~``
is the Hamiltonian rescaled into (-1, 1), and every moment is nothing
but repeated matvec against a FIXED operator — the best-case workload
for the streamed/hybrid plan amortization (DESIGN.md §20/§23/§28): the
plan is resolved and encoded ONCE at engine build and then re-streamed
per apply for hundreds of moments.

Three pieces (DESIGN.md §29):

* :func:`spectral_bounds` — a short plain Lanczos pass (no
  reorthogonalization, no stored basis: bounds only) whose extremal
  Ritz values, widened by their residual bounds plus a safety margin,
  bracket the spectrum.  KPM diverges if any eigenvalue maps outside
  [-1, 1], so the margin is applied OUTWARD on both ends.
* :func:`kpm_moments` — the three-term recurrence
  ``t_{j+1} = 2 H~ t_j - t_{j-1}`` over a block of ``n_vectors`` seeded
  random columns in the engine's native layout (hashed ``[D, M, R]``
  for distributed engines — the moments batch through the SAME
  multi-RHS apply path ``lanczos_block`` uses, so a streamed engine
  streams each plan chunk once per moment step, not once per vector).
  Moments come in pairs per apply (the standard doubling identities
  ``mu_{2j} = 2<t_j, t_j> - mu_0``, ``mu_{2j-1} = 2<t_j, t_{j-1}> -
  mu_1``), so ``n_moments`` moments cost ~``n_moments/2`` applies.
  The stochastic-trace estimate is the column mean: for isotropic
  normalized random vectors ``E[<r|A|r>] = Tr A / N``, so the averaged
  moments are the NORMALIZED moments of a unit-mass density.
* :func:`reconstruct_dos` / :func:`jackson_kernel` /
  :func:`lorentz_kernel` — the kernel-damped Chebyshev series summed on
  an energy grid.  Jackson is the DOS default (strictly positive,
  near-Gaussian broadening ~ pi/n_moments); Lorentz suits Green's
  functions.

Solver contracts match the eigensolvers: a preemption latch checked at
moment-step boundaries (SIGTERM → checkpoint → ``Preempted`` → exit 75
from the apps), checkpoint/resume through the SAME topology-portable
machinery as the Lanczos Krylov basis (the recurrence state is two
layout vectors + the host moment table; a resume restores bit-identical
state, so resumed moment series equal uninterrupted ones exactly), and
``solve > iteration > apply`` trace spans.

Pair-mode engines (the TPU (re, im)-f64 complex form) are refused with
a pointer: the recurrence would need the J-aware projections that live
in ``lanczos()``; complex sectors run native c128 on CPU.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import memory as obs_memory
from ..obs import trace as obs_trace
from ..obs.events import emit as obs_emit, flush as obs_flush, obs_enabled
from ..utils import faults, preempt
from .lanczos import (_operator_key, _rand_like, _restore_ckpt,
                      _sharded_ckpt_engine, _soft_save_ckpt)

__all__ = ["KPMResult", "spectral_bounds", "kpm_moments", "kpm_dos",
           "kpm_spectral_function", "jackson_kernel", "lorentz_kernel",
           "reconstruct_dos", "exact_moments"]


def _refuse_pair(owner, what: str) -> None:
    if bool(getattr(owner, "pair", False)):
        raise ValueError(
            f"{what} does not support pair-mode engines (the (re, im)-f64 "
            "recurrence needs the J-aware projections that live in "
            "solve.lanczos) — run the sector native-c128 on CPU, or use "
            "a real sector")


def _mv_fn(matvec: Callable):
    """Tuple-stripping eager apply (same contract as lanczos_block)."""
    def mv(x):
        y = matvec(x)
        return y[0] if isinstance(y, tuple) else y
    return mv


def _col_dots(a, b) -> jax.Array:
    """Per-column Re<a_r, b_r> over layout axes: [R] f64.  Pad slots are
    zero by engine invariant, so the flat reduction is exact; for a
    complex-Hermitian operator the diagonal/adjacent Chebyshev products
    are real up to roundoff — the real part IS the moment."""
    R = a.shape[-1]
    af = a.reshape(-1, R)
    bf = b.reshape(-1, R)
    return jnp.real(jnp.sum(af.conj() * bf, axis=0))


def spectral_bounds(matvec: Callable, n: Optional[int] = None,
                    v0=None, iters: int = 64, seed: int = 0,
                    margin: float = 0.05) -> Tuple[float, float, int]:
    """Safe spectral bracket ``(emin, emax, n_applies)`` via a short
    Lanczos pass.

    Plain three-term recurrence, no reorthogonalization and no stored
    basis (orthogonality loss only duplicates converged extremal Ritz
    values — harmless for a bracket): ``iters`` eager applies, then the
    tridiagonal eigenvalues.  The bracket widens each end by that end's
    residual bound ``|beta_m * s_m|`` PLUS ``margin`` of the Ritz span —
    the safety margin KPM needs (a single eigenvalue outside [-1, 1]
    makes the Chebyshev recurrence diverge geometrically, so the
    conservative direction is always outward; the only cost of a loose
    bracket is mildly coarser energy resolution per moment).
    """
    from scipy.linalg import eigh_tridiagonal

    mv = _mv_fn(matvec)
    owner = getattr(matvec, "__self__", None)
    _refuse_pair(owner, "spectral_bounds")
    if v0 is None:
        if owner is not None and hasattr(owner, "random_hashed"):
            v0 = owner.random_hashed(seed)
        elif n is not None:
            v0 = _rand_like((n,), np.float64, seed)
        else:
            raise ValueError("pass v0 or n")
    v = jnp.asarray(v0)
    nrm = jnp.sqrt(jnp.real(jnp.vdot(v, v)))
    w0 = mv(v)                                   # probe fixes the dtype
    dtype = jnp.promote_types(v.dtype, w0.dtype)
    v = (v / nrm.astype(v.dtype)).astype(dtype)
    w0 = (w0 / nrm.astype(w0.dtype)).astype(dtype)
    v_prev = jnp.zeros_like(v)
    alph, bet = [], []
    napply = 0
    for j in range(max(int(iters), 2)):
        w = w0 if j == 0 else mv(v)
        napply += 0 if j == 0 else 1             # probe reused as apply 0
        w0 = None
        a = float(jnp.real(jnp.vdot(v, w)))
        w = w - a * v - (bet[-1] * v_prev if bet else 0.0)
        b = float(jnp.sqrt(jnp.real(jnp.vdot(w, w))))
        alph.append(a)
        if b <= 1e-300:                          # Krylov space closed:
            bet.append(0.0)                      # bounds are exact
            break
        bet.append(b)
        v_prev, v = v, (w / b).astype(dtype)
    napply += 1
    m = len(alph)
    theta, S = eigh_tridiagonal(np.asarray(alph), np.asarray(bet[:m - 1]))
    res_lo = abs(bet[-1] * S[m - 1, 0])
    res_hi = abs(bet[-1] * S[m - 1, -1])
    span = max(float(theta[-1] - theta[0]), 1e-12)
    emin = float(theta[0] - res_lo - margin * span)
    emax = float(theta[-1] + res_hi + margin * span)
    obs_emit("kpm_bounds", emin=emin, emax=emax, iters=int(m),
             ritz_lo=float(theta[0]), ritz_hi=float(theta[-1]),
             res_lo=float(res_lo), res_hi=float(res_hi),
             margin=float(margin))
    return emin, emax, napply


@dataclass
class KPMResult:
    moments: np.ndarray            # [n_moments] normalized mu_n (mu_0 = 1)
    moment_stderr: np.ndarray      # [n_moments] stderr over the R columns
    bounds: Tuple[float, float]    # (emin, emax) bracket actually used
    scale: Tuple[float, float]     # (a, b): H~ = (H - b)/a
    n_vectors: int
    num_applies: int               # engine applies (bounds pass included)
    resumed_from: int = 0          # moment STEPS restored from a checkpoint
    # rate bookkeeping, same convention as LanczosResult: the first
    # recurrence apply pays compile + first plan stream
    first_block_seconds: float = 0.0
    first_block_moments: int = 0
    steady_seconds: float = 0.0

    @property
    def steady_moments_per_s(self) -> float:
        rest = len(self.moments) - self.first_block_moments
        if rest > 0 and self.steady_seconds > 0:
            return rest / self.steady_seconds
        return 0.0


def kpm_moments(matvec: Callable, n_moments: int = 256,
                n: Optional[int] = None, n_vectors: int = 4,
                seed: int = 0, V0=None,
                bounds: Optional[Tuple[float, float]] = None,
                bounds_iters: int = 64, margin: float = 0.05,
                checkpoint_path: Optional[str] = None,
                checkpoint_every: int = 64,
                check_every: int = 32) -> KPMResult:
    """Solve-span wrapper over :func:`_kpm_moments_impl` (full contract
    there): the whole moment run is ONE ``solve`` span, each recurrence
    step an ``iteration`` span, eager engine applies nest as ``apply``
    spans — the tree ``obs_report trace`` exports."""
    with obs_trace.span("kpm", kind="solve", n_moments=int(n_moments)):
        return _kpm_moments_impl(
            matvec, n_moments, n=n, n_vectors=n_vectors, seed=seed, V0=V0,
            bounds=bounds, bounds_iters=bounds_iters, margin=margin,
            checkpoint_path=checkpoint_path,
            checkpoint_every=checkpoint_every, check_every=check_every)


def _kpm_moments_impl(matvec, n_moments, n=None, n_vectors=4, seed=0,
                      V0=None, bounds=None, bounds_iters=64, margin=0.05,
                      checkpoint_path=None, checkpoint_every=64,
                      check_every=32) -> KPMResult:
    """Stochastic-trace Chebyshev moments of the operator behind
    ``matvec``.

    ``V0`` (engine-layout ``[..., R]`` block of NORMALIZED columns)
    overrides the seeded random block — the spectral-function path
    passes ``O|psi>/||O|psi>||`` here.  ``bounds`` skips the Lanczos
    bracket when the caller already knows one (it is stored in the
    checkpoint, so a RESUME always reuses the original scale — the
    trajectory stays bit-consistent even if a fresh bracket pass would
    land on slightly different floats).

    Checkpoint/resume (``checkpoint_path``): every
    ``checkpoint_every``-th step the two live recurrence vectors + the
    host moment table are written through the same atomic,
    topology-portable machinery as the Lanczos Krylov basis
    (``_save_ckpt``/``_restore_ckpt``); the fingerprint bakes in the
    operator key, layout tail, dtype and the (n_moments, R, seed)
    geometry, so a rerun against an edited Hamiltonian or different
    moment plan starts fresh instead of restoring foreign state.
    """
    if int(n_moments) < 2:
        raise ValueError(f"n_moments must be >= 2, got {n_moments}")
    if V0 is None and int(n_vectors) < 1:
        # guard BEFORE random_hashed: cols=0 falls into its scalar form
        # and the recurrence would silently treat shard slots as columns
        raise ValueError(f"n_vectors must be >= 1, got {n_vectors}")
    n_moments = int(n_moments)
    mv = _mv_fn(matvec)
    owner = getattr(matvec, "__self__", None)
    _refuse_pair(owner, "kpm_moments")

    v0_given = V0 is not None
    if V0 is None:
        if owner is not None and hasattr(owner, "random_hashed"):
            V0 = owner.random_hashed(seed, cols=int(n_vectors))
        elif n is not None:
            V0 = _rand_like((n, int(n_vectors)), np.float64, seed)
            V0 = V0 / np.linalg.norm(V0, axis=0, keepdims=True)
        else:
            raise ValueError("pass V0 or n")
    V0 = jnp.asarray(V0)
    R = int(V0.shape[-1])
    shape = V0.shape

    # probe apply reused as the j=0 recurrence apply (fixes dtype, runs
    # the engine's first-apply counter validation, and is the single
    # most expensive operation here — never discard it)
    t_wall = time.perf_counter()
    y0 = mv(V0)
    napply = 1
    dtype = jnp.promote_types(V0.dtype, y0.dtype)
    t0 = V0.astype(dtype)
    first_s = time.perf_counter() - t_wall

    hashed_layout = _sharded_ckpt_engine(owner, shape)
    base = (f"hashed{tuple(shape[2:])}" if hashed_layout
            else f"{tuple(shape)}")
    ckpt_fp = (f"{base}|{np.dtype(dtype).str}|{_operator_key(owner)}"
               f"|kpm-v1|m{n_moments}|r{R}|s{int(seed)}")
    multi = jax.process_count() > 1
    sharded_ckpt = multi and hashed_layout
    if checkpoint_path and multi and not sharded_ckpt:
        from ..utils.logging import log_debug
        log_debug("kpm checkpointing disabled: multi-process run with a "
                  "non-engine matvec (no per-shard vector layout)")
        checkpoint_path = None
    # RESTORE probe before any bounds pass: a resume must reuse the
    # STORED scale (the recurrence continues in exactly the rescaling
    # it started in), so re-running the ~bounds_iters-apply Lanczos
    # bracket just to discard it would waste a third of a typical run
    resumed_from = 0
    got = None
    if checkpoint_path:
        got = _restore_ckpt(checkpoint_path, ckpt_fp, owner, shape,
                            sharded=sharded_ckpt, solver="kpm",
                            dtype=np.dtype(dtype))
    mu_cols = np.zeros((n_moments, R))
    if got is not None:
        t_lo, t_hi = (r.astype(dtype) for r in got["V_rows"][:2])
        mu_saved = np.asarray(got["mu_cols"])
        mu_cols[: mu_saved.shape[0]] = mu_saved
        j = int(got["j"])
        filled = int(got["filled"])
        resumed_from = j
        a, b = float(got["scale_a"]), float(got["scale_b"])
        emin, emax = b - a, b + a
        obs_emit("solver_resume", solver="kpm", iters=int(j),
                 moments_filled=int(filled))
    else:
        if bounds is None:
            # an explicit start block also seeds the bounds pass (its
            # first column): the spectral-function path has no `n` and
            # no random draw, and a deterministic bracket keeps reruns
            # bit-identical
            bv0 = V0[..., 0] if v0_given else None
            emin, emax, nb = spectral_bounds(
                matvec, n=n, v0=bv0, iters=bounds_iters, seed=seed + 1,
                margin=margin)
            napply += nb
        else:
            emin, emax = float(bounds[0]), float(bounds[1])
        if not emax > emin:
            raise ValueError(
                f"degenerate spectral bounds ({emin}, {emax})")
        a = (emax - emin) / 2.0
        b = (emax + emin) / 2.0
        # per-column moment table on the host; mu_0 = <r|r> = 1 exactly
        # for normalized columns, mu_1 = <r|H~|r>
        t_lo, t_hi = t0, ((y0.astype(dtype) - b * t0) / a)
        mu_cols[0] = np.asarray(_col_dots(t_lo, t_lo))
        mu_cols[1] = np.asarray(_col_dots(t_lo, t_hi))
        # j: highest recurrence index for which t_j is live in `t_hi`
        j = 1
        filled = 2
    del y0

    agree_multi = jax.process_count() > 1 and (
        owner is None or bool(getattr(owner, "_multi", True)))
    preempt.ensure_installed()
    obs_emit("solver_start", solver="kpm", n_moments=n_moments,
             n_vectors=R, emin=emin, emax=emax,
             bounds_iters=int(bounds_iters),
             resumed_from=int(resumed_from))

    mem_h = obs_memory.NULL_HANDLE
    if obs_enabled():
        mem_h = obs_memory.track(
            f"solver/{obs_memory.next_instance('kpm')}/chebyshev_pair",
            2 * int(t_lo.nbytes), n_vectors=R)

    def save_ckpt(reason):
        V = jnp.stack([t_lo, t_hi])
        _soft_save_ckpt(checkpoint_path, ckpt_fp, owner, V, {
            "mu_cols": mu_cols[:filled].copy(), "j": int(j),
            "filled": int(filled), "scale_a": float(a),
            "scale_b": float(b), "m": 1, "total_iters": int(j)},
            1, sharded_ckpt, solver="kpm", reason=reason)

    steady_s = 0.0
    # the probe apply (compile + first plan stream) is the first block;
    # every loop pass after it is steady-state.  A resumed run's
    # restored moments cost THIS run nothing — they count as "first
    # block" so the steady rate divides only work actually done here
    first_moments = 2 if resumed_from == 0 else filled
    # each loop pass: harvest the doubling pair for the CURRENT t_j,
    # then advance the recurrence by one apply
    while filled < n_moments:
        faults.check("solver_block", exc=RuntimeError, solver="kpm",
                     iter=int(j))
        preempted = preempt.agreed(agree_multi)
        if preempted:
            if checkpoint_path:
                save_ckpt("preempt")
            obs_emit("solver_preempted", solver="kpm", iters=int(j),
                     checkpoint=checkpoint_path or "")
            obs_flush()
            mem_h.release()
            raise preempt.Preempted("kpm", j, checkpoint_path)
        t_step = time.perf_counter()
        with obs_trace.span("iteration", kind="iteration", solver="kpm",
                            iter=int(j)):
            # doubling identities at index j (t_lo = t_{j-1}, t_hi = t_j)
            if 2 * j - 1 < n_moments and 2 * j - 1 >= filled:
                mu_cols[2 * j - 1] = \
                    2.0 * np.asarray(_col_dots(t_hi, t_lo)) - mu_cols[1]
                filled += 1
            if 2 * j < n_moments and 2 * j >= filled:
                mu_cols[2 * j] = \
                    2.0 * np.asarray(_col_dots(t_hi, t_hi)) - mu_cols[0]
                filled += 1
            if filled < n_moments:
                y = mv(t_hi).astype(dtype)
                napply += 1
                t_lo, t_hi = t_hi, (2.0 / a) * y - (2.0 * b / a) * t_hi \
                    - t_lo
                jax.block_until_ready(t_hi)
                j += 1
        steady_s += time.perf_counter() - t_step
        if checkpoint_path and j % max(int(checkpoint_every), 1) == 0:
            save_ckpt("cadence")
        if obs_enabled() and j % max(int(check_every), 1) == 0:
            obs_emit("kpm_trace", solver="kpm", iter=int(j),
                     filled=int(filled),
                     mu_last=float(np.mean(mu_cols[max(filled - 1, 0)])))

    mu = mu_cols.mean(axis=1)
    stderr = (mu_cols.std(axis=1) / np.sqrt(max(R, 1))
              if R > 1 else np.zeros(n_moments))
    obs_emit("solver_end", solver="kpm", iters=int(j),
             converged=True, n_moments=int(n_moments),
             num_applies=int(napply))
    mem_h.release()
    return KPMResult(
        moments=mu, moment_stderr=stderr, bounds=(emin, emax),
        scale=(a, b), n_vectors=R, num_applies=napply,
        resumed_from=resumed_from,
        first_block_seconds=first_s,
        first_block_moments=first_moments,
        steady_seconds=steady_s)


# -- kernels and reconstruction -------------------------------------------

def jackson_kernel(n_moments: int) -> np.ndarray:
    """Jackson damping ``g_n`` — the DOS default: the reconstructed
    density is strictly positive and each delta broadens to a
    near-Gaussian of width ~ pi * a / n_moments (Weisse et al.,
    Rev. Mod. Phys. 78, 275 (2006), Eq. 71)."""
    N = int(n_moments)
    nn = np.arange(N)
    q = np.pi / (N + 1)
    return ((N - nn + 1) * np.cos(q * nn)
            + np.sin(q * nn) / np.tan(q)) / (N + 1)


def lorentz_kernel(n_moments: int, lam: float = 4.0) -> np.ndarray:
    """Lorentz damping — delta functions broaden to Lorentzians (the
    right shape for Green's-function resolvents); ``lam`` trades
    resolution (small) against damping (large)."""
    N = int(n_moments)
    nn = np.arange(N)
    return np.sinh(lam * (1.0 - nn / N)) / np.sinh(lam)


def _kernel(name: str, n_moments: int, lam: float) -> np.ndarray:
    if name == "jackson":
        return jackson_kernel(n_moments)
    if name == "lorentz":
        return lorentz_kernel(n_moments, lam)
    if name in (None, "none"):
        return np.ones(int(n_moments))
    raise ValueError(f"unknown KPM kernel {name!r} "
                     "(use jackson | lorentz | none)")


def reconstruct_dos(moments: np.ndarray, scale: Tuple[float, float],
                    energies: Optional[np.ndarray] = None,
                    npoints: int = 512, kernel: str = "jackson",
                    lam: float = 4.0) -> Tuple[np.ndarray, np.ndarray]:
    """Kernel-damped Chebyshev series → density on an energy grid.

    ``rho(E) = (1 / (pi a sqrt(1 - x^2))) * [g_0 mu_0 + 2 sum_n g_n
    mu_n T_n(x)]`` with ``x = (E - b)/a``.  The default grid is the
    Chebyshev-node grid ``x_k = cos(pi (k + 1/2) / K)`` (uniform
    resolution in the angle variable — the grid KPM results are usually
    quoted on); pass ``energies`` for an explicit grid, which is clipped
    strictly inside the bracket so the ``1/sqrt(1-x^2)`` weight stays
    finite.  Normalized moments (``mu_0 = 1``) integrate to unit mass.
    """
    a, b = float(scale[0]), float(scale[1])
    mu = np.asarray(moments, np.float64)
    N = mu.shape[0]
    g = _kernel(kernel, N, lam)
    coeff = g * mu
    coeff[1:] *= 2.0
    if energies is None:
        k = np.arange(int(npoints))
        x = np.cos(np.pi * (k + 0.5) / int(npoints))[::-1]
    else:
        x = np.clip((np.asarray(energies, np.float64) - b) / a,
                    -1.0 + 1e-12, 1.0 - 1e-12)
    rho_x = np.polynomial.chebyshev.chebval(x, coeff) \
        / (np.pi * np.sqrt(1.0 - x * x))
    return a * x + b, rho_x / a


def exact_moments(eigenvalues, scale: Tuple[float, float],
                  n_moments: int) -> np.ndarray:
    """Normalized Chebyshev moments of a KNOWN spectrum — the reference
    side of broadening-aware DOS comparisons: push these through
    :func:`reconstruct_dos` with the SAME kernel as the stochastic
    moments and the residual is pure trace noise, never resolution
    mismatch (used by ``make dynamics-check``)."""
    a, b = float(scale[0]), float(scale[1])
    ang = np.arccos(np.clip(
        (np.asarray(eigenvalues, np.float64) - b) / a, -1.0, 1.0))
    return np.array([np.mean(np.cos(k * ang))
                     for k in range(int(n_moments))])


def kpm_dos(matvec: Callable, n_moments: int = 256,
            n: Optional[int] = None, n_vectors: int = 4, seed: int = 0,
            npoints: int = 512, kernel: str = "jackson", lam: float = 4.0,
            bounds: Optional[Tuple[float, float]] = None,
            bounds_iters: int = 64, margin: float = 0.05,
            checkpoint_path: Optional[str] = None,
            checkpoint_every: int = 64):
    """Density of states in one call: moments + reconstruction.
    Returns ``(energies, rho, KPMResult)`` — ``rho`` integrates to 1
    (per-state density; multiply by ``n_states`` for a count density).
    """
    res = kpm_moments(matvec, n_moments, n=n, n_vectors=n_vectors,
                      seed=seed, bounds=bounds, bounds_iters=bounds_iters,
                      margin=margin, checkpoint_path=checkpoint_path,
                      checkpoint_every=checkpoint_every)
    energies, rho = reconstruct_dos(res.moments, res.scale,
                                    npoints=npoints, kernel=kernel,
                                    lam=lam)
    return energies, rho, res


def kpm_spectral_function(matvec: Callable, psi, op_apply: Callable,
                          n_moments: int = 256, npoints: int = 512,
                          kernel: str = "jackson", lam: float = 4.0,
                          bounds: Optional[Tuple[float, float]] = None,
                          bounds_iters: int = 64, margin: float = 0.05):
    """Dynamical structure factor ``S(E) = <psi|O† delta(E - H) O|psi>``.

    ``op_apply`` applies the (bound) observable O in the solve engine's
    layout (``models/observables.bind_observables`` produces exactly
    such engines sharing the basis artifacts).  The moments are the
    single-vector Chebyshev moments of ``phi = O|psi>`` — the same
    recurrence, start block ``phi/||phi||``, with the density weighted
    by ``||phi||^2``.  Returns ``(energies, S, KPMResult, weight)``.
    """
    phi = op_apply(psi)
    phi = phi[0] if isinstance(phi, tuple) else phi
    phi = jnp.asarray(phi)
    w2 = float(jnp.real(jnp.vdot(phi, phi)))
    if w2 <= 0.0:
        raise ValueError("O|psi> vanishes: no spectral weight")
    V0 = (phi / np.sqrt(w2))[..., None]
    res = kpm_moments(matvec, n_moments, V0=V0, bounds=bounds,
                      bounds_iters=bounds_iters, margin=margin)
    energies, rho = reconstruct_dos(res.moments, res.scale,
                                    npoints=npoints, kernel=kernel,
                                    lam=lam)
    return energies, w2 * rho, res, w2
