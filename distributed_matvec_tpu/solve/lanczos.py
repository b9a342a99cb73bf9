"""Thick-restart Lanczos eigensolver over a matvec closure.

The reference drives PRIMME (block Davidson/JDQMR — ``src/PRIMME.chpl``,
``src/Diagonalize.chpl:258-332``) through three callbacks: the distributed
matvec, a global sum, and a broadcast (``PRIMME.chpl:267-373``).  PRIMME is a
native C/Fortran library we don't vendor; the TPU-native replacement is a
**device-resident** thick-restart Lanczos:

* The Krylov basis lives in a fixed ``[m_cap+1, ...]`` device buffer and a
  whole *block* of iterations (matvec, two passes of blocked modified
  Gram-Schmidt as MXU matmuls, the (α, β) recurrence) runs as ONE jitted
  program (``lax.fori_loop``) with donated buffers — the host only syncs the
  small (α, β) arrays every ``check_every`` steps for the convergence test,
  so the device never waits on a per-iteration host round-trip.
* The buffer is born once, as the output of one program
  (:func:`krylov_buffer`: zeros with the normalised start vector in row 0),
  on the start vector's device or sharded over its mesh as the vector is,
  and from then on only ever donated: to the block programs, to the thick
  restart, to the setter that writes a checkpoint's rows.  Nothing in this
  module updates an array of the buffer's shape eagerly, so no second
  array of its size is ever live between programs.
* Memory is bounded by **thick restarting** (the TRLan scheme): when the
  basis hits ``max_basis_size`` (the analog of the reference's
  ``kMaxBasisSize``, Diagonalize.chpl:169), the ``min_restart_size`` lowest
  Ritz vectors are kept (one [l, m]·[m, N] matmul on the MXU) together with
  the last residual vector; the projected matrix becomes
  arrowhead-plus-tridiagonal and the recurrence continues.
* For the distributed engine the vectors are hash-sharded ``[D, M]`` arrays;
  every inner product XLA emits is a psum over ICI — exactly the
  ``globalSumReal`` semantics (PRIMME.chpl:267-311).

Works with *any* dense vector layout: vectors are whatever ``matvec``
consumes/produces (``[N]`` for LocalEngine, ``[D, M]`` hashed for
DistributedEngine; padded slots are zero by engine invariant so dots are
exact).
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec
from scipy.linalg import eigh

from ..obs import health as obs_health
from ..obs import memory as obs_memory
from ..obs import trace as obs_trace
from ..obs.events import emit as obs_emit, flush as obs_flush, obs_enabled
from ..utils import faults, preempt

__all__ = ["LanczosResult", "krylov_buffer", "lanczos", "lanczos_block"]


def _emit_trace(solver: str, it: int, m: int, theta, res,
                omega: Optional[float] = None) -> None:
    """One per-convergence-check telemetry event: the current lowest Ritz
    values and their residual bounds (plus the ω orthogonality-loss
    estimate when the health layer computed one) — a stalled eigensolve is
    diagnosable from the event log alone (``obs_report summarize`` turns
    these into convergence plot data).  ``theta``/``res`` are small host
    arrays already; no device fetch happens here."""
    if not obs_enabled():
        return
    obs_emit("lanczos_trace", solver=solver, iter=int(it), basis_size=int(m),
             ritz=[float(t) for t in np.atleast_1d(theta)],
             residual=[float(r) for r in np.atleast_1d(res)],
             **({} if omega is None else {"omega": float(omega)}))


class _Watchdog:
    """Per-solve health state: Ritz-stagnation tracking plus the ω and
    breakdown checks, reported as ``solver_health`` events through
    :mod:`obs.health` (warn = log only; critical = one ``[Warn]`` line,
    or a :class:`~obs.health.HealthError` under ``DMT_HEALTH=strict``)."""

    #: consecutive convergence checks without a ≥1% residual improvement
    #: before a stagnation warning (restart plateaus are normal — one flat
    #: check is not a stall)
    STALL_CHECKS = 5

    def __init__(self, solver: str):
        self.solver = solver
        self.best_res = np.inf
        self.stalled = 0

    def report_omega(self, omega: Optional[float], it: int) -> None:
        """Threshold a precomputed ω estimate.  Called only on checks that
        did NOT converge: a converged check's estimate still rides the
        trace event, but a solve that just met its tolerance must not be
        failed (strict mode) by the same tiny β that delivered it."""
        if omega is None or not obs_health.probes_enabled():
            return
        if omega >= obs_health.OMEGA_CRITICAL:
            obs_health.record("orthogonality_loss", "critical",
                              solver=self.solver, iter=int(it),
                              omega=float(omega))
        elif omega >= obs_health.OMEGA_WARN:
            obs_health.record("orthogonality_loss", "warn",
                              solver=self.solver, iter=int(it),
                              omega=float(omega))

    def check_stagnation(self, res, it: int) -> None:
        if not obs_health.probes_enabled():
            return
        cur = float(np.max(np.atleast_1d(res)))
        if not np.isfinite(cur):
            obs_health.record("nonfinite_residual", "critical",
                              solver=self.solver, iter=int(it), residual=cur)
            return
        if cur < 0.99 * self.best_res:
            self.best_res = cur
            self.stalled = 0
            return
        self.stalled += 1
        if self.stalled >= self.STALL_CHECKS:
            obs_health.record("ritz_stagnation", "warn", solver=self.solver,
                              iter=int(it), residual=cur,
                              checks_without_progress=self.stalled)
            self.stalled = 0

    def breakdown(self, it: int, beta: float, converged: bool) -> None:
        """β-breakdown: the Krylov space closed.  Converged closure is the
        happy path (exact invariant subspace — no event); an UNCONVERGED
        breakdown means the solve cannot reach the tolerance and is
        critical."""
        if not obs_health.probes_enabled() or converged:
            return
        obs_health.record("beta_breakdown", "critical", solver=self.solver,
                          iter=int(it), beta=float(beta))

# Row-block size for the blocked Gram-Schmidt sweeps: live basis rows are
# visited in blocks of this many rows so the sweep cost scales with the
# *current* basis size m, not the buffer capacity.
_GS_BLOCK = 8


def _omega_row(xp, w, wp, alph, bet, j, eps):
    """Row j+1 of the ω table (Paige's recurrence; ω_{j,i} ≈ ⟨v_j, v_i⟩)
    from rows j (``w``) and j−1 (``wp``) and the recurrence's (α, β) through
    step j, and the largest |ω_{j+1,i}| over i < j, the estimate the √ε gate
    reads.  Rows are padded to one fixed length (``mcap + 1``; entries past
    the diagonal are zero), so that this one function is the host's step
    (``xp`` = numpy, in :class:`_OmegaTracker`) and the device's (``xp`` =
    ``jax.numpy`` with a traced ``j``, in the window program): they decide
    the same crossing from the same arithmetic.

    SIGNED arithmetic, exactly the Paige recurrence — an absolute-value
    upper bound compounds ~(Σβ)/β per step and saturates √ε within one
    16-step block, forcing a full sweep every other block (measured:
    the whole selective win evaporates); the signed form keeps the
    cancellation that makes real loss grow only as Ritz pairs converge.
    """
    zero = xp.zeros(1, w.dtype)
    a = xp.concatenate([alph, zero])
    b = xp.concatenate([bet, zero])
    i = xp.arange(w.shape[0])
    bj = xp.maximum(b[j], 1e-300)
    up = b * xp.concatenate([w[1:], zero])            # β_i·ω_{j,i+1}
    mid = (a - a[j]) * w
    dn = xp.concatenate([zero, (b * w)[:-1]])         # β_{i−1}·ω_{j,i−1}
    back = b[j - 1] * wp
    # ϑ ≈ ε(β_i + β_j): the local roundoff injected per step
    row = (up + mid + dn - back + eps * (b + bj)) / bj
    # fresh adjacent pair (ψ term) at i = j, the diagonal at j + 1
    new = xp.where(i < j, row, xp.where(
        i == j, eps, xp.where(i == j + 1, 1.0, 0.0)))
    return new, xp.max(xp.where(i < j, xp.abs(row), 0.0))


class _OmegaTracker:
    """Accumulated ω-recurrence (Paige/Simon) across selective-reorth blocks.

    Unlike :func:`~..obs.health.omega_estimate` — which assumes a full MGS
    pass resets the ω table every step and therefore reports only one-step
    amplification — this tracks the full table ω_{j,i} ≈ |⟨v_j, v_i⟩|
    across iterations that ran with WINDOW-only reorthogonalization, so the
    host loop can escalate to a full sweep *before* semiorthogonality
    (max ω ≤ √ε, Simon '84) is lost.  A full-reorth block (or a thick
    restart, which rebuilds the basis from Ritz combinations) resets the
    table to roundoff via :meth:`reset`.

    The host's table is the authority on which steps are kept; the window
    program evolves a copy of the two rows (:func:`_omega_row`) only to
    know when to stop working.
    """

    def __init__(self, mcap: int, eps: float = 2.0 ** -52):
        self.eps = eps
        self.rows = int(mcap) + 1
        self.limit = obs_health.OMEGA_WARN     # √ε — Simon's bound
        self.m = 0
        self.w_curr = self._roundoff_row(0)
        self.w_prev = self._roundoff_row(-1)

    @property
    def state(self):
        """What the window program carries: the two rows, and the ε and the
        limit it evolves and reads them with."""
        return self.w_curr, self.w_prev, self.eps, self.limit

    def reset(self, m: int) -> None:
        """The basis was just (re)orthogonalized by full sweeps up to row
        ``m``: row m restarts at roundoff, and so does row m−1 — unless
        only ONE vector was swept since the row this tracker stands at.
        Simon's scheme needs two consecutive fully reorthogonalized vectors
        before the table may be called roundoff (the recurrence reads both
        rows), so after a single full-sweep step the true row m−1 stays."""
        prev = self.w_curr if int(m) == self.m + 1 \
            else self._roundoff_row(int(m) - 1)
        self.m = int(m)
        # w_curr[i] = ω_{m,i} for i <= m (1 on the diagonal); w_prev the
        # m-1 row.  Baseline ε: the basis was just (re)orthogonalized.
        # w_prev's own diagonal (ω_{m-1,m-1} = 1) matters: the recurrence's
        # −β_{j−1}·ω_{j−1,i} term must cancel the β_{i}·ω_{j,i+1} term at
        # i = j−1, and an ε there instead of 1 leaves an O(β/β) ~ O(1)
        # residue that falsely trips the √ε gate on the first window block
        # after every full sweep.
        self.w_curr = self._roundoff_row(self.m)
        self.w_prev = prev

    def _roundoff_row(self, m: int) -> np.ndarray:
        row = np.zeros(self.rows)
        if m >= 0:
            row[:m] = self.eps
            row[m] = 1.0
        return row

    def advance(self, alph: np.ndarray, bet: np.ndarray, m_new: int
                ) -> float:
        """Evolve the table step by step through ``self.m .. m_new-1`` over
        the recorded (α, β) and STOP before the first step whose row
        reaches the limit: that step's new vector is not semiorthogonal,
        the ones before it are.  ``self.m`` then stands at that step (at
        ``m_new`` when none crossed); returned is the largest estimate
        seen, the crossing row's included."""
        a = np.asarray(alph, np.float64)
        b = np.asarray(bet, np.float64)
        worst = 0.0
        for j in range(self.m, int(m_new)):
            new, row_max = _omega_row(np, self.w_curr, self.w_prev, a, b, j,
                                      self.eps)
            worst = max(worst, float(row_max))
            if not row_max < self.limit:
                break
            self.w_prev, self.w_curr = self.w_curr, new
            self.m = j + 1
        return worst


@dataclass
class LanczosResult:
    eigenvalues: np.ndarray          # [k] ascending
    eigenvectors: Optional[list]     # k vectors in the matvec's layout
    residual_norms: np.ndarray       # [k] |β_m · s_last| bound
    num_iters: int
    converged: bool
    resumed_from: int = 0            # iterations restored from a checkpoint
    #: thick (memory-bounding) restarts taken when the basis hit its cap
    #: (``lanczos_block``'s narrowing restarts not counted)
    restarts: int = 0
    # steady-state rate bookkeeping: the first block pays jit compile, so
    # iters/sec is (num_iters - first_block_iters) / steady_seconds
    first_block_seconds: float = 0.0
    first_block_iters: int = 0
    steady_seconds: float = 0.0
    #: per-target results of a ``column_targets`` batch solve (the solve
    #: service's heterogeneous-convergence path), aligned with the
    #: targets list; None for ordinary solves
    column_results: Optional[list] = None

    @property
    def steady_iters_per_s(self) -> float:
        """Iteration rate excluding the compile-bearing first block; 0.0 when
        the solve finished inside the first block (no steady data)."""
        rest = self.num_iters - self.first_block_iters
        if rest > 0 and self.steady_seconds > 0:
            return rest / self.steady_seconds
        return 0.0


def _operator_key(owner) -> str:
    """Hash of the (basis, operator) pair behind an engine's matvec, used to
    key mid-solve checkpoints.  Delegates to the engines' shared
    ``hash_basis_operator`` with ``include_arrays=False`` (basis JSON +
    nonbranching term tables — everything that determines H as a matrix —
    but not the representative arrays, so shard-native engines whose basis
    is never materialized globally get the same key as a global build of
    the same problem).  Returns ``"bare"`` for non-engine callables."""
    op = getattr(owner, "operator", None)
    if op is None:
        return "bare"
    import hashlib

    from ..parallel.engine import hash_basis_operator

    h = hashlib.sha256()
    hash_basis_operator(h, op, include_arrays=False)
    return h.hexdigest()[:16]


def _sharded_ckpt_engine(owner, shape) -> bool:
    """True when the matvec's owner is a distributed engine whose hashed
    [D, M(, 2)] vector layout matches ``shape`` — the case where a
    multi-process checkpoint can be written per shard (each rank saves its
    addressable shards; no rank ever fetches the global Krylov basis).
    The capability probe is ``reshard.hashed_ckpt_engine`` — the SAME
    predicate that decides whether a save gets the topology stanza, so
    layout detection and stanza writing can never disagree."""
    from ..parallel.reshard import hashed_ckpt_engine
    return (hashed_ckpt_engine(owner)
            and len(shape) >= 2
            and shape[0] == owner.n_devices
            and shape[1] == owner.shard_size)


def _save_ckpt(path, fp, owner, V, meta, m, sharded) -> None:
    """One checkpoint write.  Single-controller: the live basis rows in one
    structure file (global array).  Multi-process engine-backed: each rank
    writes its shards of every Krylov row plus the (replicated) recurrence
    metadata in ONE atomic per-rank file — metadata and rows can never be
    of mixed generations, and a crash mid-save leaves the previous
    checkpoint intact.

    Engine-backed saves add the v2 TOPOLOGY STANZA (D, shard size,
    per-shard counts, partition fingerprint — ``parallel/reshard.py``) to
    the metadata, so a restore on a different device count reshards the
    snapshot instead of refusing it."""
    from ..parallel.reshard import topology_stanza
    meta = dict(meta, **topology_stanza(owner))
    if not sharded:
        from ..io.hdf5 import save_engine_structure
        save_engine_structure(path, fp, "lanczos",
                              dict(meta, V=np.asarray(V[: m + 1])))
        return
    from ..io.sharded_io import save_hashed_vectors
    from ..parallel.mesh import shard_spec

    spec = shard_spec(owner.mesh, V.ndim - 1)
    row = jax.jit(lambda Vb, i: Vb[i], out_shardings=spec)
    # one device row in flight at a time (a whole-basis dict of device
    # rows would transiently double HBM right at the basis-size cap);
    # host staging is this rank's shards only
    rows = {}
    for i in range(m + 1):
        r = row(V, jnp.int32(i))
        rows[f"krylov_{i}"] = {
            piece.index[0].start: np.asarray(piece.data)[0]
            for piece in r.addressable_shards}
        del r
    save_hashed_vectors(path, rows, owner.counts,
                        meta=dict(meta, fingerprint=fp))


def _soft_save_ckpt(path, fp, owner, V, meta, m, sharded,
                    solver: str = "lanczos", reason: str = "cadence") -> bool:
    """A checkpoint write that cannot kill the solve it protects: failures
    (full disk, read-only checkout, injected ``ckpt_write``/``ckpt_rename``
    faults) degrade to one ``log_warn`` plus a
    ``solver_checkpoint{status=failed}`` event — a run hundreds of
    iterations deep keeps going and tries again at the next cadence.
    Success emits the ``solver_checkpoint`` event the chaos gate and a
    post-mortem read to locate the last good generation."""
    try:
        _save_ckpt(path, fp, owner, V, meta, m, sharded)
    except OSError as e:
        from ..utils.logging import log_warn
        log_warn(f"{solver} checkpoint save failed ({e!r}); "
                 "solve continues without this generation")
        obs_emit("solver_checkpoint", solver=solver, status="failed",
                 reason=reason, path=str(path), error=repr(e),
                 iters=int(meta.get("total_iters", 0)))
        return False
    obs_emit("solver_checkpoint", solver=solver, status="written",
             reason=reason, path=str(path),
             iters=int(meta.get("total_iters", 0)))
    return True


def _partition_ok(meta, solver, path) -> bool:
    """Refusal-with-pointer when the checkpoint's partition fingerprint
    genuinely differs from this build's (a different shard hash): the
    shard snapshots are NOT a permutation of the new partition, so a
    reshard would scatter rows to wrong owners — refuse loudly, name both
    fingerprints, and let the caller start fresh."""
    from ..parallel.reshard import partition_fingerprint
    want = partition_fingerprint()
    got = str(meta.get("partition_fp", "") or "")
    if not got or got == want:
        return True
    from ..utils.logging import log_warn
    log_warn(
        f"{solver} checkpoint at {path} was partitioned under {got}; this "
        f"build partitions under {want} — the shard snapshots cannot be "
        "resharded onto a different partition.  Starting fresh (delete "
        "the checkpoint, or resume it on a build with the original "
        "shard hash)")
    obs_emit("solver_checkpoint", solver=solver, status="refused_partition",
             path=str(path), checkpoint_partition=got,
             build_partition=want)
    return False


def _reshard_degrade(solver, path, e) -> None:
    """A torn/partial reshard (injected ``ckpt_reshard`` fault, missing
    source shard, I/O failure) must degrade to a FRESH solve, never to a
    half-redistributed basis — one warn + one event, then the caller
    returns None."""
    from ..utils.logging import log_warn
    log_warn(f"{solver} checkpoint reshard failed ({e!r}); the restore "
             "degrades to a fresh solve")
    obs_emit("solver_checkpoint", solver=solver, status="reshard_failed",
             path=str(path), error=repr(e))


def _sharded_ckpt_meta(path, fp, legacy_fp):
    """``(meta, fp_used)`` for a sharded checkpoint scan: the primary
    topology-free fingerprint first, then the legacy fixed-D one —
    shared by the Lanczos and LOBPCG restores so the probe order can
    never diverge between the solvers."""
    from ..io.sharded_io import load_hashed_meta
    meta = load_hashed_meta(path, expected_fingerprint=fp)
    if meta is not None or legacy_fp is None:
        return meta, fp
    return load_hashed_meta(path, expected_fingerprint=legacy_fp), legacy_fp


def _needs_reshard(meta, owner) -> bool:
    """Whether the checkpoint's topology stanza names a layout other
    than the live engine's (stanza-free v1 metadata reads as matching —
    fixed topology by construction)."""
    src_d = int(meta.get("topology_d", owner.n_devices))
    src_counts = np.asarray(meta.get("topology_counts", owner.counts),
                            np.int64)
    return (src_d != int(owner.n_devices)
            or not np.array_equal(src_counts,
                                  np.asarray(owner.counts, np.int64)))


def _stage_reshard(path, fp, owner, meta, tail, n_rows, dtype):
    """Collective-free half of a D→D′ restore: build the routing plan
    and stage every source slice this rank's devices host.  Returns
    ``(plan, staged, dt, err)`` — err instead of raising, so the caller
    can fold the outcome into the fixed-point readiness agreement of
    :func:`_restore_sharded_rows` before any collective dispatches."""
    from ..io.sharded_io import hashed_shard_reader
    from ..parallel import reshard as _rs

    try:
        plan = _rs.Resharder(owner, int(meta["topology_d"]),
                             np.asarray(meta["topology_counts"], np.int64),
                             tail=tail)
        # scan-once reader: resolves the candidate .r* files one time
        # (O(m·D) fetches would otherwise re-glob per slice, billed to
        # resume_reshard_s) and rejects files whose own generation
        # disagrees with the selected meta — barrier-free per-rank saves
        # can leave mixed generations under one fingerprint, and the
        # reshard path deliberately reads DEPARTED ranks' files
        with hashed_shard_reader(path, expected_fingerprint=fp,
                                 match_meta=meta) as fetch:
            staged, dt = plan.stage_rows(
                lambda i, s: fetch(s, name=f"krylov_{i}"),
                n_rows, dtype=dtype)
        return plan, staged, dt, None
    except (_rs.PartitionMismatch, OSError, KeyError, ValueError) as e:
        return None, None, None, e


def _read_direct_rows(path, fp, owner, meta, n_rows, tail):
    """Collective-free fixed-D read: this rank's shards of every
    checkpointed row, assembled into ``[D, M, *tail]`` device rows.
    ``(rows, err)`` — same err-returning contract as
    :func:`_stage_reshard`."""
    from ..io.sharded_io import hashed_shard_reader

    M = owner.shard_size
    rows_out = []
    try:
        # match_meta scopes every fetch to the generation load_hashed_meta
        # selected — a stale same-fingerprint .r* file from before a thick
        # restart must fail the restore (KeyError → fresh), not splice its
        # old basis rows in
        with hashed_shard_reader(path, expected_fingerprint=fp,
                                 match_meta=meta) as fetch:
            for i in range(n_rows):
                pieces = [None] * owner.n_devices
                for d in range(owner.n_devices):
                    if not owner._shard_addressable(d):
                        continue
                    r = fetch(d, name=f"krylov_{i}")
                    # dtype from the stored rows: a complex snapshot
                    # (the evolve solver's state) must not silently
                    # cast through a float64 staging buffer
                    full = np.zeros((M,) + tail, dtype=r.dtype)
                    full[: r.shape[0]] = r
                    pieces[d] = full
                rows_out.append(owner._assemble_sharded(pieces))
        return rows_out, None
    except (OSError, KeyError, ValueError) as e:
        return None, e


def _restore_sharded_rows(path, fp, legacy_fp, owner, shape, solver,
                          dtype=None, expect_m=None):
    """Sharded-format restore, safe on process-spanning meshes: select
    the metadata (primary then legacy fingerprint), dispatch direct read
    vs staged D→D′ reshard, agree, exchange.  Returns ``(meta, rows)``
    with ``rows`` in the target ``[D, M, *tail]`` layout, or
    ``(None, None)`` for a fresh start.

    On a process-spanning engine every rank runs ONE fixed-shape
    readiness allgather at this FIXED point, no matter which local
    sub-path it took — metadata missing, partition refusal, torn
    staging, incomplete direct read.  Scattering the agreement across
    sub-paths would let ranks rendezvous on DIFFERENT collectives (one
    rank's meta probe fails → it skips to the caller's generation
    agreement while its peers sit in a staging vote) and hang the job.
    The token carries (ok, reshard?, rows, total_iters, topology_d), so
    ranks that prepared DIFFERENT restores — mixed generations, or one
    resharding while another reads direct — all degrade to fresh
    together; only a unanimous matching-token vote lets the exchange
    dispatch its ppermute rounds.  Staging holds every one-sided
    failure mode (file I/O, the injected ``ckpt_reshard`` fault); the
    exchange after a unanimous vote is one identical static program on
    every rank.

    ``expect_m`` rejects a metadata generation whose basis size is not
    the caller's (LOBPCG: the block width is fixed) before any staging.
    """
    import time as _time

    meta, fp_used = _sharded_ckpt_meta(path, fp, legacy_fp)
    if meta is not None and expect_m is not None \
            and int(meta["m"]) != int(expect_m):
        meta = None
    if meta is not None and _needs_reshard(meta, owner) \
            and not _partition_ok(meta, solver, path):
        meta = None               # refusal-with-pointer: no restore
    multi_span = bool(getattr(owner, "_multi", False))
    if meta is None and not multi_span:
        return None, None
    tail = tuple(shape[2:])
    reshard = meta is not None and _needs_reshard(meta, owner)
    n_rows = int(meta["m"]) + 1 if meta is not None else 0
    plan = staged = dt = rows = err = None
    t0 = _time.perf_counter()
    if reshard:
        plan, staged, dt, err = _stage_reshard(path, fp_used, owner, meta,
                                               tail, n_rows, dtype)
    elif meta is not None:
        rows, err = _read_direct_rows(path, fp_used, owner, meta, n_rows,
                                      tail)
    ok = meta is not None and err is None
    if multi_span:
        from jax.experimental import multihost_utils as _mhu
        tok = np.array(
            [int(ok), int(reshard), n_rows,
             int(meta["total_iters"]) if meta is not None else -1,
             int(meta.get("topology_d", owner.n_devices))
             if meta is not None else -1], np.int64)
        all_tok = _mhu.process_allgather(tok)
        ok = bool((all_tok[:, 0] == 1).all()
                  and (all_tok == all_tok[0]).all())
    if not ok:
        if err is not None and reshard:
            _reshard_degrade(solver, path, err)
        elif err is not None:
            from ..utils.logging import log_debug
            log_debug(f"{solver} sharded checkpoint incomplete ({err!r}); "
                      "starting fresh")
        elif multi_span and meta is not None:
            from ..utils.logging import log_debug
            log_debug(f"{solver} checkpoint restore readiness disagrees "
                      "across ranks; starting fresh")
        return None, None
    if reshard:
        rows = plan.exchange_rows(staged, dt)
        obs_emit("solver_checkpoint", solver=solver, status="resharded",
                 path=str(path), d_from=int(meta["topology_d"]),
                 d_to=int(owner.n_devices), rows=int(n_rows),
                 reshard_s=round(_time.perf_counter() - t0, 6))
    return meta, rows


def _global_rows_for_layout(got, owner, shape, solver, legacy_shape=None):
    """Row list for a SINGLE-CONTROLLER checkpoint payload ``got`` in the
    caller's vector layout ``shape``: direct when the stored topology
    matches, resharded (``parallel/reshard.py``) on a D→D′ mismatch,
    None (fresh start) when the rows fit neither.  ``legacy_shape``
    additionally accepts pre-stanza rows of that shape verbatim (the
    fixed-D v1 format — matching topology by construction)."""
    import time as _time

    V = got["V"]
    src_d = got.get("topology_d")
    if src_d is None or not hasattr(owner, "counts"):
        # legacy fixed-D checkpoint (or a bare-callable solve): rows must
        # already be in the caller's layout
        for want in (tuple(shape),) + ((tuple(legacy_shape),)
                                       if legacy_shape is not None else ()):
            if tuple(V.shape[1:]) == want:
                return [jnp.asarray(r) for r in V]
        return None
    src_d = int(src_d)
    counts = np.asarray(got["topology_counts"], np.int64)
    if not _needs_reshard(got, owner) and tuple(V.shape[1:]) == tuple(shape):
        return [jnp.asarray(r) for r in V]
    if not _partition_ok(got, solver, path="<engine_structure>"):
        return None
    t0 = _time.perf_counter()
    try:
        from ..parallel import reshard as _rs
        plan = _rs.Resharder(owner, src_d, counts, tail=tuple(shape[2:]))
        rows = plan.reshard_rows(
            lambda i, s: V[i, s, : counts[s]], V.shape[0], dtype=V.dtype)
    except (OSError, KeyError, ValueError) as e:      # PartitionMismatch
        _reshard_degrade(solver, "<engine_structure>", e)   # ⊂ ValueError
        return None
    obs_emit("solver_checkpoint", solver=solver, status="resharded",
             d_from=src_d, d_to=int(owner.n_devices), rows=int(V.shape[0]),
             reshard_s=round(_time.perf_counter() - t0, 6))
    return rows


def _restore_ckpt(path, fp, owner, shape, sharded, legacy_fp=None,
                  solver="lanczos", legacy_shape=None, dtype=None):
    """Inverse of :func:`_save_ckpt`; returns a dict with ``V_rows`` (list
    of per-row arrays in the vector layout) plus the recurrence metadata,
    or None when no matching checkpoint exists.

    ``legacy_fp`` additionally probes the pre-elastic shape-keyed
    fingerprint, so fixed-D v1 checkpoints still restore unchanged on a
    matching device count; ``legacy_shape`` is the per-row shape that
    format stored when it differs from ``shape`` (the distributed LOBPCG
    v1 format kept FLAT padded columns where v2 keeps hashed rows).
    ``dtype`` pins the row dtype for a sharded reshard (a rank whose
    devices host no source shard must still build dtype-consistent
    slabs).  A checkpoint whose topology stanza names a DIFFERENT device
    count is resharded onto the live topology (``parallel/reshard.py``)
    instead of refused; a reshard that cannot proceed (foreign partition
    fingerprint, torn source files, the injected ``ckpt_reshard`` fault)
    degrades to a fresh solve with one warn + ``solver_checkpoint``
    event.  A single-controller restore (``sharded=False``) whose
    base-path probe misses falls through to the sharded-format scan, so
    per-rank ``.r*`` files written by a larger multi-process incarnation
    still resume after an elastic shrink to one process."""
    if not sharded:
        from ..io.hdf5 import load_engine_structure
        got = load_engine_structure(path, fp)
        legacy = None
        if got is None and legacy_fp is not None:
            got = load_engine_structure(path, legacy_fp)
            legacy = legacy_shape if legacy_shape is not None else shape
        if got is not None:
            rows = _global_rows_for_layout(got, owner, shape, solver,
                                           legacy_shape=legacy)
            if rows is None:
                return None
            return dict(got, V_rows=rows)
        # The single-controller probe missed, but a LARGER multi-process
        # incarnation of this job may have left per-rank .r* files on
        # shared storage — an elastic shrink to ONE process must not
        # orphan them.  Fall through to the sharded-format scan when the
        # owner can consume the hashed layout: the reshard machinery
        # already reads departed ranks' files, the single-controller
        # restore just has to probe the format.
        if not _sharded_ckpt_engine(owner, shape):
            return None
    meta, rows_out = _restore_sharded_rows(path, fp, legacy_fp, owner,
                                           shape, solver, dtype=dtype)
    if meta is None:
        return None
    return dict(meta, V_rows=rows_out)


def _rand_like(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    v = rng.standard_normal(shape)
    if np.issubdtype(np.dtype(dtype), np.complexfloating):
        v = v + 1j * rng.standard_normal(shape)
    return v.astype(dtype)


def _projected_matrix(alph, bet, lock_theta, lock_sigma, m):
    """Rayleigh projection T = V†HV in the current basis ``V[:m]``.

    Tridiagonal before the first restart; afterwards arrowhead (locked Ritz
    values on the diagonal, coupling row σ) + tridiagonal tail — the standard
    thick-restart structure.  Real symmetric even for complex-Hermitian H.
    """
    l = len(lock_theta)
    T = np.zeros((m, m))
    if l:
        T[:l, :l] = np.diag(lock_theta)
        T[l, :l] = lock_sigma
        T[:l, l] = lock_sigma
    for i in range(l, m):
        T[i, i] = alph[i]
    for i in range(l, m - 1):
        T[i + 1, i] = T[i, i + 1] = bet[i]
    return T


def _buffer_rows(mcap: int) -> int:
    """V-buffer row count: mcap+1 live rows padded up to a multiple of
    ``_GS_BLOCK`` so the blocked sweeps' ``dynamic_slice`` never clamps
    (a clamped start would desynchronize the row mask; pad rows stay zero
    and contribute nothing)."""
    return mcap + 1 + (-(mcap + 1)) % _GS_BLOCK


def _vdot(a, b):
    """⟨a, b⟩ over all elements as a fused elementwise multiply + reduce,
    NOT ``jnp.vdot``/``tensordot``.  The TPU has no f64 matrix unit and XLA
    emulates an f64 ``dot_general`` from bf16/f32 pieces.  Measured on a
    v5e (PERF.md, PR 22): with ``jnp.vdot`` in the block programs the
    16-site anchor's E0 came out 3.8e-9 below the exact ground state and
    chain_32_symm had not converged after 96 iterations; with this form
    both match the CPU to twelve digits, in the CPU's 64 iterations.  The
    elementwise form is the emulated-f64 arithmetic the applies use, and
    the faster one (see ``mgs_pass``)."""
    return jnp.sum(a.conj().reshape(-1) * b.reshape(-1))


def _row_shape(shape):
    """A basis row as the solver's programs hold it: flat, or, for a pair
    vector (a trailing (re, im) axis of 2), its own shape.  A pair vector
    flattened and put back for the apply is a transpose on a TPU, which
    lays the (re, im) axis out as sublanes: the compiler then carried the
    buffer, the window's rows and the vectors in layouts padded 16 to 128
    times, and at ``chain_32_k1`` on four chips the full-sweep block program
    needed 16.84 GB of a 15.75 GB chip (PERF.md, PR 39).  Decided by the
    shape alone, so that a program built from (cap, shape, dtype) outside
    ``lanczos`` (the benchmark's warm-up) is the one ``lanczos`` builds."""
    if len(shape) > 1 and shape[-1] == 2:
        return tuple(shape)
    return (int(np.prod(shape)),)


def _bcast(c, k):
    """``c`` with ``k`` trailing axes of 1, to scale rows of ``k`` axes."""
    return c[(...,) + (None,) * k]


def _pair_dot(a, b):
    """⟨a, b⟩ of two pair vectors of one shape: :func:`_vdot`'s form, with
    no flattening (see :func:`_row_shape`)."""
    return jnp.sum(a * b)


def _J(w):
    """Multiply-by-i on a pair vector: (re, im) → (−im, re)."""
    return jnp.stack([-w[..., 1], w[..., 0]], axis=-1)


def _project_J(wf, Vb, mask=1.0):
    """``wf`` less its projections on ``J·Vb`` (the rows of ``Vb`` times i),
    without forming ``J·Vb``: J is antisymmetric, so ⟨J·v, w⟩ = −⟨v, J·w⟩,
    and Σ d_r J·v_r = J·Σ d_r v_r, so J turns two vectors and not every row
    of the block."""
    k = wf.ndim
    d = jnp.sum(Vb * _J(wf)[None], axis=tuple(range(1, k + 1))) * mask
    return wf + _J(jnp.sum(_bcast(d, k) * Vb, axis=0))


@jax.jit
def _combine_rows(S, Vf):
    """``Sᵀ·Vf`` — out[i] = Σ_j S[j, i]·Vf[j] for S [r, l] and the first r
    rows of Vf [R, n] — accumulated over row blocks of ``_GS_BLOCK`` as a
    fused elementwise multiply + reduce (the form of ``mgs_pass``), for the
    reasons :func:`_vdot` gives.  As a ``tensordot`` the 96-row
    chain_32_symm basis became an ``f32[8, 96, 4707969]`` temporary
    (13.5 GB) and the thick restart did not fit a 16 GB chip.  One pass over
    the [l, n] accumulator per block, not per row.  A row of Vf may have
    more than one axis (:func:`_row_shape`); out is then [l, *row]."""
    r, l = S.shape
    row = Vf.shape[1:]
    k = len(row)

    def add(Y, Sb, Vb):
        return Y + jnp.sum(_bcast(Sb, k) * Vb[:, None], axis=0)

    def full_block(j, Y):
        r0 = j * _GS_BLOCK
        zero = jnp.zeros((), r0.dtype)
        return add(Y,
                   jax.lax.dynamic_slice(S, (r0, zero), (_GS_BLOCK, l)),
                   jax.lax.dynamic_slice(Vf, (r0,) + (zero,) * k,
                                         (_GS_BLOCK,) + row))

    nfull, rest = divmod(r, _GS_BLOCK)
    Y = jnp.zeros((l,) + row, jnp.promote_types(S.dtype, Vf.dtype))
    if nfull:
        Y = jax.lax.fori_loop(0, nfull, full_block, Y)
    if rest:
        Y = add(Y, S[r - rest:], Vf[r - rest:r])
    return Y


def _make_block_runner(mv, mcap, shape, dtype, n_reorth, pair=False):
    """One jitted program advancing the recurrence by ``nsteps`` iterations.

    State: V [_buffer_rows, *shape] basis buffer (donated), alph/bet [mcap]
    f64.  Each iteration: w = H·V[m]; α = ⟨v, w⟩; ``n_reorth`` passes of
    blocked MGS against the live rows; β = ‖w‖; V[m+1] = w/β.  Returns the
    state and the number of steps it ran (its trip count: the loop has no
    other exit), as the window program does.

    ``pair=True`` marks (re, im)-f64 pair vectors (trailing axis 2, the
    TPU-safe complex form).  The realified operator commutes with
    J: (re, im) ↦ (−im, re) (multiplication by i), so each eigenvalue of the
    complex H appears twice — once along v, once along J·v.  MGS therefore
    orthogonalizes against J·V as well: ⟨v, w⟩ and ⟨J·v, w⟩ are exactly
    Re and −Im of the complex ⟨z, w⟩, so the J-aware recurrence *is*
    complex-arithmetic Lanczos (each eigenvalue once, no phantom copies) —
    in pure f64.  Pair rows keep their shape (:func:`_row_shape`).

    ``mv(x, operands)`` is a pure function: the engine's matrix tables ride
    in ``operands`` as real jit arguments.  Closing over them instead would
    bake gigabyte-scale constants into this program (see
    ``LocalEngine.bound_matvec``).
    """
    nrows = _buffer_rows(mcap)
    row = _row_shape(shape)
    k = len(row)
    dot = _pair_dot if pair else _vdot

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def run_block(V, alph, bet, m0, nsteps, operands):
        def mgs_pass(wf, Vf, m):
            # NOTE on form: the projections are written as elementwise
            # multiply + sum, NOT `Vb @ wf` / `c @ Vb` — XLA's f64
            # dot_general is ~10× slower than the fused elementwise reduce
            # on v5e (no f64 MXU; measured 16.5 vs 2.5 ms for a [48, 4.7M]
            # slab), and the reorth passes dominated the iteration at scale.
            def project(wf, Vb, mask):
                c = jnp.sum(Vb.conj() * wf[None], axis=tuple(
                    range(1, k + 1))) * mask.astype(wf.dtype)
                return wf - jnp.sum(_bcast(c, k) * Vb, axis=0)

            def one_block(r0, wf):
                Vb = jax.lax.dynamic_slice(
                    Vf, (r0,) + (jnp.zeros((), r0.dtype),) * k,
                    (_GS_BLOCK,) + row)
                mask = (r0 + jnp.arange(_GS_BLOCK)) <= m
                wf = project(wf, Vb, mask)
                if pair:
                    wf = _project_J(wf, Vb, mask.astype(wf.dtype))
                return wf

            nblk = (m + 1 + _GS_BLOCK - 1) // _GS_BLOCK
            return jax.lax.fori_loop(
                0, nblk, lambda j, wf: one_block(j * _GS_BLOCK, wf), wf)

        def body(i, carry):
            V, alph, bet = carry
            m = m0 + i
            Vf = V.reshape((nrows,) + row)
            vm = jax.lax.dynamic_index_in_dim(Vf, m, keepdims=False)
            # named scopes: metadata on the operations (``op_name``), so
            # a device trace tells the phases of an iteration apart
            with jax.named_scope("lanczos/apply"):
                w = mv(vm.reshape(shape), operands)
            with jax.named_scope("lanczos/recurrence"):
                a = jnp.real(dot(vm, w))
                wf = w.reshape(row)
            with jax.named_scope("lanczos/reorth"):
                for _ in range(n_reorth):
                    wf = mgs_pass(wf, Vf, m)
            with jax.named_scope("lanczos/recurrence"):
                b = jnp.sqrt(jnp.real(dot(wf, wf)))
                vnew = (wf / jnp.where(b <= 1e-300, 1.0, b)).astype(dtype)
            with jax.named_scope("lanczos/store"):
                V = jax.lax.dynamic_update_index_in_dim(
                    Vf, vnew, m + 1, axis=0).reshape(V.shape)
                alph = alph.at[m].set(a)
                bet = bet.at[m].set(b)
            return V, alph, bet

        return jax.lax.fori_loop(0, nsteps, body, (V, alph, bet)) + (nsteps,)

    return run_block


def _make_window_runner(mv, mcap, shape, dtype, n_reorth, nsteps,
                        pair=False):
    """Selective-reorthogonalization block: up to ``nsteps`` iterations
    whose MGS passes project only against the trailing ``W_ROWS`` rows, and
    which stops working at the step where the ω estimate crosses √ε.

    The program carries the host tracker's ``state`` (``omega``: its two
    ω rows, padded to ``mcap + 1``, its ε and its limit) and evolves the
    rows after each step with the host's own arithmetic
    (:func:`_omega_row`).  Once a step's row has reached the limit no
    further apply runs: everything after that step would be thrown away by
    the host loop, which runs the rest of the block under the full sweep.
    Returned beside ``V``, ``alph``, ``bet`` is the number of steps that
    ran, the crossing one included.  The estimate only decides when the
    device stops working; what is kept is the host's decision, from the
    (α, β) that come back.

    Structured around a SMALL ring buffer, not the big V carry: the full
    runner's ``fori_loop`` carries the whole [_buffer_rows, N] basis, which
    it reads and writes, and XLA's CPU runtime copies that carry on every
    iteration (measured 28 ms/iter for chain_20's 83 MB buffer — a floor
    that swallowed the whole selective win).  Here the loop carries only
    the [W_ROWS, N] window, ``lax.scan`` stacks the new vectors in place,
    and the basis buffer is written ONCE per block — the per-iteration
    traffic drops from O(mcap·N) to O(window·N).  ``nsteps`` is a
    compile-time constant (scan needs a static length); a solve sees at
    most a handful of distinct block lengths, each compiled once.  The
    early end is a ``lax.cond`` on the carried flag inside the scan's step:
    after the crossing the remaining trips do nothing (and stack zero rows
    above the live ones, which the full sweep overwrites).  The other form,
    a ``lax.while_loop`` on ``(i < nsteps) & ~crossed`` that writes each
    new row into the carried basis buffer (written there, never read, and
    NOT copied per iteration by the CPU runtime: 1.4 ms a step against
    this form's 1.5 at chain_20's size), measured 1-2% slower end to end on
    a v5e at both one-chip cells (PERF.md, PR 29).

    The ω-gated host loop guarantees the window is enough: whenever the
    accumulated orthogonality estimate reaches √ε, the rest of the block
    runs the full sweep via :func:`_make_block_runner`."""
    nrows = _buffer_rows(mcap)
    row = _row_shape(shape)
    k = len(row)
    dot = _pair_dot if pair else _vdot
    # the trailing window: v_m and v_{m-1} (the recurrence pair) plus two
    # more recent rows of slack — PROPACK's local reorthogonalization uses
    # exactly the pair; the ω gate upgrades to full sweeps when locality
    # stops being enough, so the window stays minimal
    W_ROWS = 4
    # one local MGS pass per step (the three-term recurrence + cleanup);
    # escalated blocks run the full runner with its n_reorth sweeps
    n_local = max(1, n_reorth - 1)

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def run_window(V, alph, bet, m0, omega, operands):
        om_curr, om_prev, eps, limit = omega
        Vf = V.reshape((nrows,) + row)
        r0 = jnp.maximum(m0 - (W_ROWS - 1), 0)
        W = jax.lax.dynamic_slice(
            Vf, (r0,) + (jnp.zeros((), r0.dtype),) * k, (W_ROWS,) + row)
        # rows above m0 can be stale (short thick restarts leave old basis
        # rows beyond l) — zero them; zero rows project to nothing
        W = jnp.where(_bcast((r0 + jnp.arange(W_ROWS)) <= m0, k), W, 0)
        # ring invariant: v_{m0} sits in the LAST row.  When m0 < W_ROWS-1
        # the clamped slice leaves it at index m0 — roll the (zeroed)
        # stale rows over the top
        W = jnp.roll(W, (W_ROWS - 1) - (m0 - r0), axis=0)

        def project(wf, Vb):
            c = jnp.sum(Vb.conj() * wf[None], axis=tuple(range(1, k + 1)))
            return wf - jnp.sum(_bcast(c, k) * Vb, axis=0)

        def work(carry):
            ran, _, W, alph, bet, om, omp = carry
            m = m0 + ran
            vm = W[W_ROWS - 1]
            with jax.named_scope("lanczos/apply"):
                w = mv(vm.reshape(shape), operands)
            with jax.named_scope("lanczos/recurrence"):
                a = jnp.real(dot(vm, w))
                wf = w.reshape(row)
            with jax.named_scope("lanczos/reorth"):
                for _ in range(n_local):
                    wf = project(wf, W)
                    if pair:
                        wf = _project_J(wf, W)
            with jax.named_scope("lanczos/recurrence"):
                b = jnp.sqrt(jnp.real(dot(wf, wf)))
                vnew = (wf / jnp.where(b <= 1e-300, 1.0, b)).astype(dtype)
                W = jnp.concatenate([W[1:], vnew[None]], axis=0)
            with jax.named_scope("lanczos/store"):
                alph = alph.at[m].set(a)
                bet = bet.at[m].set(b)
            with jax.named_scope("lanczos/omega"):
                new, worst = _omega_row(jnp, om, omp, alph, bet, m, eps)
            # a NaN row counts as crossed, as on the host
            return (ran + 1, ~(worst < limit), W, alph, bet, new, om), vnew

        def rest(carry):
            return carry, jnp.zeros(row, dtype)

        (ran, _, _, alph, bet, _, _), Vnew = jax.lax.scan(
            lambda carry, _: jax.lax.cond(carry[1], rest, work, carry),
            (jnp.int32(0), jnp.bool_(False), W, alph, bet, om_curr, om_prev),
            None, length=nsteps)
        with jax.named_scope("lanczos/store"):
            Vf = jax.lax.dynamic_update_slice(
                Vf, Vnew, (m0 + 1,) + (jnp.zeros((), m0.dtype),) * k)
        return Vf.reshape(V.shape), alph, bet, ran

    return run_window


@lru_cache(maxsize=16)
def _make_restart(mcap, shape, dtype, l):
    """V[:l] ← SᵀV[:m] (kept Ritz vectors), V[l] ← last residual vector.

    One program per (cap, shape, dtype, l), kept between solves as the
    buffer's programs are: it closes over no engine, so a program built
    ahead of a solve (the benchmark's ``warm_epilogue``) is the one the
    solve runs."""
    nrows = _buffer_rows(mcap)
    row = _row_shape(shape)

    @partial(jax.jit, donate_argnums=(0,))
    def restart(V, S_l):
        Vf = V.reshape((nrows,) + row)
        v_last = Vf[mcap]
        Y = _combine_rows(S_l.astype(dtype), Vf)
        Vf = jax.lax.dynamic_update_slice(Vf, Y, (0,) * (len(row) + 1))
        Vf = jax.lax.dynamic_update_index_in_dim(Vf, v_last, l, axis=0)
        return Vf.reshape(V.shape)

    return restart


@lru_cache(maxsize=16)
def _make_buffer_programs(nrows, shape, dtype, sharding):
    """The two programs through which a Krylov buffer ``[nrows, *shape]``
    comes to hold rows it did not compute: ``make(row)``, the buffer zero
    but for ``row`` in row 0, and ``set_row(V, i, row)``, which writes row
    ``i`` of a donated buffer (a checkpoint's restore).  Inside one program
    the zero fill and the row write are one output buffer, and a donated
    buffer is updated in place, so neither ever holds a second array of the
    buffer's size: an eager ``jnp.zeros(...).at[0].set(row)`` donates
    nothing and held two (9.42 GB where 5.37 are resident at chain_32_symm:
    PERF.md, PR 37).

    ``sharding`` is the rows' ``NamedSharding`` (the hashed ``[D, M(, 2)]``
    vectors of ``DistributedEngine``) or None.  Over a mesh both programs
    put their output out with the row's spec behind an unsharded row axis,
    so every chip allocates its own share from birth; an uncommitted
    ``jnp.zeros`` landed whole on device 0.  One pair per (rows, shape,
    dtype, sharding), kept between solves so that nothing is re-traced; a
    long-lived process that solves in many vector spaces keeps the last
    sixteen."""
    out = {} if sharding is None else {"out_shardings": NamedSharding(
        sharding.mesh, PartitionSpec(None, *sharding.spec))}

    @partial(jax.jit, **out)
    def make(row):
        return jnp.zeros((nrows,) + shape, dtype).at[0].set(row)

    @partial(jax.jit, donate_argnums=(0,), **out)
    def set_row(V, i, row):
        return jax.lax.dynamic_update_index_in_dim(
            V, row.astype(dtype), i, axis=0)

    return make, set_row


def _buffer_programs(mcap: int, row):
    """:func:`_make_buffer_programs` for the buffer whose rows are laid out
    as ``row`` is: what selects the sharded form is the sharding the row
    carries, nothing else."""
    sharding = getattr(row, "sharding", None)
    if not isinstance(sharding, NamedSharding):
        sharding = None
    return _make_buffer_programs(_buffer_rows(mcap), row.shape,
                                 np.dtype(row.dtype), sharding)


def krylov_buffer(mcap: int, row):
    """The buffer a solve of at most ``mcap`` basis vectors runs in:
    ``[_buffer_rows(mcap), *row.shape]`` of ``row``'s dtype, zero but for
    ``row`` in row 0, on ``row``'s device or sharded over its mesh as
    ``row`` is.  One program's output (see :func:`_make_buffer_programs`);
    dispatched, not waited for."""
    return _buffer_programs(mcap, row)[0](row)


def _recurrence_zeros(mcap: int, start):
    """Zero (α, β) ``[mcap]`` placed where the block programs leave them:
    replicated over the mesh of a sharded start vector, else on its device,
    committed as it is.  The first call of a block program then has the
    argument signature of every later one, so it is traced and built once:
    given uncommitted (α, β), a mesh's programs return them replicated and
    committed, a second signature, and the full-sweep program would be
    built twice a solve."""
    zeros = np.zeros(mcap)
    sharding = start.sharding
    if isinstance(sharding, NamedSharding):
        return jax.device_put(zeros,
                              NamedSharding(sharding.mesh, PartitionSpec()))
    return jax.device_put(zeros, sharding) if start.committed \
        else jnp.asarray(zeros)


def lanczos_block(matvec: Callable, *args, **kwargs) -> LanczosResult:
    """Solve-span wrapper over :func:`_lanczos_block_impl` (see there for
    the full contract): the solver call is ONE ``solve`` span, each block
    step an ``iteration`` span, and the eager engine applies inside nest
    as ``apply`` spans — the span tree ``obs_report trace`` exports."""
    with obs_trace.span("lanczos_block", kind="solve",
                        k=int(kwargs.get("k", args[1] if len(args) > 1
                                          else 1))):
        return _lanczos_block_impl(matvec, *args, **kwargs)


def _lanczos_block_impl(
    matvec: Callable,
    n: Optional[int] = None,
    k: int = 1,
    block_size: Optional[int] = None,
    max_iters: int = 200,
    tol: float = 1e-10,
    seed: int = 0,
    V0=None,
    compute_eigenvectors: bool = False,
    column_targets=None,
    max_basis_size: Optional[int] = None,
    min_restart_size: Optional[int] = None,
) -> LanczosResult:
    """Lowest-``k`` eigenpairs via *block* Lanczos over the batched matvec.

    Each step applies H to a whole ``[n, p]`` block in ONE engine call —
    the multi-RHS ELL apply gathers each structure row once and contracts
    over the p columns, so the per-vector cost drops well below p separate
    applies (the amortization PRIMME's blocked Davidson gets from
    ``kMaxBlockSize``, Diagonalize.chpl:171).  Block recurrence with full
    reorthogonalization (two MGS passes against every kept block) and QR
    between steps; the projected matrix is block tridiagonal
    ``[A_0 B_0ᵀ; B_0 A_1 …]``, and the residual bound for a Ritz pair
    (θ, s) is ``‖B_j · s[last p rows]‖``.

    **Thick restarts** (``max_basis_size``): by default the basis grows
    to ``max_iters`` vectors; with a cap, whenever the next step would
    exceed it the basis is COMPRESSED to the ``min_restart_size``
    (default: the block width) lowest Ritz vectors and the recurrence
    restarts from that block — the same compression-restart machinery
    the narrowing column exit uses (DESIGN.md §26/§29), so every
    reported residual stays an exact recurrence residual.  This bounds
    the Krylov workspace at ``max_basis_size`` columns — the only way a
    streamed-engine solve at the chain_36-class rung keeps its solver
    state in memory — at the price of more total iterations (each
    epoch restarts from the best Ritz subspace, so convergence stays
    monotone).  Pair-mode engines are refused — the J-aware
    reorthogonalization lives in :func:`lanczos`; complex sectors run
    natively here (CPU) or via :func:`lanczos` on TPU.

    ``max_iters`` counts *individual matvec columns* (p per block step),
    so budgets are comparable with :func:`lanczos`.

    Heterogeneous convergence (``column_targets``, the solve service's
    batched path — DESIGN.md §26): a list of ``{"k", "tol", "job_id"}``
    mappings, one per batched job.  Every target is judged each step
    against ITS OWN (k, tol) on the shared Ritz pairs; when a target
    converges its result is snapshotted (eigenvalues/residuals at that
    basis size) and its column EXITS the batch: the basis is compressed
    to the lowest Ritz vectors and the recurrence RESTARTS at the
    narrower width (restarted block Lanczos — naive column truncation
    would discard live Krylov directions and silently break the
    residual bound, so narrowing always goes through a restart; every
    reported residual is an exact recurrence residual).  The solve ends
    when every target is done (``converged`` = all converged);
    per-target records land in :attr:`LanczosResult.column_results`.
    Narrowing recompiles the engine apply per new width — worth it
    whenever the remaining work is more than a few steps (the AOT cache
    makes repeat widths free).

    Hashed multi-RHS: a :class:`~..parallel.distributed.DistributedEngine`
    behind ``matvec`` is driven natively in its hashed ``[D, M, p]``
    layout — pass ``V0`` of that shape, or pass neither ``V0`` nor ``n``
    and the start block comes from ``owner.random_hashed(seed, cols=p)``.
    Each block step is then ONE eager engine apply, so a STREAMED engine
    streams each plan chunk once per k-column block instead of once per
    column — this is the solver loop the streamed mode's amortization
    targets (eigenvectors come back in hashed layout).
    """
    owner = getattr(matvec, "__self__", None)
    if bool(getattr(owner, "pair", False)):
        streamed = getattr(owner, "mode", None) in ("streamed", "hybrid")
        raise ValueError(
            "lanczos_block does not support pair-mode engines "
            "(J-aware reorthogonalization lives in lanczos())"
            + ("; a PAIR-mode STREAMED engine currently has no in-tree "
               "solver — use mode='ell'/'fused' for pair sectors, or run "
               "the sector native-c128 on CPU" if streamed else ""))
    targets = None
    if column_targets is not None:
        targets = [{"k": int(t.get("k", 1)), "tol": float(t.get("tol", tol)),
                    "max_iters": int(t["max_iters"])
                    if t.get("max_iters") else None,
                    "job_id": t.get("job_id")} for t in column_targets]
        if not targets:
            raise ValueError("column_targets must be a non-empty sequence")
        k = max(int(k), max(t["k"] for t in targets))
    p = int(block_size or max(k, 2,
                              len(targets) if targets is not None else 0))
    if p < 1:
        raise ValueError(f"block_size must be >= 1, got {p}")
    if targets is not None and len(targets) > p:
        raise ValueError(f"{len(targets)} column targets need a block of "
                         f"at least that many columns, got {p}")
    mcap = l_thick = None
    if max_basis_size is not None:
        # restart width: the Ritz block the compression keeps — by
        # default max(width, 2k+2): keeping only the k targets starves
        # the restarted epoch near convergence (the residual directions
        # of converged pairs collapse the next QR into a breakdown
        # before the bound crosses tol — measured on chain_12 at
        # tol 1e-13), while 2k+2 is the same slack the single-vector
        # thick restart keeps.  The cap itself must leave the restart
        # block room to grow by two steps, or the recurrence could
        # never advance — undersized caps round UP to that minimum
        # rather than refuse.
        l_thick = max(int(min_restart_size) if min_restart_size
                      else max(p, 2 * k + 2), k, 1)
        mcap = max(int(max_basis_size), l_thick + 2 * p)

    hashed_owner = (owner is not None and hasattr(owner, "shard_size")
                    and hasattr(owner, "random_hashed"))
    if V0 is None:
        if n is None:
            if not hashed_owner:
                raise ValueError("pass V0 or n")
            V0 = owner.random_hashed(seed, cols=p)      # [D, M, p]
        else:
            V0 = _rand_like((n, p), np.float64, seed)
    V0 = jnp.asarray(V0)
    vec_shape = None         # non-None: hashed [D, M] engine layout
    if (hashed_owner and V0.ndim == 3
            and V0.shape[:2] == (owner.n_devices, owner.shard_size)):
        vec_shape = V0.shape[:2]
        V0 = V0.reshape(-1, V0.shape[2])   # flat [D·M, p] for the algebra
    if V0.ndim != 2:
        raise ValueError(f"V0 must be [n, p] (or hashed [D, M, p] for a "
                         f"distributed engine), got shape {V0.shape}")
    n, p = V0.shape

    def mv(X):
        # hashed engines consume/produce [D, M, p]; the dense algebra
        # (QR, projections) runs on the flat [D·M, p] view — pad slots are
        # zero by engine invariant, so inner products and factorizations
        # are exact.  Width read off X, not closed over: a column-target
        # solve narrows the block as jobs finish.
        pc = int(X.shape[1])
        Y = matvec(X.reshape(vec_shape + (pc,))) if vec_shape else matvec(X)
        Y = Y[0] if isinstance(Y, tuple) else Y
        return Y.reshape(-1, pc) if vec_shape else Y

    # Probe eagerly with the QR'd first block and REUSE the result as
    # step 0's apply: fixes the dtype (a complex-Hermitian operator
    # promotes a real block) and runs engine first-apply validation
    # without discarding a p-column matvec — the single most expensive
    # operation here.  QR commutes with the later real→complex cast.
    import time as _time
    t0 = _time.perf_counter()
    Q, _ = jnp.linalg.qr(V0)
    W0 = mv(Q)
    dtype = jnp.promote_types(V0.dtype, W0.dtype)
    Q = Q.astype(dtype)
    probe_s = _time.perf_counter() - t0
    blocks = [Q]                     # each [n, w_i], mutually orthonormal
    A_list: list = []                # diagonal blocks   [w_i, w_i]
    B_list: list = []                # subdiagonal blocks [w_{i+1}, w_i]
    widths: list = []                # per-step block widths (uniform at
    #                                  p_cur within an epoch — a narrowing
    #                                  restart resets these lists at the
    #                                  new width)
    theta = S = res = None
    converged = False
    total = 0
    p_cur = p
    n_restarts = 0
    a_seq: list = []        # scalarized per-step (α, β) for the ω estimate
    b_seq: list = []
    # thick-restart lock state (DESIGN.md §29): locked Ritz values, their
    # orthonormal basis block, and the residual coupling of the FIRST
    # active block to them — the block arrowhead, the same structure the
    # single-vector solver's (lock_theta, lock_sigma) carry.  Locked
    # vectors are never fed back through H (doing so collapses the next
    # QR once a pair converges); the recurrence continues from the NEXT
    # Krylov block, with the coupling keeping every residual exact.
    lock_theta = np.zeros(0)
    lock_Y = None                       # [n, l] locked Ritz block
    lock_C = None                       # [widths[0], l] coupling row

    def _ritz_block(S_cols, m_rows):
        """[n, c] Ritz combinations over the kept basis covering the
        first ``m_rows`` rows — locked rows first, then the active
        blocks (snapshots are taken at step ends, so block boundaries
        always align).  Reads the lock/blocks state at CALL time —
        valid for any snapshot taken since the last restart."""
        l0 = int(lock_theta.shape[0])
        Sj = jnp.asarray(S_cols, dtype=dtype)
        offs = np.concatenate(([0], np.cumsum(widths))).astype(int)
        nb = int(np.searchsorted(offs, m_rows - l0))
        out = sum(blocks[i] @ Sj[l0 + offs[i]: l0 + offs[i + 1]]
                  for i in range(nb))
        if l0:
            out = lock_Y @ Sj[:l0] + out
        return out

    def _assemble(S_cols, m_rows):
        """Normalized Ritz vectors in the matvec's layout."""
        E = _ritz_block(np.asarray(S_cols), m_rows)
        out = []
        for i in range(np.asarray(S_cols).shape[1]):
            e = E[:, i]
            e = e / jnp.sqrt(jnp.real(jnp.vdot(e, e))).astype(dtype)
            out.append(e.reshape(vec_shape) if vec_shape else e)
        return out

    first_block_s = 0.0
    first_block_iters = 0
    steady_s = 0.0
    watchdog = _Watchdog("lanczos_block")
    preempt.ensure_installed()
    agree_multi = jax.process_count() > 1 and (
        owner is None or bool(getattr(owner, "_multi", True)))
    obs_emit("solver_start", solver="lanczos_block", k=int(k),
             block_size=int(p), max_iters=int(max_iters), tol=float(tol),
             **({"column_targets": len(targets)} if targets else {}))

    # unbounded-basis solver: the block list GROWS — the ledger entry is
    # updated per appended block so forensics show the live footprint
    mem_h = obs_memory.NULL_HANDLE
    blk_path = None
    if obs_enabled():
        blk_path = (f"solver/{obs_memory.next_instance('lanczos_block')}"
                    "/block_basis")
        mem_h = obs_memory.track(blk_path, int(Q.nbytes),
                                 block_size=int(p))

    j = 0
    while True:
        faults.check("solver_block", exc=RuntimeError,
                     solver="lanczos_block", iter=int(total))
        # safe point between block steps (no checkpoint machinery here —
        # the block basis is unbounded; the exit is still clean and agreed
        # so a preempted streamed solve dies at a block boundary, not
        # inside a half-streamed plan pass)
        if preempt.agreed(agree_multi):
            obs_emit("solver_preempted", solver="lanczos_block",
                     iters=int(total), checkpoint="")
            obs_flush()
            mem_h.release()
            raise preempt.Preempted("lanczos_block", total, None)
        t0 = _time.perf_counter()
        # iteration span: one block step (p_cur matvec columns + the block
        # recurrence) — the eager engine apply inside nests as its child
        with obs_trace.span("iteration", kind="iteration",
                            solver="lanczos_block", iter=int(total),
                            block=j):
            Qj = blocks[-1]
            # step 0 reuses the probe's apply (timed via probe_s below)
            W = (W0 if j == 0 else mv(Qj)).astype(dtype)
            W0 = None
            A = Qj.conj().T @ W
            W = W - Qj @ A
            if B_list:          # empty right after a narrowing restart
                W = W - blocks[-2] @ B_list[-1].conj().T
            # full reorthogonalization, two passes, LOCKED block
            # included (classic block-Lanczos loss of orthogonality is
            # what makes the naive recurrence useless; the locked
            # coupling is carried by the arrowhead, so the projection
            # here just enforces exact orthogonality)
            for _ in range(2):
                for Qi in (() if lock_Y is None else (lock_Y,)) \
                        + tuple(blocks):
                    W = W - Qi @ (Qi.conj().T @ W)
            Qn, B = jnp.linalg.qr(W)
            jax.block_until_ready(Qn)
        dt = _time.perf_counter() - t0
        if j == 0:
            first_block_s, first_block_iters = dt + probe_s, p
        else:
            steady_s += dt
        A_list.append(np.asarray(A))
        B_list.append(np.asarray(B))
        widths.append(p_cur)
        total += p_cur
        l0 = int(lock_theta.shape[0])
        m = l0 + sum(widths)
        # scalarized (α, β) proxy for the ω-recurrence: the block analog of
        # β_j is the smallest new-direction magnitude min|diag(R_j)| — the
        # quantity whose collapse signals orthogonality/rank loss — and of
        # α_j the block's magnitude scale
        a_seq.append(float(np.max(np.abs(A_list[-1]))))
        b_seq.append(float(np.min(np.abs(np.diag(B_list[-1])))))

        # projected matrix (Hermitian by construction; A is numerically
        # Hermitian only to roundoff — symmetrize): block tridiagonal,
        # preceded after a thick restart by the arrowhead — locked Ritz
        # values on the diagonal, the coupling row against the first
        # active block.  Offsets come from the widths list; within one
        # epoch (between restarts, which reset these lists) every block
        # is p_cur wide, so all blocks here are square at widths[i]
        T = np.zeros((m, m), dtype=np.result_type(
            *(A_list + ([lock_C] if lock_C is not None else []))))
        if l0:
            T[:l0, :l0] = np.diag(lock_theta)
            w0 = widths[0]
            T[l0: l0 + w0, :l0] = lock_C
            T[:l0, l0: l0 + w0] = lock_C.conj().T
        off = l0
        for i, Ai in enumerate(A_list):
            w = widths[i]
            T[off: off + w, off: off + w] = (Ai + Ai.conj().T) / 2
            off += w
        off = l0
        for i, Bi in enumerate(B_list[:-1]):
            w0, w1 = widths[i], widths[i + 1]
            T[off + w0: off + w0 + w1, off: off + w0] = Bi
            T[off: off + w0, off + w0: off + w0 + w1] = Bi.conj().T
            off += w0
        kk = min(k, m)
        theta, S = eigh(T, subset_by_index=(0, kk - 1))
        res = np.linalg.norm(
            np.asarray(B_list[-1]) @ S[m - widths[-1]:, :], axis=0)
        omega = obs_health.omega_estimate(
            np.asarray(a_seq), np.asarray(b_seq),
            len(b_seq) - 1, len(b_seq)) \
            if obs_health.probes_enabled() else None
        _emit_trace("lanczos_block", total, m, theta, res, omega)
        newly_done = 0
        if targets is None:
            if m >= k and np.all(res < tol * np.maximum(1.0,
                                                        np.abs(theta))):
                converged = True
                break
        else:
            # heterogeneous convergence: every unfinished target judged
            # against ITS OWN (k, tol) on the shared Ritz pairs; a
            # converged target's result is snapshotted here and its
            # column exits below
            for t in targets:
                if t.get("done"):
                    continue
                kt = min(t["k"], kk)
                ok = m >= t["k"] and np.all(
                    res[:kt] < t["tol"]
                    * np.maximum(1.0, np.abs(theta[:kt])))
                # a target whose OWN column budget is spent exits too —
                # unconverged, exactly like its solo run would have: a
                # batch must never bill a job more columns than its spec
                # (and its admission pricing) allowed
                spent = (not ok and t["max_iters"] is not None
                         and total >= t["max_iters"])
                if not ok and not spent:
                    continue
                t["done"] = True
                t["snapshot"] = {
                    "theta": np.asarray(theta[:kt]).copy(),
                    "res": np.asarray(res[:kt]).copy(),
                    "S": np.asarray(S[:, :kt]).copy(),
                    "m": int(m), "iters": int(total),
                    "converged": bool(ok)}
                newly_done += 1
                obs_emit("solver_column_converged"
                         if ok else "solver_column_budget_exhausted",
                         solver="lanczos_block",
                         target_job_id=str(t.get("job_id") or ""),
                         k=int(t["k"]), iters=int(total),
                         basis_size=int(m), width=int(p_cur))
            if all(t.get("done") for t in targets):
                converged = all(t["snapshot"]["converged"]
                                for t in targets)
                break
        watchdog.report_omega(omega, total)
        # breakdown: the Krylov space closed (rank-deficient new block) —
        # with full reorth a deficient column is numerical noise, stop
        rdiag = np.abs(np.diag(np.asarray(B)))
        if rdiag.min() < 1e-12 * max(rdiag.max(), 1.0):
            watchdog.breakdown(total, float(rdiag.min()), converged=False)
            break
        if total + p_cur > max_iters:
            break
        watchdog.check_stagnation(res, total)
        if newly_done:
            remaining = [t for t in targets if not t.get("done")]
            p_new = max(len(remaining),
                        max(t["k"] for t in remaining), 1)
            if p_new < p_cur:
                # Column exit via a COMPRESSION RESTART: simply dropping
                # columns of the QR'd new block would discard genuine
                # Krylov directions and silently break the residual
                # bound (||B·s_last|| no longer accounts for the
                # discarded component — measured: a 1e-10 claim with a
                # 1e-6 true error).  Instead the basis is compressed to
                # the p_new lowest Ritz vectors and the recurrence
                # RESTARTS at the narrower width — restarted block
                # Lanczos, every subsequent residual an exact recurrence
                # residual again.  Finished targets' eigenvectors are
                # materialized first (their snapshots reference the
                # blocks this restart is about to drop).
                if compute_eigenvectors:
                    for t in targets:
                        snap = t.get("snapshot")
                        if snap is not None and "vecs" not in snap:
                            snap["vecs"] = _assemble(snap["S"], snap["m"])
                _, S_r = eigh(T, subset_by_index=(0, p_new - 1))
                Q0, _ = jnp.linalg.qr(_ritz_block(S_r, m))
                jax.block_until_ready(Q0)
                blocks = [Q0.astype(dtype)]
                A_list, B_list, widths = [], [], []
                a_seq, b_seq = [], []      # ω table resets with the basis
                # the narrowing compression folds any locked block into
                # Q0 (the _ritz_block above spans it) — lock state clears
                lock_theta = np.zeros(0)
                lock_Y = lock_C = None
                obs_emit("solver_restart_narrow", solver="lanczos_block",
                         iters=int(total), width=int(p_cur),
                         new_width=int(p_new), basis_size=int(m),
                         remaining=len(remaining))
                p_cur = p_new
                if blk_path is not None:
                    mem_h.set(blk_path,
                              int(sum(b.nbytes for b in blocks)))
                j += 1
                continue
        if mcap is not None and m + p_cur > mcap:
            # Thick (memory-bounding) restart — the TRLan scheme in
            # block form: keep the l_thick lowest Ritz vectors as a
            # LOCKED block, continue the recurrence from the NEXT
            # Krylov block Qn (already orthonormal to everything), and
            # carry the exact coupling C = B·S[last rows] into the
            # arrowhead of every later projection.  H is never applied
            # to the locked vectors again — re-applying it is what
            # collapses the next QR into a spurious breakdown once a
            # pair converges — and H·(basis·S) = basis·S·Θ + Qn·C
            # exactly, so every later residual bound stays an exact
            # recurrence residual.  Finished targets' eigenvectors are
            # materialized first: their snapshots reference the blocks
            # this restart drops.
            if compute_eigenvectors and targets:
                for t in targets:
                    snap = t.get("snapshot")
                    if snap is not None and "vecs" not in snap:
                        snap["vecs"] = _assemble(snap["S"], snap["m"])
            ll = min(int(l_thick), m - 1)
            theta_all, S_all = eigh(T)
            Y_new = _ritz_block(S_all[:, :ll], m).astype(dtype)
            C_new = np.asarray(B) @ S_all[m - widths[-1]:, :ll]
            jax.block_until_ready(Y_new)
            lock_theta = np.asarray(theta_all[:ll])
            lock_Y = Y_new
            lock_C = C_new             # [p_cur, ll]: next epoch's first
            blocks = [Qn]              # block is Qn, width p_cur
            A_list, B_list, widths = [], [], []
            a_seq, b_seq = [], []      # ω table resets with the basis
            n_restarts += 1
            obs_emit("solver_restart_thick", solver="lanczos_block",
                     iters=int(total), basis_size=int(m), kept=int(ll),
                     width=int(p_cur), cap=int(mcap))
            if blk_path is not None:
                mem_h.set(blk_path,
                          int(sum(b.nbytes for b in blocks)
                              + lock_Y.nbytes))
            j += 1
            continue
        blocks.append(Qn)
        if blk_path is not None:
            mem_h.set(blk_path, int(
                sum(b.nbytes for b in blocks)
                + (lock_Y.nbytes if lock_Y is not None else 0)))
        j += 1

    m_fin = int(lock_theta.shape[0]) + sum(widths)
    kk = min(k, m_fin) if m_fin else 0

    evecs = None
    if compute_eigenvectors and theta is not None:
        # `blocks` may hold one extra (not yet projected) block when the
        # loop ran to its last step — _assemble() stops at the m-th row
        evecs = _assemble(np.asarray(S[:, :kk]), m_fin)

    column_results = None
    if targets is not None:
        column_results = []
        for t in targets:
            snap = t.get("snapshot")
            if snap is None and theta is not None:
                # unfinished target: its best-so-far reading at the final
                # basis size, marked unconverged
                kt = min(t["k"], kk)
                snap = {"theta": np.asarray(theta[:kt]),
                        "res": np.asarray(res[:kt]),
                        "S": np.asarray(S[:, :kt]),
                        "m": int(m_fin), "iters": int(total),
                        "converged": False}
            entry = {"job_id": t.get("job_id"), "k": int(t["k"]),
                     "tol": float(t["tol"]),
                     "converged": bool(snap and snap["converged"]),
                     "eigenvalues": np.asarray(snap["theta"])
                     if snap else np.zeros(0),
                     "residuals": np.asarray(snap["res"])
                     if snap else np.zeros(0),
                     "iters": int(snap["iters"]) if snap else 0,
                     "basis_size": int(snap["m"]) if snap else 0}
            if compute_eigenvectors and snap is not None:
                # materialized at a narrowing restart when the snapshot's
                # blocks were dropped; assembled here otherwise
                entry["eigenvectors"] = snap.get("vecs") \
                    or _assemble(np.asarray(snap["S"]), snap["m"])
            column_results.append(entry)

    obs_emit("solver_end", solver="lanczos_block", iters=int(total),
             converged=bool(converged),
             eigenvalues=[float(t) for t in np.atleast_1d(theta)[:kk]]
             if theta is not None else [])
    mem_h.release()
    return LanczosResult(
        eigenvalues=np.asarray(theta[:kk]) if theta is not None
        else np.zeros(0),
        eigenvectors=evecs,
        residual_norms=np.asarray(res[:kk]) if res is not None
        else np.zeros(0),
        num_iters=total,
        converged=converged,
        restarts=n_restarts,
        first_block_seconds=first_block_s,
        first_block_iters=first_block_iters,
        steady_seconds=steady_s,
        column_results=column_results,
    )


def lanczos(matvec: Callable, *args, **kwargs) -> LanczosResult:
    """Solve-span wrapper over :func:`_lanczos_impl` — the whole solver
    call (setup, restore, every iteration block, the eigenvector
    epilogue) becomes ONE ``solve`` span, so a traced run's events nest
    iteration ⊂ solve even across preemption exits.  See
    :func:`_lanczos_impl` for the full contract."""
    with obs_trace.span("lanczos", kind="solve",
                        k=int(kwargs.get("k", args[1] if len(args) > 1
                                          else 1))) as root:
        return _lanczos_impl(matvec, *args, root=root, **kwargs)


def _lanczos_impl(
    matvec: Callable,
    n: Optional[int] = None,
    k: int = 1,
    max_iters: int = 300,
    tol: float = 1e-10,
    seed: int = 0,
    v0=None,
    compute_eigenvectors: bool = False,
    full_reorth: bool = True,
    max_basis_size: Optional[int] = None,
    min_restart_size: Optional[int] = None,
    check_every: int = 16,
    pair: Optional[bool] = None,
    checkpoint_path: Optional[str] = None,
    checkpoint_every: int = 4,
    reorth: Optional[str] = None,
    *,
    root=obs_trace.NULL_SPAN,
) -> LanczosResult:
    """Lowest-``k`` eigenpairs of the Hermitian operator behind ``matvec``.

    ``v0`` (or ``n`` + ``seed``) fixes the start vector; convergence is the
    standard residual bound ``|β_m s_m,i| < tol·max(1,|θ_i|)`` for the k
    lowest Ritz pairs.  ``max_basis_size``/``min_restart_size`` mirror the
    reference driver's ``kMaxBasisSize``/``kMinRestartSize``
    (Diagonalize.chpl:169-170) and bound device memory at
    ``(max_basis_size+1)`` vectors via thick restarts.

    ``pair`` marks (re, im)-f64 pair vectors (see ``_make_block_runner``);
    default: auto-detected from a pair-mode engine behind ``matvec``.

    ``checkpoint_path`` enables mid-solve checkpoint/resume (something the
    reference's PRIMME driver cannot do): every ``checkpoint_every``-th
    block boundary the live Krylov basis + recurrence state are written
    atomically, and a rerun with the same path, operator, and solver
    geometry resumes where it left off.  The checkpoint is keyed by the
    vector shape/dtype AND, when an engine is behind ``matvec``, by the
    operator itself (basis JSON + term tables), so a rerun against an
    edited Hamiltonian of the same size starts fresh instead of restoring
    a foreign Krylov state.  Bare callables are keyed by shape only —
    there, a fresh path per problem remains the caller's responsibility.
    In a multi-process run an ENGINE-backed solve checkpoints per shard
    (each rank atomically writes its addressable shards of every Krylov
    row + the replicated recurrence state to ``path.r<rank>``); bare
    callables have no per-shard layout and are ignored with a debug log.

    ``reorth`` picks the reorthogonalization policy (default: the
    ``lanczos_reorth`` config knob, ``"selective"``): ``"selective"`` runs
    each iteration's MGS pass against only a trailing window of recent
    vectors.  The window program evolves the ω-recurrence estimate beside
    the steps and ends itself at the step where it reaches √ε; the host,
    which evolves the same table over the (α, β) that came back and is the
    authority, KEEPS the steps before the crossing (sound by the rule that
    condemns the crossing one) and runs the rest of that block with the
    full sweep, so the Ritz check and every later block fall where they
    would have (window blocks never touch rows ≤ m, so dropping a step is
    free; an info-level ``solver_health`` event marks each trigger with
    the ``step`` within the block; the first block after a restart or
    resume is always full — the arrowhead coupling row must be projected
    out).  ``"full"`` is the pre-round-9 behavior: full MGS sweeps every
    iteration.

    ``root`` is the solve's open span (:func:`lanczos` passes it).  It
    takes the solve's counts as they happen, so a preempted solve's event
    has them too: ``steps_counted`` (iterations this call counted),
    ``steps_run`` (every step a block program reports it ran, a window
    block's crossing step included), ``omega_stops`` (window blocks that
    ended themselves early), ``steps_discarded`` (steps run and not kept:
    one a stop when host and device agree), ``probe_applies``,
    ``programs_built`` (block programs this call had to trace and compile
    or load; they live for the call) and ``programs_reused`` (1 where the
    thick restart's program, counted at the first restart, was built
    before the call: :func:`_make_restart` keeps it by geometry).  A
    block program's run is an ``iteration`` span, the full-sweep run of a
    stopped block's remainder carries ``redo=True`` with its own
    ``steps``, and restarts are on the result.
    Under the root the host loop is named where the device can wait for it:
    ``lanczos/start`` (start vector and probe apply; then the one program
    that makes the Krylov buffer, dispatched and not waited for),
    then per ``iteration`` ``lanczos/dispatch`` (``built`` says whether the
    block program was new to this call), ``lanczos/wait`` and
    ``lanczos/check`` (the recurrence's copies to the host, the ω tracker,
    the Ritz solve, the convergence test, the checkpoint),
    ``lanczos/restart`` and ``lanczos/epilogue``.
    """
    # Engines expose (apply_fn, operands) so the block runner can pass the
    # matrix tables as jit arguments; plain callables fall back to empty
    # operands (fine unless they close over very large device arrays).
    # Only the engine's own ``matvec`` method is substituted — any other
    # bound method (shifted/wrapped/global-layout variants) must keep its
    # semantics and goes through the generic fallback.
    owner = getattr(matvec, "__self__", None)
    if pair is None:
        pair = bool(getattr(owner, "pair", False))
    if getattr(owner, "mode", None) in ("streamed", "hybrid"):
        raise ValueError(
            "lanczos() traces the matvec into one jitted block program, "
            "which a streamed/hybrid engine cannot provide (its plan "
            "lives in host RAM and streams per apply) — streamed/hybrid "
            "engines are driven by the EAGER solver family instead: "
            "solve.lanczos_block (eigenpairs; multi-RHS block applies "
            "stream each plan chunk once per block, thick-restartable "
            "via max_basis_size), solve.kpm (Chebyshev/KPM spectral "
            "densities), and solve.evolve (Krylov exp(-iHt) time "
            "evolution)")
    if reorth is None:
        from ..utils.config import get_config
        reorth = get_config().lanczos_reorth
    if reorth not in ("selective", "full"):
        raise ValueError(
            f"unknown reorth policy {reorth!r} (use selective | full)")

    # every key of the solve's counts is on the root span's event, zeros
    # included
    root.add(steps_counted=0, steps_run=0, probe_applies=0,
             programs_built=0, programs_reused=0, omega_stops=0,
             steps_discarded=0)
    # lanczos/start: the start vector and the eager probe apply (and below,
    # once more: the start vector normalised and the one program that makes
    # the Krylov buffer around it)
    with obs_trace.span("lanczos/start", kind="phase"):
        if v0 is None:
            if n is None:
                raise ValueError("pass v0 or n")
            v0 = _rand_like((n, 2) if pair else (n,), np.float64, seed)
        elif pair and np.iscomplexobj(v0):
            # warm starts may arrive in complex form; the recurrence (and the
            # engine's bound apply_fn) runs on (re, im)-f64 pair vectors
            from ..ops.kernels import pair_from_complex
            v0 = pair_from_complex(np.asarray(v0))
        v = jnp.asarray(v0)
        shape = v.shape
        if pair and (len(shape) < 2 or shape[-1] != 2):
            raise ValueError(
                f"pair-mode Lanczos needs an [..., 2] (re, im) f64 start "
                f"vector (or complex v0), got shape {shape}")

        # Probe matvec once eagerly: fixes the recurrence dtype (a complex
        # Hermitian operator promotes a real start vector) and lets engines run
        # their first-apply counter checks outside of jit.
        w_probe = matvec(v)
        root.add(probe_applies=1)
        if isinstance(w_probe, tuple):
            w_probe = w_probe[0]
        dtype = jnp.promote_types(v.dtype, w_probe.dtype)
        del w_probe

    if (owner is not None and hasattr(owner, "bound_matvec")
            and getattr(matvec, "__func__", None)
            is getattr(type(owner), "matvec", None)):
        apply_fn, operands = owner.bound_matvec()
    else:
        apply_fn, operands = (lambda x, _ops: matvec(x)), ()

    def mv(x, ops):
        y = apply_fn(x, ops)
        return (y[0] if isinstance(y, tuple) else y).astype(dtype)

    mcap = max_basis_size or min(max(4 * k + 16, 96), max_iters + 1)
    mcap = max(mcap, k + 2)
    l_restart = min_restart_size or max(2 * k + 2, min(mcap // 3, 24))
    l_restart = int(np.clip(l_restart, k, mcap - 2))
    n_reorth = 2 if full_reorth else 1

    with obs_trace.span("lanczos/start", kind="phase"):
        nrm = jnp.sqrt(jnp.real(_vdot(v, v)))
        start = (v / nrm.astype(dtype)).astype(dtype)
        make_buffer, set_row = _buffer_programs(mcap, start)
        V = make_buffer(start)
        alph_d = _recurrence_zeros(mcap, start)
        bet_d = _recurrence_zeros(mcap, start)
        del start                   # row 0 holds it now
        # the Krylov buffer is the solver's whole device footprint —
        # register it in the memory ledger for the solve's lifetime, with
        # what each device holds of it (released at normal completion; a
        # failed solve keeps the entry live, which is what an OOM forensics
        # report should show)
        mem_h = obs_memory.NULL_HANDLE
        if obs_enabled():
            mem_h = obs_memory.track_tree(
                f"solver/{obs_memory.next_instance('lanczos')}/krylov_basis",
                (V, alph_d, bet_d), rows=int(_buffer_rows(mcap)))
        # the buffer's program dispatched behind the probe apply, before
        # any block program: in use are what is resident, the probe apply's
        # vectors and ONE buffer (each chip's share of it on a mesh), with
        # nothing of a buffer's size owned by nobody.  Not waited for: a
        # wait here would hold the host's build of the block programs back
        # behind the probe apply (0.45 s a solve at chain_32_symm: PERF.md,
        # PR 37).  The allocator counts a buffer when its program is
        # dispatched, so the sample reads it all the same
        obs_memory.sample_watermark("lanczos/start")

    # Block programs compiled lazily: ONE full-sweep runner (dynamic step
    # count) and, in selective mode, a window runner per distinct block
    # length (scan needs a static length; a solve sees only a handful).  A
    # selective solve that never trips the ω gate compiles only the cheap
    # window program(s).
    _runners: dict = {}

    def run_steps(full_pass: bool, V, alph, bet, m, nsteps, operands):
        """Dispatch one block program: ``(V, alph, bet, ran)``, ``ran`` the
        device's count of the steps it ran (a window block may end itself
        short of ``nsteps``)."""
        key = "full" if full_pass else ("window", int(nsteps))
        built = key not in _runners
        # lanczos/dispatch: until the block program's call returns.  A
        # program new to this call is traced, lowered and compiled or
        # loaded from the persistent cache inside that call
        with obs_trace.span("lanczos/dispatch", kind="phase",
                            built=built, full=bool(full_pass),
                            steps=int(nsteps)):
            root.add(programs_built=int(built))
            if full_pass:
                if built:
                    _runners[key] = _make_block_runner(
                        mv, mcap, shape, dtype, n_reorth, pair=pair)
                return _runners[key](V, alph, bet, jnp.int32(m),
                                     jnp.int32(nsteps), operands)
            if built:
                _runners[key] = _make_window_runner(
                    mv, mcap, shape, dtype, n_reorth, int(nsteps),
                    pair=pair)
            return _runners[key](V, alph, bet, jnp.int32(m),
                                 omega_tr.state, operands)

    hits = _make_restart.cache_info().hits
    restart_fn = _make_restart(mcap, shape, dtype, l_restart)
    # counted at the first restart: a solve that never restarts runs none
    restart_kept = _make_restart.cache_info().hits > hits

    lock_theta = np.zeros(0)
    lock_sigma = np.zeros(0)
    m = 0                       # live basis: V[0..m] (m completed steps)
    total_iters = 0
    converged = False
    theta = S = res = None

    # keyed by the vector space AND (when an engine is behind the matvec)
    # the operator itself — NOT by solver geometry, so a rerun with a
    # different max_iters / basis bound still resumes (the saved rows are
    # valid in any buffer that fits them), but a rerun against an EDITED
    # Hamiltonian with the same lattice size (same shape) refuses the
    # foreign Krylov state instead of silently restoring it.  Bare
    # callables fall back to shape-only keying (documented caller
    # responsibility).
    #
    # Engine-backed hashed solves key TOPOLOGY-FREE (lanczos-v3): the
    # (D, M) layout dims are deliberately out of the fingerprint — the
    # operator key + row tail identify the vector SPACE — so a checkpoint
    # written at D devices is FOUND at D′ and resharded on restore
    # (parallel/reshard.py).  The legacy shape-keyed v2 fingerprint is
    # still probed on restore, so pre-elastic fixed-D checkpoints resume
    # unchanged on a matching device count.
    hashed_layout = _sharded_ckpt_engine(owner, shape)
    if hashed_layout:
        ckpt_fp = (f"hashed{tuple(shape[2:])}|{np.dtype(dtype).str}"
                   f"|{_operator_key(owner)}|lanczos-v3")
        legacy_fp = (f"{tuple(shape)}|{np.dtype(dtype).str}"
                     f"|{_operator_key(owner)}|lanczos-v2")
    else:
        ckpt_fp = (f"{tuple(shape)}|{np.dtype(dtype).str}"
                   f"|{_operator_key(owner)}|lanczos-v2")
        legacy_fp = None
    resumed_from = 0
    multi = jax.process_count() > 1
    # Multi-process checkpointing needs a per-shard vector format (no rank
    # can fetch the global Krylov basis): available for engine-backed
    # matvecs over hashed [D, M(, 2)] vectors; bare callables stay
    # single-controller-only.
    sharded_ckpt = multi and hashed_layout
    if checkpoint_path and multi and not sharded_ckpt:
        from ..utils.logging import log_debug
        log_debug("lanczos checkpointing disabled: multi-process run with "
                  "a non-engine matvec (no per-shard vector layout)")
        checkpoint_path = None
    if checkpoint_path:
        got = _restore_ckpt(checkpoint_path, ckpt_fp, owner, shape,
                            sharded=sharded_ckpt, legacy_fp=legacy_fp,
                            dtype=np.dtype(dtype))
        if sharded_ckpt and (owner is None
                             or bool(getattr(owner, "_multi", True))):
            # Per-rank checkpoint files are written without a barrier, so
            # ranks can observe different generations (or one none at all).
            # Resuming from mixed states would desynchronize the SPMD
            # collective programs — agree on (m, total_iters) and start
            # fresh everywhere unless every rank restored the same state.
            # Rank-local-mesh engines (_multi False) skip the agreement:
            # their solves are process-local.  For a TRUE process-spanning
            # engine a FAILED agreement collective propagates and kills
            # the rank — deliberately NOT the local-fallback arm
            # agree_restored uses for plan caches.  There a rebuild is
            # bit-identical to a restore, so a locally-kept verdict is
            # harmless; here fresh and resumed solver states genuinely
            # differ, and a rank deciding "fresh" locally while a peer's
            # allgather succeeded (it contributed our token before we
            # raised) would desynchronize the very SPMD programs this
            # agreement exists to protect.  Any backend that can run a
            # process-spanning engine can run this collective.
            from jax.experimental import multihost_utils as _mhu
            tok = np.array([got["m"], got["total_iters"]]
                           if got is not None else [-1, -1], np.int64)
            all_tok = _mhu.process_allgather(tok)
            if not (all_tok >= 0).all() or \
                    not (all_tok == all_tok[0]).all():
                if got is not None:
                    from ..utils.logging import log_debug
                    log_debug("lanczos checkpoint generations disagree "
                              "across ranks; starting fresh")
                got = None
        if got is not None:
            rows = int(got["m"]) + 1
            if rows > _buffer_rows(mcap) or int(got["m"]) > mcap:
                from ..utils.logging import log_debug
                log_debug("lanczos checkpoint basis exceeds max_basis_size; "
                          "starting fresh")
            else:
                # each row through one program that donates the buffer:
                # an eager update would copy the whole of it a row
                for i, row in enumerate(got["V_rows"]):
                    V = set_row(V, i, row)
                na = min(int(got["m"]), mcap)
                alph_d = alph_d.at[:na].set(
                    jnp.asarray(got["alph"][:na]))
                bet_d = bet_d.at[:na].set(jnp.asarray(got["bet"][:na]))
                lock_theta = np.asarray(got["lock_theta"])
                lock_sigma = np.asarray(got["lock_sigma"])
                m = int(got["m"])
                total_iters = resumed_from = int(got["total_iters"])
    blocks_done = 0

    if m:
        # Rayleigh-Ritz on the restored state up front: a resume whose
        # budget is already spent still returns the checkpointed estimates
        # (and may exit converged immediately) instead of empty arrays
        alph = np.asarray(alph_d)
        bet = np.asarray(bet_d)
        kk = min(k, m)
        T = _projected_matrix(alph, bet, lock_theta, lock_sigma, m)
        theta, S = eigh(T, subset_by_index=(0, kk - 1))
        res = np.abs(bet[m - 1] * S[m - 1, :])
        if m >= k and np.all(res < tol * np.maximum(1.0, np.abs(theta))):
            converged = True

    import time as _time

    first_block_s = 0.0
    first_block_iters = 0
    steady_s = 0.0
    n_restarts = 0
    watchdog = _Watchdog("lanczos")
    preempt.ensure_installed()
    # the preemption latch needs cross-rank agreement only when the
    # solve's collectives actually span processes — a rank-local-mesh
    # engine in a multi-process job preempts independently
    agree_multi = multi and (owner is None
                             or bool(getattr(owner, "_multi", True)))
    obs_emit("solver_start", solver="lanczos", k=int(k),
             max_iters=int(max_iters), tol=float(tol), pair=bool(pair),
             max_basis_size=int(mcap), resumed_from=int(resumed_from),
             reorth=str(reorth))
    if m and theta is not None:
        _emit_trace("lanczos", total_iters, m, theta, res)

    # Selective-reorth state: the accumulated ω table, and whether the
    # NEXT block must run the full sweep.  The first block after a resume
    # (m > 0: the checkpointed basis's ω history is unknown) and after
    # every thick restart (the arrowhead coupling row must be projected
    # out of w = H·v_l against ALL locked rows) is always full.
    selective = reorth == "selective"
    omega_tr = _OmegaTracker(mcap) if selective else None
    pending_full = bool(m)
    if selective:
        # warm the dynamic-step full runner with a ZERO-step call: short
        # remainder blocks, restarts, and ω fallbacks then reuse its
        # compiled program instead of landing a compile inside the
        # steady-rate window (the window program compiles in the first —
        # rate-excluded — block)
        V, alph_d, bet_d, _ = run_steps(True, V, alph_d, bet_d, m, 0,
                                        operands)

    # steps of the current block kept from its window program: non-zero
    # only on the pass that runs the rest of that block under the full
    # sweep, after the ω gate stopped it
    head = 0
    redo = False
    while total_iters < max_iters and not converged:
        if m == mcap:
            with obs_trace.span("lanczos/restart", kind="phase"):
                # Thick restart at the TOP of the loop (a resumed checkpoint
                # may arrive with a full buffer): keep the l lowest Ritz
                # vectors + the residual vector; the projection becomes
                # arrowhead + tridiagonal.
                alph = np.asarray(alph_d)
                bet = np.asarray(bet_d)
                T = _projected_matrix(alph, bet, lock_theta, lock_sigma, m)
                l = l_restart   # clipped to <= mcap-2 at setup; restart_fn
                theta_all, S_all = eigh(T)   # hard-codes the residual row at l
                if n_restarts == 0:
                    root.add(programs_reused=int(restart_kept))
                V = restart_fn(V, jnp.asarray(S_all[:, :l]))
                lock_theta = theta_all[:l].copy()
                lock_sigma = bet[m - 1] * S_all[m - 1, :l]
                m = l
                pending_full = True
                n_restarts += 1
                # the restart's program is in flight
                obs_memory.sample_watermark("lanczos/restart")
        nsteps = min(check_every, mcap - m, max_iters - total_iters)
        # tiny remainder stubs (< half a block) reuse the prewarmed
        # dynamic-step full runner: a fresh window program would spend
        # more wall on its compile than the handful of iterations saves.
        # Half-block-or-larger lengths get window programs — pre-restart
        # remainders recur every restart cycle, so their one compile
        # amortizes.
        used_full = (not selective or pending_full
                     or nsteps < max(check_every // 2, 1))
        pending_full = False
        if not redo:
            t0 = _time.perf_counter()
        # iteration span: one block program's run (the applies run INSIDE
        # the jitted program, so this is the finest host-visible iteration
        # granule here) and the host's check of it, which runs to the end
        # of the loop body: the stack closes ``lanczos/check`` and then the
        # iteration.  A convergence-check block of nsteps Lanczos steps is
        # one of them, or two where the ω gate stopped its window program:
        # ``redo`` marks the full-sweep run of the rest of that block
        with contextlib.ExitStack() as block:
            block.enter_context(obs_trace.span(
                "iteration", kind="iteration", solver="lanczos",
                iter=int(total_iters + head), steps=int(nsteps - head),
                **({"redo": True} if redo else {})))
            V, alph_d, bet_d, ran = run_steps(
                used_full, V, alph_d, bet_d, m + head, nsteps - head,
                operands)
            with obs_trace.span("lanczos/wait", kind="phase"):
                jax.block_until_ready(V)   # one collective program in flight
                # what stays on the chip between block programs, and how
                # far the one just run pushed the peak
                obs_memory.sample_watermark("lanczos/wait", synced=True)
            block.enter_context(
                obs_trace.span("lanczos/check", kind="phase"))
            # what the program reports it ran, the crossing step included
            ran = int(ran)
            root.add(steps_run=ran)
            if selective and not used_full:
                om_acc = omega_tr.advance(np.asarray(alph_d),
                                          np.asarray(bet_d), m + ran)
                if omega_tr.m < m + nsteps:
                    # ω reached √ε inside the window block (or the device's
                    # copy of the estimate did, and stopped it): from the
                    # step the host's table stands at, semiorthogonality is
                    # no longer guaranteed and cannot be repaired after the
                    # fact.  The steps before it are sound by the same rule
                    # and are kept; the block only WROTE rows above them, so
                    # the rest of it runs under the full sweep on the loop's
                    # next pass.  The host's reading wins a disagreement: a
                    # step the device ran on is dropped, one it stopped
                    # short of runs under the full sweep.
                    # level "info": a trigger near convergence is the scheme
                    # WORKING (loss grows exactly as Ritz pairs converge),
                    # not a health problem — the zero-warning gate of `make
                    # health-check` must not fail a healthy converged solve
                    head = omega_tr.m - m
                    root.add(omega_stops=int(ran < nsteps),
                             steps_discarded=ran - head)
                    obs_emit("solver_health",
                             check="selective_reorth_fallback", level="info",
                             solver="lanczos", iter=int(total_iters + nsteps),
                             step=int(head), omega=float(om_acc))
                    pending_full = redo = True
                    continue
            head, redo = 0, False
            dt = _time.perf_counter() - t0
            if first_block_iters == 0:
                first_block_s, first_block_iters = dt, nsteps
            else:
                steady_s += dt
            alph = np.asarray(alph_d)
            bet = np.asarray(bet_d)
            m += nsteps
            total_iters += nsteps
            root.add(steps_counted=int(nsteps))

            # Breakdown: a ~zero β means the Krylov space closed at that step;
            # discard the garbage steps after it.
            lo = len(lock_theta)
            broke = None
            for i in range(max(lo, m - nsteps), m):
                if bet[i] < 1e-14:
                    broke = i
                    break
            if broke is not None:
                m = broke + 1

            if selective and used_full:
                # the full sweep left every new vector orthogonal to the
                # whole live basis — the ω table restarts at roundoff
                omega_tr.reset(m)

            kk = min(k, m)
            T = _projected_matrix(alph, bet, lock_theta, lock_sigma, m)
            theta, S = eigh(T, subset_by_index=(0, kk - 1))
            res = np.abs(bet[m - 1] * S[m - 1, :])
            omega = obs_health.omega_estimate(
                alph, bet, max(lo, m - nsteps), m) \
                if obs_health.probes_enabled() else None
            _emit_trace("lanczos", total_iters, m, theta, res, omega)
            if m >= k and np.all(res < tol * np.maximum(1.0, np.abs(theta))):
                converged = True
                break
            watchdog.report_omega(omega, total_iters)
            if broke is not None:
                # Krylov space closed without meeting the tolerance
                watchdog.breakdown(total_iters, float(bet[broke]),
                                   converged=False)
                break
            watchdog.check_stagnation(res, total_iters)

            blocks_done += 1
            # chaos site at the block boundary: `delay=` stretches a solve so
            # the chaos gate can land a kill mid-iteration deterministically;
            # inert (shared no-op) when DMT_FAULT is unset
            faults.check("solver_block", exc=RuntimeError, solver="lanczos",
                         iter=int(total_iters))
            # safe point: the recurrence state is host-consistent and no
            # collective is in flight — the latch verdict is agreed across
            # ranks so every rank checkpoints the SAME generation and exits
            # together (DESIGN.md §21).  ckpt_meta (four D2H fetches) is built
            # only when a save actually happens — the plain hot loop pays
            # nothing here.
            cadence_due = bool(checkpoint_path) \
                and blocks_done % max(checkpoint_every, 1) == 0
            preempted = preempt.agreed(agree_multi)
            if cadence_due or (preempted and checkpoint_path):
                _soft_save_ckpt(
                    checkpoint_path, ckpt_fp, owner, V, {
                        "alph": np.asarray(alph_d), "bet": np.asarray(bet_d),
                        "lock_theta": np.asarray(lock_theta),
                        "lock_sigma": np.asarray(lock_sigma),
                        "m": int(m), "total_iters": int(total_iters)},
                    m, sharded_ckpt,
                    reason="cadence" if cadence_due else "preempt")
            if preempted:
                obs_emit("solver_preempted", solver="lanczos",
                         iters=int(total_iters),
                         checkpoint=checkpoint_path or "")
                obs_flush()
                mem_h.release()
                raise preempt.Preempted("lanczos", total_iters,
                                        checkpoint_path)

    kk = min(k, m)
    evecs = None
    if compute_eigenvectors and m:
        with obs_trace.span("lanczos/epilogue", kind="phase"):
            Vf = V.reshape((_buffer_rows(mcap),) + _row_shape(shape))
            Sj = jnp.asarray(S[:, :kk].astype(
                np.complex128
                if np.issubdtype(np.dtype(dtype), np.complexfloating)
                else np.float64), dtype=dtype)
            E = _combine_rows(Sj, Vf)              # the first m rows of Vf
            evecs = []
            dot = _pair_dot if pair else _vdot
            for i in range(kk):
                e = E[i]
                enrm = jnp.sqrt(jnp.real(dot(e, e)))
                evecs.append((e / enrm.astype(dtype)).reshape(shape))
            # the combination and the norms are in flight
            obs_memory.sample_watermark("lanczos/epilogue")
    obs_emit("solver_end", solver="lanczos", iters=int(total_iters),
             converged=bool(converged),
             eigenvalues=[float(t) for t in np.atleast_1d(theta)[:kk]]
             if theta is not None else [])
    mem_h.release()
    return LanczosResult(
        eigenvalues=np.asarray(theta[:kk]) if theta is not None
        else np.zeros(0),
        eigenvectors=evecs,
        residual_norms=np.asarray(res[:kk]) if res is not None
        else np.zeros(0),
        num_iters=total_iters,
        resumed_from=resumed_from,
        converged=converged,
        first_block_seconds=first_block_s,
        first_block_iters=first_block_iters,
        steady_seconds=steady_s,
        restarts=n_restarts,
    )
