#!/usr/bin/env python
"""Diagonalize: YAML model in → lowest-k eigenpairs + residuals out (HDF5).

The driver app — reference parity with ``bin/Diagonalize``
(``/root/reference/src/Diagonalize.chpl:258-332``):

  1. load the YAML config (basis + hamiltonian [+ observables]),
  2. build or *restore* the representative set from the output file
     (checkpoint semantics of ``makeBasisStates``, Diagonalize.chpl:227-246),
  3. run the eigensolver (Lanczos, or LOBPCG with --block) over the jitted
     engine — single device or an n-device mesh (--devices),
  4. save eigenvalues/eigenvectors/residuals into the output HDF5
     (Diagonalize.chpl:248-256) and print a summary (+ observable expectation
     values when requested).

Flags mirror the reference's config consts (Diagonalize.chpl:164-172).

Usage:
    python apps/diagonalize.py model.yaml -o out.h5 -k 2 --tol 1e-10
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import numpy as np

from distributed_matvec_tpu.utils.cache import enable_compilation_cache

enable_compilation_cache()


def main(argv=None, inspect=None):
    """Run the app.  ``inspect``, when given, is called once after the solve
    with a namespace (``engine``, ``config``, ``eigenvalues``, ``residuals``,
    ``eigenvectors``, ``iterations``, ``solve_seconds``, ``timer``) while the
    engine and the solver's vectors are still alive — ``chip_smoke.py`` checks
    where they live and applies the engine once more through it."""
    # root run span: every event of the run (engine builds, solver
    # iterations, applies, the save epilogue) becomes a descendant of one
    # `diagonalize` span, and the trace-id stamp resolves lazily AFTER
    # _main() points obs at the run directory — the span event itself is
    # written by the line-buffered sink + atexit flush backstop
    from distributed_matvec_tpu.obs import trace as _trace

    with _trace.span("diagonalize", kind="run"):
        return _main(argv, inspect)


def _main(argv=None, inspect=None):
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n")[0],
        epilog="exit codes: 0 solved, 2 bad config/arguments, "
               "75 preempted (SIGTERM/SIGINT latched; a checkpoint was "
               "written at the last safe point — relaunch the SAME argv "
               "to resume; apps/solve_service.py uses the same code when "
               "draining), 76 stalled (a wedged peer rank tripped the "
               "heartbeat watchdog).  A supervisor should retry 75/76 "
               "and treat other nonzero codes as permanent.  "
               "Solver kinds served over the same engines: eigs "
               "(lowest-k eigenpairs — this app, and the JobSpec "
               "default for --submit), kpm (Chebyshev/KPM spectral "
               "densities) and evolve (Krylov exp(-iHt) time "
               "evolution) — the dynamics kinds run via "
               "apps/dynamics.py (same 75/76 contract) or a JobSpec "
               "with solver=kpm|evolve through the solve service "
               "(DESIGN.md §29).")
    ap.add_argument("input", help="YAML config (data/*.yaml schema)")
    ap.add_argument("-o", "--output", default=None,
                    help="output HDF5 (default: <input>.h5); also the "
                         "representative checkpoint (kOutput)")
    ap.add_argument("-k", "--num-evals", type=int, default=1,
                    help="number of eigenpairs (numEvals)")
    ap.add_argument("--tol", type=float, default=1e-10,
                    help="residual tolerance (kEps)")
    ap.add_argument("--max-iters", type=int, default=1000,
                    help="total Lanczos iteration cap")
    ap.add_argument("--max-basis-size", type=int, default=None,
                    help="Krylov basis bound before a thick restart "
                         "(kMaxBasisSize)")
    ap.add_argument("--min-restart-size", type=int, default=None,
                    help="Ritz vectors kept at a restart (kMinRestartSize)")
    ap.add_argument("--devices", type=int, default=0,
                    help="shard over an n-device mesh (0 = single device)")
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                    help="multi-host: jax.distributed coordinator address "
                         "(the GASNet-substrate analog; omit for "
                         "single-host or cluster auto-detection)")
    ap.add_argument("--num-processes", type=int, default=None,
                    help="multi-host: total process count")
    ap.add_argument("--process-id", type=int, default=None,
                    help="multi-host: this process's rank")
    ap.add_argument("--shards", default=None, metavar="SHARDS_H5",
                    help="construct the engine from a sharded-enumeration "
                         "file (tools/sharded_enum_scale.py) — the global "
                         "representative array is never built; the solve "
                         "stays in hashed space and eigenvectors are saved "
                         "per shard (vector_shards/eigenvector_<i>)")
    ap.add_argument("--mode", choices=("ell", "compact", "streamed",
                                       "fused", "hybrid"),
                    default=None,
                    help="engine mode: precomputed structure (ell, the "
                         "default), 4 B/entry for isotropic real sectors "
                         "(compact), the structure resolved once into a "
                         "host-RAM plan streamed per apply (streamed — "
                         "fused-level device memory, no per-apply orbit "
                         "scan; solved via the eager block-Lanczos), "
                         "recompute-on-the-fly (fused — the default with "
                         "--shards; plan builds also work shard-native, "
                         "streaming peer shards from the file, and are "
                         "worth their one-time cost for long solves), or "
                         "the per-term recompute-vs-stream split priced "
                         "by the calibrated cost model (hybrid — the "
                         "DMT_HYBRID knob picks the split policy; solved "
                         "via the eager block-Lanczos like streamed)")
    ap.add_argument("--block", action="store_true",
                    help="use LOBPCG (blocked) instead of Lanczos")
    ap.add_argument("--solver-checkpoint", default=None, metavar="CKPT_H5",
                    help="mid-solve Lanczos/LOBPCG checkpoint/resume file "
                         "(beyond the reference: PRIMME state is never "
                         "saved there); a rerun with the same config "
                         "resumes where it stopped — including after a "
                         "preemption exit (code 75)")
    ap.add_argument("--checkpoint-every", type=int, default=4,
                    help="solver-checkpoint cadence in convergence-check "
                         "blocks (each block is check_every=16 iterations; "
                         "the write costs a basis fetch, so raise this at "
                         "large N)")
    ap.add_argument("--no-eigenvectors", action="store_true",
                    help="skip eigenvector computation/saving")
    ap.add_argument("--observables", action="store_true",
                    help="evaluate ⟨ψ|O|ψ⟩ for YAML observables")
    ap.add_argument("--timings", action="store_true",
                    help="print phase timings (kDisplayTimings)")
    ap.add_argument("--obs-dir", default=None, metavar="DIR",
                    help="telemetry run directory (sets obs_dir / "
                         "DMT_OBS_DIR): engine-init splits, solver "
                         "convergence traces, rank-tagged apply events, and "
                         "phase timings stream to DIR/rank_<r>/events.jsonl "
                         "for tools/obs_report.py (merge / report --ranks "
                         "for multi-rank runs)")
    ap.add_argument("--job-id", default=None, metavar="ID",
                    help="job-namespacing id stamped into every telemetry "
                         "event (DMT_JOB_ID; default: the run's trace id) "
                         "— lets a scheduler multiplexing many concurrent "
                         "solves filter one job's events/spans out of a "
                         "shared stream (obs_report watch/trace read it)")
    ap.add_argument("--submit", action="store_true",
                    help="do not solve inline: enqueue this run as a job "
                         "spec in --serve-dir's spool for a running solve "
                         "service (apps/solve_service.py) and exit 0; the "
                         "service batches same-basis submissions through "
                         "one warm engine and writes the result to "
                         "<serve-dir>/done/<job_id>.json")
    ap.add_argument("--serve-dir", default=None, metavar="DIR",
                    help="solve-service spool directory for --submit "
                         "(created if missing)")
    ap.add_argument("--health", choices=("on", "strict", "off"),
                    default=None,
                    help="numerical-health watchdog (DMT_HEALTH): on = "
                         "log-and-continue (default), strict = critical "
                         "conditions (NaN/Inf outputs, exchange overflow, "
                         "Lanczos breakdown) raise HealthError, off = no "
                         "probes")
    args = ap.parse_args(argv)
    if args.mode is None:
        args.mode = "fused" if args.shards else "ell"

    if args.submit:
        # enqueue-and-exit: no engine, no solve, no JAX backend touch —
        # the job spec carries everything the service needs to rebuild
        # the model (the yaml path) and shape the engine
        if not args.serve_dir:
            print("--submit needs --serve-dir DIR (the service's spool)",
                  file=sys.stderr)
            return 2
        if args.shards or args.block:
            print("--submit covers single-operator Lanczos jobs; "
                  "--shards/--block runs stay inline", file=sys.stderr)
            return 2
        import uuid

        from distributed_matvec_tpu.serve import JobSpec, submit_to_spool

        job_id = args.job_id or f"cli-{uuid.uuid4().hex[:10]}"
        spec = JobSpec(job_id=job_id, yaml=os.path.abspath(args.input),
                       k=args.num_evals, tol=args.tol,
                       max_iters=args.max_iters, mode=args.mode,
                       n_devices=args.devices)
        path = submit_to_spool(args.serve_dir, spec)
        print(f"submitted job {job_id} -> {path}")
        print(f"result will land at "
              f"{os.path.join(args.serve_dir, 'done', job_id + '.json')}")
        return 0

    from distributed_matvec_tpu import obs
    from distributed_matvec_tpu.io import (
        make_or_restore_representatives, save_eigen)
    from distributed_matvec_tpu.models.yaml_io import load_config_from_yaml
    from distributed_matvec_tpu.solve import lanczos, lobpcg
    from distributed_matvec_tpu.utils.config import update_config
    from distributed_matvec_tpu.utils.timers import TreeTimer

    if args.obs_dir:
        update_config(obs_dir=args.obs_dir)
    if args.job_id:
        # env AND config, same both-or-neither contract as --health: an
        # inherited DMT_JOB_ID must not outrank the id requested on the
        # command line, and child processes must inherit it
        os.environ["DMT_JOB_ID"] = args.job_id
        update_config(job_id=args.job_id)
    if args.health:
        # the env var outranks the config field (per-subprocess override
        # contract), so the CLI must set BOTH or an inherited DMT_HEALTH
        # would silently drop the mode requested on the command line
        os.environ["DMT_HEALTH"] = args.health
        update_config(health=args.health)

    if args.coordinator or args.num_processes:
        from distributed_matvec_tpu.parallel.mesh import init_distributed
        init_distributed(coordinator_address=args.coordinator,
                         num_processes=args.num_processes,
                         process_id=args.process_id)
    if args.timings:
        update_config(display_timings=True)
    # preemption latch BEFORE any long-running phase: a SIGTERM during the
    # basis/engine build still latches, and the solve exits at its first
    # safe point with a checkpoint + EXIT_PREEMPTED (resume = same argv)
    import signal as _signal

    from distributed_matvec_tpu.utils import preempt as _preempt
    from distributed_matvec_tpu.utils.preempt import (EXIT_PREEMPTED,
                                                      Preempted)
    # a batch driver opts Ctrl-C into the latch too (library solves
    # install SIGTERM only, keeping interactive KeyboardInterrupt alive)
    _preempt.ensure_installed(signals=(_signal.SIGTERM, _signal.SIGINT))
    import jax
    # multi-controller: every rank computes, rank 0 owns the output file
    # (the reference's locale-0 I/O role, MyHDF5.chpl:215-252)
    rank0 = jax.process_index() == 0
    out = args.output or os.path.splitext(args.input)[0] + ".h5"
    # cross-rank heartbeat watchdog (DMT_HEARTBEAT_S > 0): a hung peer
    # becomes a stall_report + EXIT_STALLED instead of an infinite
    # all_to_all wait
    watchdog = None
    from distributed_matvec_tpu.utils.config import get_config
    _cfg = get_config()
    if _cfg.heartbeat_s > 0 and jax.process_count() > 1:
        from distributed_matvec_tpu.parallel.heartbeat import (
            HeartbeatWatchdog)
        hb_dir = args.obs_dir or os.path.dirname(os.path.abspath(out))
        watchdog = HeartbeatWatchdog(
            hb_dir, interval_s=_cfg.heartbeat_s,
            timeout_s=_cfg.heartbeat_timeout_s).start()
    timer = TreeTimer("diagonalize")
    obs.emit("run_start", app="diagonalize", input=args.input, output=out,
             k=args.num_evals, devices=args.devices,
             mode=args.mode, block=bool(args.block))

    with timer.scope("load_config"):
        cfg = load_config_from_yaml(args.input, hamiltonian=True,
                                    observables=args.observables)
    if cfg.hamiltonian is None:
        print("config has no hamiltonian section", file=sys.stderr)
        return 2

    if args.shards:
        with timer.scope("engine"):
            from distributed_matvec_tpu.parallel.distributed import (
                DistributedEngine)
            eng = DistributedEngine.from_shards(
                cfg.hamiltonian, args.shards,
                n_devices=args.devices or None, mode=args.mode)
            v0 = eng.random_hashed(seed=42)
        n = eng.n_states
        print(f"basis: N={n} states (shard-native from {args.shards})")
    else:
        with timer.scope("basis"):
            # every rank restores from the same checkpoint (agreement even
            # against a stale file); only rank 0 writes it
            restored = make_or_restore_representatives(cfg.basis, out,
                                                       save=rank0)
        n = cfg.basis.number_states
        print(f"basis: N={n} states "
              f"({'restored from' if restored else 'checkpointed to'} {out})")

    if args.mode in ("streamed", "hybrid"):
        # fail BEFORE the engine pays the plan-resolution cost: pair-form
        # sectors (complex characters on a TPU mesh) have no in-tree
        # streamed solver — lanczos() cannot trace a streamed engine and
        # lanczos_block() has no J-aware reorthogonalization
        from distributed_matvec_tpu.parallel.engine import use_pair_complex
        if (not cfg.hamiltonian.effective_is_real) and use_pair_complex():
            print(f"--mode {args.mode} does not support pair-form complex "
                  "sectors (no streamed-compatible solver handles the "
                  "J-aware recurrence); use --mode ell/fused, or run the "
                  "sector native-c128 on CPU", file=sys.stderr)
            return 2

    with timer.scope("engine"):
        if args.shards:
            pass                              # engine built above
        elif (args.devices and args.devices > 1) \
                or args.mode in ("streamed", "hybrid"):
            from distributed_matvec_tpu.parallel.distributed import (
                DistributedEngine)
            # streamed/hybrid live on DistributedEngine; without
            # --devices they run the documented single-device form
            eng = DistributedEngine(cfg.hamiltonian,
                                    n_devices=args.devices or 1,
                                    mode=args.mode)
            v0 = eng.random_hashed(seed=42)
        else:
            from distributed_matvec_tpu.parallel.engine import LocalEngine
            eng = LocalEngine(cfg.hamiltonian, mode=args.mode)
            v0 = None

    from distributed_matvec_tpu.utils.profiling import maybe_profile

    resumed_from = restarts = 0
    try:
        with timer.scope("solve"), maybe_profile():
            t0 = time.perf_counter()
            if args.block:
                if jax.process_count() > 1 \
                        and not hasattr(eng, "from_hashed"):
                    print("--block (LOBPCG) in a multi-process run needs a "
                          "distributed engine (--devices or --shards)",
                          file=sys.stderr)
                    return 2
                evals, evecs_cols, iters = lobpcg(
                    eng.matvec, n, k=args.num_evals, tol=args.tol,
                    max_iters=args.max_iters,
                    checkpoint_path=args.solver_checkpoint,
                    # the flag counts Lanczos convergence-check blocks of
                    # check_every=16 iterations; LOBPCG segments count
                    # iterations directly, so scale for a comparable cadence
                    checkpoint_every=max(args.checkpoint_every, 1) * 16)
                # lobpcg returns block-order columns for both engines;
                # route the residual matvec through the block-facing entry
                # point
                mv_block = getattr(eng, "matvec_global", None) \
                    or (lambda v: np.asarray(eng.matvec(v)))
                evecs = [evecs_cols[:, i]
                         for i in range(evecs_cols.shape[1])]
                residuals = np.array([
                    float(np.linalg.norm(mv_block(v) - w * np.asarray(v)))
                    for w, v in zip(evals, evecs)])
                niter = iters
                # lobpcg's 3-tuple API carries no resume count — surface
                # the solver_resume event so a relaunched run prints the
                # same confirmation line Lanczos does
                resumed = [e for e in obs.events("solver_resume")
                           if e.get("solver") == "lobpcg"]
                if resumed:
                    resumed_from = int(resumed[-1]["iters"])
            elif args.mode in ("streamed", "hybrid"):
                # a streamed/hybrid engine cannot be traced into the
                # single-program Lanczos block runner — drive it with the
                # eager block solver (each k-column block streams the plan
                # once)
                from distributed_matvec_tpu.solve import lanczos_block
                if args.solver_checkpoint:
                    print("warning: --solver-checkpoint applies to the "
                          "single-vector Lanczos and LOBPCG; "
                          "streamed-mode block solves exit cleanly on "
                          "preemption but are not checkpointed",
                          file=sys.stderr)
                res = lanczos_block(eng.matvec, k=args.num_evals,
                                    tol=args.tol, max_iters=args.max_iters,
                                    seed=42,
                                    compute_eigenvectors=not
                                    args.no_eigenvectors)
                evals, residuals, niter = (res.eigenvalues,
                                           res.residual_norms,
                                           res.num_iters)
                evecs = res.eigenvectors
                if not res.converged:
                    print("warning: solver did not converge",
                          file=sys.stderr)
            else:
                res = lanczos(eng.matvec, n=None if v0 is not None else n,
                              v0=v0, k=args.num_evals, tol=args.tol,
                              max_iters=args.max_iters,
                              max_basis_size=args.max_basis_size,
                              min_restart_size=args.min_restart_size,
                              checkpoint_path=args.solver_checkpoint,
                              checkpoint_every=args.checkpoint_every,
                              compute_eigenvectors=not args.no_eigenvectors)
                evals, residuals, niter = (res.eigenvalues,
                                           res.residual_norms,
                                           res.num_iters)
                evecs = res.eigenvectors
                resumed_from, restarts = res.resumed_from, res.restarts
                if not res.converged:
                    print("warning: solver did not converge",
                          file=sys.stderr)
            dt = time.perf_counter() - t0
    except Preempted as e:
        # checkpoint-and-exit: the solver already wrote a generation-agreed
        # checkpoint (when configured) and flushed its events; close the
        # run's telemetry and hand the supervisor the distinct exit code —
        # a relaunch with the SAME argv resumes from the checkpoint
        print(f"preempted: {e}", file=sys.stderr)
        obs.emit("run_preempted", app="diagonalize", solver=e.solver,
                 iters=int(e.iters), checkpoint=e.checkpoint_path or "",
                 exit_code=EXIT_PREEMPTED)
        timer.emit(app="diagonalize")
        obs.emit("metrics_snapshot", metrics=obs.snapshot())
        obs.flush()
        if watchdog is not None:
            watchdog.stop()
        return EXIT_PREEMPTED
    if resumed_from:
        print(f"solver: resumed from {resumed_from} checkpointed "
              "iterations")
    print(f"solver: {niter} iterations in {dt:.2f}s "
          f"({niter / max(dt, 1e-9):.2f} iters/s)"
          + (f", {restarts} thick restarts" if restarts else ""))
    obs.emit("diagonalize_result",
             eigenvalues=[float(w) for w in np.atleast_1d(evals)],
             residuals=[float(r) for r in np.atleast_1d(residuals)],
             iters=int(niter), solve_s=round(dt, 3))
    if inspect is not None:
        from types import SimpleNamespace
        inspect(SimpleNamespace(
            engine=eng, config=cfg, eigenvalues=np.atleast_1d(evals),
            residuals=np.atleast_1d(residuals), eigenvectors=evecs,
            iterations=int(niter), solve_seconds=dt, timer=timer))

    evec_rows = None
    evecs_hashed = None
    is_pair = bool(getattr(eng, "pair", False))
    hashed_ndim = 3 if is_pair else 2       # [D, M(, 2)] hashed layout
    if evecs is not None and not args.no_eigenvectors:
        if args.shards and all(np.ndim(v) == hashed_ndim
                               for v in evecs[: args.num_evals]):
            # shard-native solve: eigenvectors stay hashed and are saved
            # one shard at a time with pads stripped (the per-locale block
            # writes of MyHDF5.chpl:272-333) — no global [N] array is ever
            # materialized, so --shards no longer needs --no-eigenvectors
            evecs_hashed = evecs[: args.num_evals]
        else:
            rows = []
            for v in evecs[: args.num_evals]:
                # hashed → block order for I/O BEFORE any host fetch: in a
                # multi-controller run the hashed array spans other
                # processes' devices and from_hashed allgathers it
                if hasattr(eng, "from_hashed") and np.ndim(v) == hashed_ndim:
                    v = eng.from_hashed(v)
                v = np.asarray(v)
                if is_pair and not np.iscomplexobj(v):
                    # (re, im) pair → complex for I/O (LOBPCG already
                    # returns complex columns)
                    from distributed_matvec_tpu.ops.kernels import (
                        complex_from_pair)
                    v = complex_from_pair(v)
                rows.append(v)
            evec_rows = np.stack(rows)

    with timer.scope("save"):
        if rank0:
            save_eigen(out, np.asarray(evals), evec_rows,
                       np.asarray(residuals))
        if evecs_hashed is not None:
            # every rank writes its addressable shards (the save targets
            # out.r<rank> in multi-process runs); pair-mode vectors keep
            # the (re, im) trailing axis on disk; one file pass for all k
            from distributed_matvec_tpu.io.sharded_io import (
                save_hashed_vectors)
            save_hashed_vectors(
                out, {f"eigenvector_{i}": v
                      for i, v in enumerate(evecs_hashed)}, eng.counts)

    for i, (w, r) in enumerate(zip(np.atleast_1d(evals),
                                   np.atleast_1d(residuals))):
        print(f"  E[{i}] = {w:.12f}   residual {r:.2e}")

    if args.observables and cfg.observables and evecs_hashed is not None:
        # Shard-native observables: |ψ₀⟩ never leaves the hashed space.
        # Every observable engine shares H's mesh and hash layout (pure
        # functions of the basis + device count), so the hashed ψ is
        # directly consumable — no block-order psi, no layout
        # materialization, no global array at any point.  The binding +
        # state-form algebra lives in models/observables (shared with
        # the dynamics solvers, DESIGN.md §29).
        from distributed_matvec_tpu.io.hdf5 import save_observables
        from distributed_matvec_tpu.models.observables import (
            expectations as _expectations)

        with timer.scope("observables"):
            values = _expectations(cfg.observables, eng, evecs_hashed[0],
                                   shards_path=args.shards)
        if rank0:
            for name, val in save_observables(out, values).items():
                print(f"  <{name}> = {val:.12f}")
    elif args.observables and cfg.observables and evec_rows is not None:
        # ⟨ψ₀|O|ψ₀⟩ per observable, printed and saved under /observables —
        # the output group the reference driver creates (Diagonalize.chpl:276-279).
        # Each observable gets its own *fused-mode* engine: no structure
        # build (the ELL pack costs minutes at scale and would be paid per
        # observable), device-speed apply — the analog of the reference
        # keeping observables inside its exported kernels
        # (LatticeSymmetries.chpl:16-31) instead of a host path.
        from distributed_matvec_tpu.io.hdf5 import save_observables

        psi = evec_rows[0]
        xh_cache = {}

        def obs_input(obs):
            """psi in the form the observable's engine consumes.

            A REAL-sector engine cannot carry a complex state — casting
            would silently drop Im(psi) — but for real Hermitian O,
            ψ†Oψ = Re†O·Re + Im†O·Im (the cross terms cancel), so complex
            psi becomes the two-column real batch [Re, Im] and the batched
            dot sums both columns.  A complex-sector engine gets psi
            promoted to complex.
            """
            if obs.effective_is_real:
                if np.iscomplexobj(psi):
                    return np.stack([psi.real, psi.imag], axis=1)
                return psi
            return psi.astype(np.complex128)

        def expectation(obs):
            p = obs_input(obs)
            if hasattr(eng, "from_hashed"):
                from distributed_matvec_tpu.parallel.distributed import (
                    DistributedEngine)
                # share H's mesh and hash layout (pure functions of the
                # basis + device count) and reuse the shuffled |psi> per
                # engine form — only the fused kernel tables differ per
                # observable.  A shard-native engine's observables come
                # from the SAME shard file (the basis is still never built
                # globally); the layout psi's block form already required
                # is shared, not rebuilt.
                if args.shards:
                    oeng = DistributedEngine.from_shards(
                        obs, args.shards, mesh=eng.mesh, mode="fused")
                    oeng.layout = eng._require_layout()
                else:
                    oeng = DistributedEngine(obs, mesh=eng.mesh,
                                             mode="fused", layout=eng.layout)
                key = (oeng.pair, p.dtype.kind, p.ndim)
                if key not in xh_cache:
                    xh_cache[key] = oeng.to_hashed(p)
                xh = xh_cache[key]
                # a [Re, Im] batch's dot sums both columns — exactly the
                # two needed terms
                return float(np.real(complex(oeng.dot(xh, oeng.matvec(xh)))))
            from distributed_matvec_tpu.parallel.engine import LocalEngine
            oeng = LocalEngine(obs, mode="fused")
            y = np.asarray(oeng.matvec(p))
            return float(np.real(np.vdot(p, y)))

        with timer.scope("observables"):
            values = [(obs.name or f"observable_{k}", expectation(obs))
                      for k, obs in enumerate(cfg.observables)]
        if rank0:
            for name, val in save_observables(out, values).items():
                print(f"  <{name}> = {val:.12f}")

    # phase timings + registry totals into the same stream the engines and
    # solvers wrote, then flush — the run dir is self-contained for
    # `obs_report summarize` the moment the process exits
    timer.emit(app="diagonalize")
    obs.emit("metrics_snapshot", metrics=obs.snapshot())
    obs.flush()
    timer.report()
    if watchdog is not None:
        watchdog.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
