#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

One process, in-process calls to ``apps/diagonalize.py``'s ``main(argv)`` —
the entry point a user runs — on a TPU, and nowhere else: the script fails
(non-zero exit, traceback printed, no result line) when
``jax.devices()[0].platform != "tpu"`` and never sets ``JAX_PLATFORMS``.

``python chip_smoke.py`` (one chip), in order:

  environment  versions, device, compile-cache directory in force, compiler
               and the native enumerator's build command and result;
  anchor       a 16-site Heisenberg ring written by the script, solved
               through the app: E0/4 = -7.1422963606 to 2e-10;
  solve        ``data/heisenberg_chain_32_symm.yaml`` (4,707,969 states,
               |G| = 128) through the app with ``-k 1`` and the default
               engine: native enumeration, tables and Krylov vectors on the
               device, one apply against the host row-form reference on
               65,536 sampled rows at atol 1e-14 / rtol 1e-12, and the
               solver's residual inside ``FULL_TOL``.

``python chip_smoke.py --chips 4`` runs ONLY the several-chip path and what
it is compared with: the same YAML through ``--devices 4``
(``DistributedEngine``), a shard of the tables and of the vector on each of
the four devices, one hashed apply against the same sampled host rows, and
E0 against a ``LocalEngine`` solve on device 0 in the same process at rtol
1e-10.

Every phase prints one ``[chip_smoke] <phase>: ...`` line with its wall
seconds and what it checked.  The last line of stdout is the contract's
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Every run is cold but for XLA's compile cache: ``main`` switches the basis /
structure artifact layer off (``DMT_ARTIFACT_CACHE=off``; it would write the
1.1 GB structure sidecar under ``~/.cache`` and let the next run under the
same ``HOME`` restore the tables instead of building them), and a solve whose
engine restored its structure fails.  What is written: the native
enumerator's binary beside its source, a temporary directory that is removed,
and the compile cache (``JAX_COMPILATION_CACHE_DIR``, else
``<checkout>/.cache/xla``).
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

FULL_YAML = os.path.join(REPO, "data", "heisenberg_chain_32_symm.yaml")
FULL_STATES = 4_707_969
#: ``lanczos_e0`` of a round-2 builder's run on other hardware (40
#: iterations, not converged) — a lead printed beside ours, not a gate
R02_LANCZOS_E0 = -56.8261101
ANCHOR_SITES = 16
ANCHOR_E0_OVER_4 = -7.1422963606      # .claude/skills/verify/SKILL.md
#: the constant carries 10 decimals (the converged value differs from it by
#: 1.7e-11); 1e-9 let an f64-dot_general error of 9.7e-10 through on the chip
ANCHOR_TOL = 2e-10
#: Lanczos residual tolerance of the full-size solve.  Looser than the app's
#: 1e-10 default: it cuts iterations, never states, so that the cold run
#: stays well inside the smoke's time limit
FULL_TOL = 1e-8
ATOL, RTOL = 1e-14, 1e-12             # the repo's per-apply contract
SAMPLE_ROWS = 1 << 16


def say(phase, seconds, **facts):
    body = " ".join(f"{k}={v}" for k, v in facts.items())
    print(f"[chip_smoke] {phase}: {seconds:.1f}s {body}", flush=True)


class CompileCounter:
    """Counts backend compilations (and their seconds) through
    ``jax.monitoring`` — every jit, AOT builder program and solver block."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def since(self, mark):
        return self.count - mark[0], self.seconds - mark[1]

    def mark(self):
        return self.count, self.seconds


def write_ring_yaml(path, n_sites, symmetric):
    """A periodic Heisenberg chain in the upstream YAML schema: the S_z = 0
    sector, and with ``symmetric`` the chain_32_symm symmetries at this
    size (spin inversion, translation k = 0, even reflection)."""
    bonds = [[i, (i + 1) % n_sites] for i in range(n_sites)]
    lines = [f"basis:\n  number_spins: {n_sites}\n"
             f"  hamming_weight: {n_sites // 2}\n"]
    if symmetric:
        lines.append(
            "  spin_inversion: 1\n  symmetries:\n"
            f"    - {{permutation: {[*range(1, n_sites), 0]}, sector: 0}}\n"
            f"    - {{permutation: {[*reversed(range(n_sites))]}, "
            "sector: 0}\n")
    lines.append("hamiltonian:\n  name: Heisenberg\n  terms:\n")
    for axis in "ˣʸᶻ":
        lines.append(f"    - {{expression: \"σ{axis}₀ σ{axis}₁\", "
                     f"sites: {bonds}}}\n")
    with open(path, "w") as f:
        f.writelines(lines)
    return path


def phase_environment(platform="tpu", count=None):
    """Print the environment line, build the native enumerator from the
    committed source, THEN insist on the device — so a refusal says why."""
    t0 = time.perf_counter()
    import jax
    import jaxlib

    from distributed_matvec_tpu.enumeration import native
    from distributed_matvec_tpu.utils.cache import enable_compilation_cache

    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:
        libtpu = "not installed"
    cache_dir = enable_compilation_cache()
    build = native.build_info()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say("environment", time.perf_counter() - t0,
        python=sys.version.split()[0], jax=jax.__version__,
        jaxlib=jaxlib.__version__, libtpu=libtpu, **device,
        compile_cache=cache_dir,
        cache_from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        cxx=build["compiler"], native_build=f"'{build['command']}'",
        native_result=build["result"].replace(" ", "_"))
    if device["platform"] != platform:
        raise RuntimeError(
            f"chip_smoke needs a {platform} device; JAX found "
            f"{device['platform']} ({device['kind']}). Nothing was run.")
    if count is not None and device["count"] != count:
        raise RuntimeError(
            f"--chips {count} needs {count} devices, JAX found "
            f"{device['count']}")
    return device


def _device_bytes(engine):
    """Bytes of the engine's resident arrays per device they live on."""
    import jax

    per = {}
    for a in jax.tree_util.tree_leaves(engine.memory_arrays()):
        for sh in getattr(a, "addressable_shards", ()):
            per[sh.device] = per.get(sh.device, 0) + sh.data.nbytes
    return per


def _check_apply(run, platform, sample_rows):
    """One apply of the run's engine against the host row-form reference on
    sampled rows; returns (max abs error, rows compared)."""
    eng, op = run.engine, run.config.hamiltonian
    n = op.basis.number_states
    rng = np.random.default_rng(0)
    x = rng.standard_normal(n)
    x /= np.linalg.norm(x)
    rows = np.sort(rng.choice(n, size=min(sample_rows, n), replace=False))
    if hasattr(eng, "to_hashed"):
        yh = eng.matvec(eng.to_hashed(x))
        y_dev, y = yh, eng.from_hashed(yh)
    else:
        y_dev = eng.matvec(x)
        y = np.asarray(y_dev)
    where = sorted(d.platform for d in y_dev.devices())
    if set(where) != {platform}:
        raise AssertionError(f"the apply's result lives on {where}")
    ref = op.matvec_host_rows(x, rows)
    err = float(np.max(np.abs(y[rows] - ref)))
    # NaN fails: `not (nan <= x)` is True
    if not np.all(np.abs(y[rows] - ref) <= ATOL + RTOL * np.abs(ref)):
        raise AssertionError(
            f"apply disagrees with the host rows: max |err| = {err:.3e} "
            f"on {rows.size} rows (atol {ATOL}, rtol {RTOL})")
    return err, int(rows.size)


def phase_solve(yaml_path, workdir, *, expect_states, tol, compiles,
                platform="tpu", devices=0, expect_native=True,
                sample_rows=SAMPLE_ROWS, name="solve", e0_over_4=None,
                output=None):
    """Solve ``yaml_path`` through ``apps/diagonalize.py`` and check the run
    from the inside (``inspect`` hook).  ``devices`` > 1 takes the
    hash-sharded ``DistributedEngine``; ``sample_rows=0`` skips the apply
    check (the anchor's E0 is its check)."""
    import jax

    from apps import diagonalize
    from distributed_matvec_tpu import obs

    t0 = time.perf_counter()
    mark = compiles.mark()
    before = dict(obs.snapshot()["counters"])
    out = output or os.path.join(workdir, f"{name}.h5")
    argv = [yaml_path, "-o", out, "-k", "1", "--tol", repr(tol)]
    if devices > 1:
        argv += ["--devices", str(devices)]
    seen = {}

    def inspect(run):
        eng = run.engine
        seen["iterations"] = run.iterations
        seen["e0"] = float(run.eigenvalues[0])
        seen["residual"] = float(run.residuals[0])
        seen["n_states"] = int(run.config.basis.number_states)
        seen["engine"] = type(eng).__name__
        if getattr(eng, "structure_restored", False):
            raise AssertionError(
                "the engine restored its structure from an artifact: the "
                "on-device structure build this smoke proves did not run")
        # the engines name their structure-build scope differently
        build = "build_plan" if devices > 1 else "build_structure"
        seen["seconds"] = dict(
            basis_s=round(run.timer.scope_total("basis"), 2),
            engine_s=round(run.timer.scope_total("engine"), 2),
            structure_build_s=round(eng.timer.scope_total(build), 2),
            structure_compile_s=round(
                eng.timer.scope_total(build, "compile"), 2),
            solve_s=round(run.solve_seconds, 2))
        per = _device_bytes(eng)
        if {d.platform for d in per} != {platform}:
            raise AssertionError(
                f"engine tables live on {sorted(map(str, per))}, "
                f"not on {platform}")
        if len(per) != max(devices, 1) or min(per.values()) <= 0:
            raise AssertionError(
                f"expected table shards on {max(devices, 1)} device(s), "
                f"found {({str(d): b for d, b in per.items()})}")
        seen["table_bytes"] = {str(d): int(b) for d, b in per.items()}
        v = run.eigenvectors[0]
        vdev = sorted(v.devices(), key=lambda d: d.id)
        if {d.platform for d in vdev} != {platform} \
                or len(vdev) != max(devices, 1):
            raise AssertionError(
                f"the Krylov/eigen vector lives on {list(map(str, vdev))}")
        seen["vector_shard_bytes"] = [
            int(sh.data.nbytes) for sh in v.addressable_shards]
        if sample_rows:
            seen["apply_err"], seen["apply_rows"] = _check_apply(
                run, platform, sample_rows)

    rc = diagonalize.main(argv, inspect=inspect)
    if rc != 0 or not seen:
        raise AssertionError(f"apps/diagonalize.py returned {rc}")
    if seen["n_states"] != expect_states:
        raise AssertionError(
            f"number_states {seen['n_states']} != {expect_states}")
    after = obs.snapshot()["counters"]
    by = {b: after.get(f"enumeration{{backend={b}}}", 0)
          - before.get(f"enumeration{{backend={b}}}", 0)
          for b in ("native", "numpy")}
    if expect_native and not (by["native"] == 1 and by["numpy"] == 0):
        raise AssertionError(
            f"the native enumerator did not do the enumeration: {by}")
    if not seen["residual"] <= tol * max(1.0, abs(seen["e0"])):
        raise AssertionError(
            f"solver residual {seen['residual']:.3e} misses tol {tol} "
            f"(|E0| = {abs(seen['e0']):.3f})")
    if e0_over_4 is not None \
            and not abs(seen["e0"] / 4 - e0_over_4) <= ANCHOR_TOL:
        raise AssertionError(
            f"E0/4 = {seen['e0'] / 4:.10f}, anchor {e0_over_4:.10f}")
    n_comp, comp_s = compiles.since(mark)
    stats = jax.devices()[0].memory_stats() or {}
    facts = dict(
        engine=seen["engine"], n_states=seen["n_states"],
        enumerated_by="native" if by["native"] else
        ("numpy" if by["numpy"] else "restored"),
        **seen["seconds"], compilations=n_comp, compile_s=round(comp_s, 2),
        iterations=seen["iterations"], E0=f"{seen['e0']:.10f}",
        E0_over_4=f"{seen['e0'] / 4:.10f}",
        residual=f"{seen['residual']:.2e}", tol=tol,
        peak_bytes=stats.get("peak_bytes_in_use", "not_reported"))
    if sample_rows:
        facts.update(apply_max_err=f"{seen['apply_err']:.2e}",
                     apply_rows=seen["apply_rows"], atol=ATOL, rtol=RTOL)
    if devices > 1:
        facts.update(table_bytes=json.dumps(seen["table_bytes"]),
                     vector_shard_bytes=seen["vector_shard_bytes"])
    say(name, time.perf_counter() - t0, **facts)
    seen["output"] = out
    return seen


def phase_anchor(workdir, compiles, platform="tpu", n_sites=ANCHOR_SITES,
                 e0_over_4=ANCHOR_E0_OVER_4):
    from math import comb

    path = write_ring_yaml(os.path.join(workdir, f"ring_{n_sites}.yaml"),
                           n_sites, symmetric=False)
    return phase_solve(path, workdir, expect_states=comb(n_sites,
                                                         n_sites // 2),
                       tol=1e-10, compiles=compiles, platform=platform,
                       expect_native=False, sample_rows=0, name="anchor",
                       e0_over_4=e0_over_4)


def phase_four_chips(yaml_path, workdir, *, expect_states, tol, compiles,
                     platform="tpu", devices=4, sample_rows=SAMPLE_ROWS):
    """The several-chip path and what it is compared with, nothing else:
    the hash-sharded solve, then a single-device solve on device 0 that
    restores the same representatives from the first run's output file."""
    t0 = time.perf_counter()
    out = os.path.join(workdir, "four_chips.h5")
    dist = phase_solve(yaml_path, workdir, expect_states=expect_states,
                       tol=tol, compiles=compiles, platform=platform,
                       devices=devices, sample_rows=sample_rows,
                       name=f"solve_{devices}_devices", output=out)
    local = phase_solve(yaml_path, workdir, expect_states=expect_states,
                        tol=tol, compiles=compiles, platform=platform,
                        expect_native=False, sample_rows=sample_rows,
                        name="solve_device_0", output=out)
    rel = abs(dist["e0"] - local["e0"]) / abs(local["e0"])
    if not rel <= 1e-10:
        raise AssertionError(
            f"E0 on {devices} devices {dist['e0']:.12f} vs device 0 "
            f"{local['e0']:.12f}: rel {rel:.2e} > 1e-10")
    say("four_chips", time.perf_counter() - t0, devices=devices,
        E0_distributed=f"{dist['e0']:.12f}", E0_local=f"{local['e0']:.12f}",
        rel_diff=f"{rel:.2e}", rtol=1e-10)
    return dist, local


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip path and the one-chip "
                         "solve it is compared with")
    args = ap.parse_args(argv)
    # no basis/structure artifact is read or written under $HOME: every run
    # builds its tables on the device (the compile cache stays on)
    os.environ["DMT_ARTIFACT_CACHE"] = "off"
    t_all = time.perf_counter()
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        device = phase_environment(
            "tpu", count=4 if args.chips == 4 else None)
        compiles = CompileCounter()
        if args.chips == 4:
            phase_four_chips(FULL_YAML, workdir, expect_states=FULL_STATES,
                             tol=FULL_TOL, compiles=compiles)
        else:
            phase_anchor(workdir, compiles)
            print(f"[chip_smoke] note: full-size solve at tol {FULL_TOL} "
                  f"(iterations cut, states are not); r02 builder record's "
                  f"unconverged lanczos_e0 was {R02_LANCZOS_E0}", flush=True)
            phase_solve(FULL_YAML, workdir, expect_states=FULL_STATES,
                        tol=FULL_TOL, compiles=compiles)
        say("total", time.perf_counter() - t_all,
            compilations=compiles.count,
            compile_s=round(compiles.seconds, 1))
    except BaseException:
        # the next refusal must say WHY: full traceback, no result line
        traceback.print_exc()
        sys.stdout.flush()
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
