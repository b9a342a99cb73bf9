#!/usr/bin/env python
"""obs_report — read, summarize, merge, diff, and tail telemetry runs.

The reader side of the ``distributed_matvec_tpu/obs`` subsystem.  A *run* is
either

* a run directory written under ``DMT_OBS_DIR`` (one
  ``rank_<r>/events.jsonl`` per process — the pre-rank
  ``events.p<proc>.jsonl`` layout is still read),
* a single ``.jsonl`` event file, or
* a detail JSON (``{config_key: {metrics}}``, what the check scripts
  write for ``diff``), which is treated as a run containing only
  ``bench_result`` events so such rows diff directly against live runs.

Subcommands::

    summarize RUN [--json]
        One run → engine-init split table (structure/compile/transfer/diag),
        artifact-cache hit rates + AOT executable-cache reuse + transfer
        volume from the final metrics snapshot, numerical-health counters
        (exchange overflow/invalid, nonfinite outputs) + events, a memory
        section (ledger top allocations + totals per rank, peak HBM
        watermarks, executable memory analyses, OOM reports), per-config
        bench metrics, and solver convergence traces (iteration → Ritz
        value/residual — ready-to-plot data).

    merge RUN [-o OUT.jsonl]
        Multi-rank run → ONE ordered timeline.  Per-rank wall-clock skew is
        estimated from events that follow cross-rank synchronization points
        (engine inits, the i-th eager apply — SPMD runs execute the same
        program order on every rank), each event gains a skew-corrected
        ``ts_adj``, and the merged stream is ordered by
        ``(ts_adj, rank, seq)`` (within-rank ``seq`` order is monotonic and
        trusted; wall clocks across hosts are not).

    report RUN [--ranks] [--memory] [--phases] [--json]
        Cross-rank skew report: estimated clock offsets, straggler
        attribution per apply (the rank whose aligned ``matvec_apply``
        lands last; excess = max − median), and with ``--ranks`` the
        per-rank table — events, survivor states, bytes exchanged,
        plan-build wall, double-buffer stalls, per-rank peak HBM, mean
        time-at-barrier.  ``--memory`` appends the memory section
        (ledger / watermarks / executables / OOM reports); ``--phases``
        the per-(engine, mode) phase table from ``apply_phases`` events
        (mean apply wall, per-phase bytes/gathers, measured plan-stream
        waits).

    roofline RUN [--calibration PATH] [--json]
        The analytical roofline report (``obs/roofline.py``) over the
        run's ``apply_phases`` events: per (engine, mode) the attributed
        per-phase wall times (summing to the measured apply wall),
        bound times at the calibrated rates, achieved-vs-bound fractions,
        the named binding resource, and the pipelined-apply speedup
        estimate (the ROADMAP's overlap item, priced before it's built).
        Runs that recorded PIPELINED applies (``pipeline_depth`` >= 2,
        DESIGN.md §25) get their own per-depth group with the measured
        time-at-barrier / hidden-staging split, and — when the same run
        also holds sequential applies of that (engine, mode) — the
        measured-vs-priced speedup side by side, with a WARNING when the
        measured overlap falls below 50% of the estimate.
        Calibration: explicit ``--calibration`` JSON > the
        content-addressed sidecar ``tools/gather_bound.py`` persists >
        the documented DESIGN.md §2 defaults.

    diff BASELINE NEW [--threshold 0.2] [--metric device_ms ...]
                      [--config NAME ...] [--memory] [--phases]
                      [--all-metrics]
        Two runs → per-config relative change of every comparable numeric
        metric; exits 1 when any *gated* metric regressed beyond the
        threshold (default gate: device_ms; direction-aware — ms/seconds
        up is a regression, iters-per-second down is).  ``--memory`` adds
        the memory gate (table_bytes, executable temp/peak bytes,
        watermark peak — growth is the regression); ``--phases`` gates
        every ``phase_*`` bench metric (per-phase bytes/gathers/ms — all
        cost-like), so a plan-compression PR can assert "H2D phase bytes
        down, compute phase flat" with
        ``--phases`` or ``--metric phase_plan_h2d_bytes``.  A gate entry
        ending in ``*`` matches by prefix.  The check scripts gate
        structural counts with it (``make compress-check``,
        ``make hybrid-check``); times are left to the benchmark's cells.

    trace RUN [-o OUT.json]
        Chrome/Perfetto trace-event export of the merged span tree
        (``obs/trace.py``): one process per rank, track 0 the recorded
        spans (solve > iteration > apply > chunk as nested B/E pairs),
        track 1 the per-apply phase split derived from ``apply_phases``
        (matched by envelope ``span_id``), counter tracks for HBM in use,
        solver ritz/residual, and lossy-tier drift.  Load the JSON in
        ui.perfetto.dev (or chrome://tracing).

    watch RUN [--once] [--interval 1.0] [--window 60]
        Live terminal dashboard over the rank streams (tails every
        ``rank_<r>/events.jsonl`` with the same rotation-safe machinery
        as ``tail --follow``): apply count/rate per rank, per-phase time
        split, solver convergence (ritz/residual), cross-rank straggler
        skew, health/fault/stall counters, lossy-tier drift, HBM/host
        watermarks.  ``--once`` renders a single frame and exits (CI and
        scripts); otherwise refreshes in place every ``--interval``.

    tail RUN [-n 20] [--follow]
        Human-readable view of the last events; ``--follow`` keeps reading
        as a live run appends (rotated/recreated files are reopened on
        inode change, so a restarted writer never silently drops the tail).

    slo RUN [--target NAME=VALUE ...] [--json]
        Evaluate the stock burn-rate SLO set (``obs/slo.py`` — serve p99
        latency, solves/min floor, steady apply/iteration walls,
        compression drift, stall/fault/OOM incident counters) over a
        recorded run, post hoc and deterministic (windows anchor on the
        newest event timestamp).  ``--target`` pins an explicit objective
        by SLO name (repeatable); unpinned thresholds self-baseline from
        the run's earliest quartile.  Exits 1 when any SLO is firing —
        the CI shape ``make slo-check`` drives.

    postmortem RUN [--json]
        Read the crash flight-recorder bundles a dying rank left under
        ``rank_<r>/postmortem/`` (``obs/flight.py``): per bundle the
        trigger (stall/preempt/oom/quarantine), exit code, rank,
        trace/job identity, the span the process died inside, and the
        content-address verification (the filename's sha16 is re-hashed
        against the bytes — a torn or tampered bundle is flagged loudly
        and exits 1).  ``RUN`` may also be one bundle ``.json`` path.
"""

from __future__ import annotations

import argparse
import glob
import hashlib
import json
import os
import statistics
import sys
import time
from typing import Dict, List, Optional

# Metric directions live in ONE shared table
# (distributed_matvec_tpu/obs/directions.py) consumed by every gate
# (this tool and the check scripts) —
# registering a new metric's direction happens exactly once there.  The
# module is loaded by FILE so this standalone reader never imports the
# package (and therefore never initializes a JAX backend just to read
# JSONL).
def _load_directions():
    import importlib.util
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "distributed_matvec_tpu", "obs", "directions.py")
    spec = importlib.util.spec_from_file_location("dmt_obs_directions",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_is_higher_better = _load_directions().is_higher_better


def _load_slo():
    """File-load ``obs/slo.py`` (same pattern as the directions table):
    its import-dual header falls back to the pure standalone evaluation
    surface, so the ``slo`` subcommand never imports the package (and
    therefore never initializes a JAX backend just to grade a run)."""
    import importlib.util
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "distributed_matvec_tpu", "obs", "slo.py")
    spec = importlib.util.spec_from_file_location("dmt_obs_slo", path)
    mod = importlib.util.module_from_spec(spec)
    # dataclass processing resolves string annotations through
    # sys.modules[cls.__module__] — an unregistered file-loaded module
    # would crash @dataclass on 3.10
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _load_hlo():
    """File-load ``obs/hlo.py`` (same pattern as the SLO module): its
    import-dual header falls back to the pure parse/attribute/diff
    surface, so the ``profile`` subcommand never imports the package
    (and therefore never initializes a JAX backend just to diff two
    JSON cost tables)."""
    import importlib.util
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "distributed_matvec_tpu", "obs", "hlo.py")
    spec = importlib.util.spec_from_file_location("dmt_obs_hlo", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _resolve_profile(hlo_mod, path: str,
                     program: Optional[str] = None) -> Optional[dict]:
    """Resolve a ``profile`` subcommand argument to one profile dict:
    a profile-artifact ``.json`` loads directly; a run directory or
    ``.jsonl`` stream resolves through its ``hlo_cost`` events to the
    newest artifact (optionally filtered by ``program`` substring)."""
    if os.path.isfile(path) and path.endswith(".json"):
        try:
            return hlo_mod.load_profile(path)
        except (ValueError, json.JSONDecodeError):
            pass                     # not an artifact: fall through
    try:
        events = load_events(path)
    except Exception:
        return None
    cands = [e for e in events if e.get("kind") == "hlo_cost"]
    if program:
        cands = [e for e in cands if program in str(e.get("program"))]
    for ev in reversed(cands):
        art = str(ev.get("artifact") or "")
        if art and os.path.isfile(art):
            try:
                return hlo_mod.load_profile(art)
            except (ValueError, json.JSONDecodeError):
                continue
    return None


_DEFAULT_GATE = ("device_ms",)

# the memory-regression gate (`diff --memory`): all cost-like, so the
# direction rule above already reads growth as the regression
_MEMORY_GATE = ("table_bytes", "executable_temp_bytes",
                "executable_peak_bytes", "peak_hbm_bytes")

# the phase gate (`diff --phases`): every per-phase bench metric
# (phase_<name>_bytes / _gathers / _ms) — all cost-like, prefix-matched
_PHASE_GATE = ("phase_*",)


# ---------------------------------------------------------------------------
# loading


def _rank_of(ev: dict) -> int:
    return int(ev.get("rank", ev.get("proc", 0)))


def _run_files(path: str) -> List[str]:
    """The JSONL files of a run directory: rank-subdirectory layout
    (``rank_<r>/events.jsonl``, current) or the legacy flat
    ``events.p<proc>.jsonl`` files.  When BOTH are present the directory
    holds two different runs (a pre-upgrade one plus a new one) — merging
    them would interleave duplicate seq numbers into one corrupt
    timeline, so the legacy files are ignored with a warning."""
    rank_files = sorted(glob.glob(os.path.join(path, "rank_*", "*.jsonl")))
    legacy = sorted(glob.glob(os.path.join(path, "events.p*.jsonl")))
    if rank_files and legacy:
        if path not in _warned_mixed:      # once, not per follow poll
            _warned_mixed.add(path)
            print(f"[obs_report] {path}: ignoring {len(legacy)} legacy "
                  "events.p*.jsonl file(s) beside rank_*/ streams — a "
                  "reused run directory holds two different runs; point "
                  "at a fresh directory to read the old run",
                  file=sys.stderr)
        return rank_files
    return rank_files + legacy


_warned_mixed: set = set()


def load_events(path: str) -> List[dict]:
    """Events of one run, ordered by (rank, seq).  Accepts a run directory,
    one .jsonl file, or a ``{config: {metrics}}`` .json (synthesized into
    ``bench_result`` events)."""
    if os.path.isdir(path):
        files = _run_files(path)
        if not files:
            raise FileNotFoundError(
                f"no rank_*/ or events.p*.jsonl streams under {path}")
        evs = []
        for f in files:
            evs.extend(_read_jsonl(f))
        evs.sort(key=lambda e: (_rank_of(e), e.get("seq", 0)))
        return evs
    if path.endswith(".jsonl"):
        return _read_jsonl(path)
    with open(path) as f:
        detail = json.load(f)
    if not isinstance(detail, dict):
        raise ValueError(f"{path}: expected a JSON object of configs")
    evs = []
    for i, (key, rec) in enumerate(sorted(detail.items())):
        if not isinstance(rec, dict) or "error" in rec:
            continue
        evs.append({"seq": i, "proc": 0, "kind": "bench_result",
                    "config": rec.get("config", key), **rec})
    return evs


def _read_jsonl(path: str) -> List[dict]:
    evs = []
    with open(path) as f:
        for ln, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                evs.append(json.loads(line))
            except json.JSONDecodeError as e:
                # a torn final line from a live/killed writer is expected;
                # anything mid-file is worth a loud stderr note
                print(f"[obs_report] skipping unparseable line "
                      f"{path}:{ln}: {e}", file=sys.stderr)
    return evs


# ---------------------------------------------------------------------------
# summarize


def bench_metrics(events: List[dict]) -> Dict[str, Dict[str, float]]:
    """{config_name: {metric: number}} from ``bench_result`` events (last
    event per config wins — reruns supersede)."""
    out: Dict[str, Dict[str, float]] = {}
    for ev in events:
        if ev.get("kind") != "bench_result":
            continue
        cfg = str(ev.get("config", "unknown"))
        out[cfg] = {k: v for k, v in ev.items()
                    if isinstance(v, (int, float)) and not isinstance(v, bool)
                    and k not in ("seq", "ts", "proc")}
    return out


def _cache_rates(snap: dict) -> dict:
    """Hit rates + transfer totals from a metrics snapshot's counters."""
    counters = snap.get("counters", {})
    agg: Dict[str, Dict[str, int]] = {}
    bytes_io = {"bytes_h2d": 0, "bytes_d2h": 0}
    retrace = 0
    for name, val in counters.items():
        base = name.split("{", 1)[0]
        if base in ("artifact_cache", "aot_executable_cache"):
            event = kind = ""
            if "{" in name:
                for part in name[name.index("{") + 1:-1].split(","):
                    k, _, v = part.partition("=")
                    if k == "event":
                        event = v
                    elif k == "kind":
                        kind = v
            key = f"{base}/{kind}" if kind else base
            agg.setdefault(key, {}).setdefault(event, 0)
            agg[key][event] += int(val)
        elif base in bytes_io:
            bytes_io[base] += int(val)
        elif base == "retrace_count":
            retrace += int(val)
    rates = {}
    for key, ev in sorted(agg.items()):
        hits = ev.get("hit", 0)
        misses = ev.get("miss", 0) + ev.get("compile", 0)
        total = hits + misses
        rates[key] = dict(ev, hit_rate=round(hits / total, 4) if total
                          else None)
    return {"caches": rates, **bytes_io, "retrace_count": retrace}


def memory_summary(events: List[dict], top_n: int = 8) -> dict:
    """Memory observability digest of one run: the LAST ``memory_ledger``
    snapshot per rank (top-N allocations by bytes), max watermark peak per
    rank, the fullest device's own row of each rank's last watermark with
    what the ledger held on it (``fullest``: in-use and peak of ONE device,
    which can be subtracted; a sample without the field gives none),
    executable analyses (one per compiled specialization, last wins), and
    any OOM ``memory_report`` events."""
    ledgers: Dict[int, dict] = {}
    peaks: Dict[int, int] = {}
    fullest: Dict[int, dict] = {}
    analyses: Dict[str, dict] = {}
    ooms = []
    for ev in events:
        kind = ev.get("kind")
        if kind == "memory_ledger":
            ledgers[_rank_of(ev)] = ev
        elif kind == "memory_watermark":
            r = _rank_of(ev)
            peaks[r] = max(peaks.get(r, 0), int(ev.get("peak_bytes") or 0))
            if ev.get("fullest"):
                fullest[r] = dict(ev["fullest"], tag=ev.get("tag"),
                                  synced=bool(ev.get("synced")),
                                  ledger_bytes=int(ev.get("ledger_bytes")
                                                   or 0))
        elif kind == "memory_analysis":
            analyses[str(ev.get("key") or ev.get("program"))] = {
                k: ev.get(k) for k in
                ("program", "argument_bytes", "output_bytes", "temp_bytes",
                 "generated_code_bytes", "peak_estimate_bytes")}
        elif kind == "memory_report":
            ooms.append({k: ev.get(k) for k in
                         ("rank", "context", "ledger_total_bytes",
                          "error", "remediation") if k in ev})
    top: Dict[int, list] = {}
    totals: Dict[int, int] = {}
    contexts: Dict[int, dict] = {}
    for r, ev in ledgers.items():
        entries = ev.get("entries") or {}
        rows = sorted(((p, int(e.get("bytes", 0)))
                       for p, e in entries.items()),
                      key=lambda pe: -pe[1])
        top[r] = [{"path": p, "bytes": b} for p, b in rows[:top_n]]
        totals[r] = int(ev.get("total_bytes") or 0)
        contexts[r] = {k: ev.get(k) for k in
                       ("context", "engine", "mode", "n_states", "T0",
                        "table_bytes") if k in ev}
    return {"ledger_total_bytes": totals, "top_allocations": top,
            "ledger_context": contexts, "peak_hbm_bytes": peaks,
            "fullest": fullest,
            "executables": analyses, "oom_events": ooms}


_PHASE_ORDER = ("plan_h2d", "compute", "exchange", "accumulate", "overhead")


def phases_summary(events: List[dict]) -> dict:
    """Per-(engine, mode) digest of the ``apply_phases`` events: apply
    count, mean wall (steady = first apply dropped when ≥2), per-phase
    structural totals and mean measured walls, mean plan-stream chunk
    stall.  Structural-only — the calibrated bound/attribution view lives
    in the ``roofline`` subcommand (obs/roofline.py)."""
    groups: Dict[str, List[dict]] = {}
    for ev in events:
        if ev.get("kind") == "apply_phases" and ev.get("phases"):
            key = f"{ev.get('engine')}/{ev.get('mode')}"
            groups.setdefault(key, []).append(ev)
    out = {}
    for key, evs in sorted(groups.items()):
        steady = evs[1:] if len(evs) > 1 else evs
        walls = [float(e.get("wall_ms") or 0.0) for e in steady]
        phases: Dict[str, dict] = {}
        for p in sorted({p for e in steady for p in e["phases"]}):
            recs = [e["phases"].get(p) or {} for e in steady]
            mws = [float(r["wall_ms"]) for r in recs
                   if r.get("wall_ms") is not None]
            phases[p] = {
                "bytes": int(sum(r.get("bytes", 0) for r in recs)
                             / max(len(recs), 1)),
                "gathers": int(sum(r.get("gathers", 0) for r in recs)
                               / max(len(recs), 1)),
                "flops": int(sum(r.get("flops", 0) for r in recs)
                             / max(len(recs), 1)),
            }
            if mws:
                phases[p]["measured_wall_ms"] = round(
                    sum(mws) / len(mws), 4)
        stalls = [c["stall_ms"] for e in steady
                  for c in (e.get("chunk_timeline") or [])
                  if c.get("stall_ms") is not None]
        out[key] = {
            "applies": len(evs),
            "mean_wall_ms": round(sum(walls) / len(walls), 4)
            if walls else None,
            "chunks": int(steady[-1].get("chunks") or 1),
            "phases": phases,
        }
        if stalls:
            out[key]["mean_chunk_stall_ms"] = round(
                sum(stalls) / len(stalls), 4)
    return out


def print_phases_section(ph: dict) -> None:
    """Render the :func:`phases_summary` digest (``summarize`` phases
    section / ``report --phases``)."""
    print("\nphase attribution (apply_phases; mean over steady applies):")
    for key, grp in sorted(ph.items()):
        print(f"  {key}: {grp['applies']} applies, "
              f"wall {grp['mean_wall_ms']} ms/apply, "
              f"{grp['chunks']} chunk(s)"
              + (f", mean plan-stream stall "
                 f"{grp['mean_chunk_stall_ms']} ms"
                 if "mean_chunk_stall_ms" in grp else ""))
        for p in _PHASE_ORDER:
            rec = grp["phases"].get(p)
            if rec is None or not any(rec.get(k) for k in
                                      ("bytes", "gathers", "flops",
                                       "measured_wall_ms")):
                continue
            mw = rec.get("measured_wall_ms")
            print(f"    {p:<12} bytes={rec['bytes']:<14,} "
                  f"gathers={rec['gathers']:<12,} flops={rec['flops']:,}"
                  + (f"  measured {mw} ms" if mw is not None else ""))


def run_summary(events: List[dict]) -> dict:
    """The machine-readable summary ``summarize`` renders."""
    inits = [{k: ev.get(k) for k in
              ("proc", "engine", "mode", "n_states", "basis_restored",
               "structure_restored", "init_s", "build_structure_s",
               "compile_s", "kernels_s", "transfer_s", "diag_s")}
             for ev in events if ev.get("kind") == "engine_init"]

    solvers = []
    cur: Optional[dict] = None
    for ev in events:
        kind = ev.get("kind")
        if kind == "solver_start":
            cur = {"solver": ev.get("solver"), "proc": ev.get("proc"),
                   "k": ev.get("k"), "tol": ev.get("tol"), "trace": []}
            solvers.append(cur)
        elif kind == "lanczos_trace":
            if cur is None or ev.get("solver") != cur["solver"]:
                cur = {"solver": ev.get("solver"), "proc": ev.get("proc"),
                       "trace": []}
                solvers.append(cur)
            cur["trace"].append({"iter": ev.get("iter"),
                                 "basis_size": ev.get("basis_size"),
                                 "ritz": ev.get("ritz"),
                                 "residual": ev.get("residual")})
        elif kind == "solver_end" and cur is not None \
                and ev.get("solver") == cur["solver"]:
            cur.update(iters=ev.get("iters"), converged=ev.get("converged"),
                       eigenvalues=ev.get("eigenvalues"))
            cur = None

    snaps = [ev for ev in events if ev.get("kind") == "metrics_snapshot"]
    cache = _cache_rates(snaps[-1].get("metrics", {})) if snaps else None

    # numerical-health counters (exchange overflow/invalid, nonfinite
    # outputs — zero is the healthy reading, so they are surfaced even at
    # zero) + the structured health events themselves
    health_counters: Dict[str, int] = {}
    if snaps:
        for name, val in snaps[-1].get("metrics", {}) \
                .get("counters", {}).items():
            if name.split("{", 1)[0] in (
                    "exchange_overflow", "exchange_invalid",
                    "matvec_nonfinite", "health_events"):
                health_counters[name] = int(val)
    health_events = [
        {k: ev.get(k) for k in ("rank", "kind", "check", "level", "solver",
                                "engine", "iter", "count", "overflow",
                                "invalid", "omega") if k in ev}
        for ev in events if ev.get("kind") in ("health", "solver_health")]

    # SLO alerting + flight-recorder digest: slo_alert transitions per
    # SLO name, the lifetime alert/dump counters from the final
    # snapshot, and every crash bundle the run left behind
    slo_alerts: Dict[str, Dict[str, int]] = {}
    for ev in events:
        if ev.get("kind") != "slo_alert":
            continue
        rec = slo_alerts.setdefault(str(ev.get("slo")),
                                    {"fired": 0, "cleared": 0})
        rec["fired" if ev.get("state") == "firing" else "cleared"] += 1
    slo_counters: Dict[str, int] = {}
    if snaps:
        for name, val in snaps[-1].get("metrics", {}) \
                .get("counters", {}).items():
            if name.split("{", 1)[0] in ("slo_alert_count",
                                         "flight_dump_count"):
                slo_counters[name] = int(val)
    flight_dumps = [
        {k: ev.get(k) for k in ("rank", "reason", "exit_code", "bundle",
                                "span_path") if k in ev}
        for ev in events if ev.get("kind") == "flight_dump"]

    ident = {}
    for ev in events:
        if ev.get("trace_id"):
            ident = {"trace_id": ev["trace_id"],
                     "job_id": ev.get("job_id")}
            break

    return {"n_events": len(events),
            "identity": ident,
            "processes": sorted({_rank_of(ev) for ev in events}),
            "engine_inits": inits,
            "cache": cache,
            "health": {"counters": health_counters,
                       "events": health_events},
            "slo": {"alerts": slo_alerts, "counters": slo_counters,
                    "flight_dumps": flight_dumps},
            "profile": profile_summary(events),
            "memory": memory_summary(events),
            "phases": phases_summary(events),
            "bench": bench_metrics(events),
            "solvers": solvers}


def profile_summary(events: List[dict]) -> Optional[dict]:
    """Digest of the continuous-profiling plane's events: the newest
    HLO cost profile per compiled program (``hlo_cost``), trace-capture
    counts per kind (``profile_captured``), and whether the overhead
    guard latched sampling off.  None for runs that never profiled —
    the summary stays byte-identical for them."""
    hlo: Dict[str, dict] = {}
    captures: Dict[str, int] = {}
    latch = None
    for ev in events:
        kind = ev.get("kind")
        if kind == "hlo_cost":
            hlo[str(ev.get("program"))] = ev     # newest wins
        elif kind == "profile_captured":
            cap = str(ev.get("capture") or "unknown")
            captures[cap] = captures.get(cap, 0) + 1
        elif kind == "profile_overhead_latch":
            latch = {"overhead_pct": ev.get("overhead_pct"),
                     "budget_pct": ev.get("budget_pct")}
    if not hlo and not captures and latch is None:
        return None
    out: Dict[str, object] = {
        "programs": {p: {"fingerprint": str(e.get("fingerprint", ""))[:16],
                         "flops": e.get("flops"),
                         "bytes": e.get("bytes"),
                         "n_ops": e.get("n_ops"),
                         "artifact": e.get("artifact", "")}
                     for p, e in sorted(hlo.items())},
        "captures": captures,
    }
    if hlo:
        newest = max(hlo.values(), key=lambda e: e.get("seq", 0))
        out["newest"] = {
            "program": str(newest.get("program")),
            "fingerprint": str(newest.get("fingerprint", ""))[:16],
            "artifact": str(newest.get("artifact") or ""),
            "top_ops": list(newest.get("top_ops") or [])[:3],
        }
    if latch is not None:
        out["latched"] = latch
    return out


def _fmt_seconds(v) -> str:
    return f"{'-':>8}" if v is None else f"{v:8.3f}"


def print_summary(s: dict) -> None:
    ident = s.get("identity") or {}
    tag = ""
    if ident.get("trace_id"):
        tag = f"  trace_id: {ident['trace_id']}"
        if ident.get("job_id") and ident["job_id"] != ident["trace_id"]:
            tag += f"  job_id: {ident['job_id']}"
    print(f"events: {s['n_events']}  processes: {s['processes']}{tag}")
    if s["engine_inits"]:
        print("\nengine inits (seconds; split from the construction timers):")
        print(f"  {'engine':<12} {'mode':<8} {'N':<10}"
              f"{'init':>8} {'build':>8} {'compile':>8} {'kernels':>8}"
              f"{'transfer':>9} {'diag':>8}  restored(basis/structure)")
        for e in s["engine_inits"]:
            print(f"  {str(e['engine']):<12} {str(e['mode']):<8} "
                  f"{str(e['n_states']):<10}"
                  f"{_fmt_seconds(e['init_s'])} "
                  f"{_fmt_seconds(e['build_structure_s'])} "
                  f"{_fmt_seconds(e['compile_s'])} "
                  f"{_fmt_seconds(e['kernels_s'])} "
                  f"{_fmt_seconds(e['transfer_s']):>9} "
                  f"{_fmt_seconds(e['diag_s'])}  "
                  f"{bool(e['basis_restored'])}/"
                  f"{bool(e['structure_restored'])}")
    if s["cache"]:
        c = s["cache"]
        print("\ncache / transfer totals (final metrics snapshot):")
        for key, ev in c["caches"].items():
            rate = ev.get("hit_rate")
            counts = " ".join(f"{k}={v}" for k, v in sorted(ev.items())
                              if k != "hit_rate")
            print(f"  {key:<28} {counts}"
                  + (f"  hit_rate={rate:.1%}" if rate is not None else ""))
        print(f"  bytes_h2d={c['bytes_h2d']}  bytes_d2h={c['bytes_d2h']}  "
              f"retrace_count={c['retrace_count']}")
    h = s.get("health") or {}
    if h.get("counters") or h.get("events"):
        print("\nnumerical health:")
        for name, val in sorted((h.get("counters") or {}).items()):
            print(f"  {name:<44} {val}")
        evs = h.get("events") or []
        if evs:
            print(f"  {len(evs)} health event(s):")
            for ev in evs[:10]:
                detail = " ".join(
                    f"{k}={v}" for k, v in ev.items() if k != "kind")
                print(f"    {ev.get('kind')}: {detail}")
        else:
            print("  no health events (clean run)")
    slo = s.get("slo") or {}
    if slo.get("alerts") or slo.get("counters") or slo.get("flight_dumps"):
        # conditional by design: alert-free, crash-free runs summarize
        # exactly as before this section existed
        print("\nslo alerts / flight recorder:")
        for name, rec in sorted((slo.get("alerts") or {}).items()):
            print(f"  {name:<36} fired {rec['fired']}, "
                  f"cleared {rec['cleared']}")
        for name, val in sorted((slo.get("counters") or {}).items()):
            print(f"  {name:<44} {val}")
        for fd in slo.get("flight_dumps") or []:
            where = f" in {fd['span_path']}" if fd.get("span_path") else ""
            print(f"  flight_dump rank {fd.get('rank')}: "
                  f"{fd.get('reason')} (exit {fd.get('exit_code')})"
                  f"{where} -> {fd.get('bundle')}")
    prof = s.get("profile")
    if prof:
        # conditional by design: runs that never profiled summarize
        # exactly as before this section existed
        print_profile_section(prof)
    mem = s.get("memory") or {}
    if any(mem.get(k) for k in ("top_allocations", "peak_hbm_bytes",
                                "executables", "oom_events")):
        print_memory_section(mem)
    if s.get("phases"):
        print_phases_section(s["phases"])
    if s["bench"]:
        print("\nbench results:")
        for cfg, m in sorted(s["bench"].items()):
            keys = ("n_states", "engine_init_s", "device_ms",
                    "batch4_ms_per_vector", "lanczos_iters_per_s")
            line = "  ".join(f"{k}={m[k]}" for k in keys if k in m)
            print(f"  {cfg:<28} {line}")
    for sv in s["solvers"]:
        trace = sv.get("trace", [])
        head = (f"\nsolver {sv.get('solver')} (proc {sv.get('proc')}): "
                f"iters={sv.get('iters')} converged={sv.get('converged')}")
        if sv.get("eigenvalues"):
            head += f" E0={sv['eigenvalues'][0]:.10f}"
        print(head)
        if trace:
            print("  iter   basis    ritz[0]            max|residual|")
            for t in trace:
                ritz = (t.get("ritz") or [float("nan")])[0]
                res = max(t.get("residual") or [float("nan")])
                print(f"  {str(t.get('iter')):<6} {str(t.get('basis_size')):<8}"
                      f" {ritz:<18.12g} {res:.3e}")


def _fmt_bytes(b) -> str:
    if b is None:
        return "-"
    b = float(b)
    for unit in ("B", "KB", "MB", "GB"):
        if abs(b) < 1024 or unit == "GB":
            return f"{b:.1f} {unit}" if unit != "B" else f"{int(b)} B"
        b /= 1024
    return f"{b:.1f} GB"


def print_profile_section(prof: dict) -> None:
    """Render the :func:`profile_summary` digest: newest HLO cost
    artifact + top-3 hottest ops, per-program cost totals, capture
    counts, and the overhead latch if it fired."""
    print("\nprofiling (hlo cost attribution / trace captures):")
    newest = prof.get("newest")
    if newest:
        print(f"  newest profile: {newest['program']} "
              f"[{newest['fingerprint']}]"
              + (f" -> {newest['artifact']}" if newest.get("artifact")
                 else ""))
        for o in newest.get("top_ops") or []:
            print(f"    hot op {o.get('name'):<32} {o.get('opcode'):<20} "
                  f"{o.get('phase'):<12} "
                  f"bytes={_fmt_bytes(o.get('bytes'))} "
                  f"flops={float(o.get('flops') or 0.0):.3g}")
    for p, rec in sorted((prof.get("programs") or {}).items()):
        print(f"  {p:<36} [{rec.get('fingerprint')}] "
              f"{rec.get('n_ops')} ops  "
              f"flops={float(rec.get('flops') or 0.0):.3g}  "
              f"bytes={_fmt_bytes(rec.get('bytes'))}")
    caps = prof.get("captures") or {}
    if caps:
        print("  captures: " + "  ".join(f"{k}={v}" for k, v
                                         in sorted(caps.items())))
    if prof.get("latched"):
        lt = prof["latched"]
        print(f"  OVERHEAD LATCH: sampling off at "
              f"{float(lt.get('overhead_pct') or 0.0):.2f}% measured "
              f"(budget {float(lt.get('budget_pct') or 0.0):.2f}%)")


def print_memory_section(mem: dict) -> None:
    """Render the :func:`memory_summary` digest: ledger top allocations
    and totals per rank, peak HBM watermarks, executable analyses sorted
    by temp bytes, OOM reports (the ``summarize`` memory section and the
    body of ``report --memory``)."""
    print("\nmemory (device-memory ledger / watermarks / executables):")
    totals = mem.get("ledger_total_bytes") or {}
    peaks = mem.get("peak_hbm_bytes") or {}
    for r in sorted(set(totals) | set(peaks)):
        ctx = (mem.get("ledger_context") or {}).get(r) or {}
        note = " ".join(f"{k}={v}" for k, v in ctx.items()
                        if k in ("mode", "n_states", "T0"))
        print(f"  rank {r}: ledger {_fmt_bytes(totals.get(r))} resident, "
              f"peak HBM {_fmt_bytes(peaks.get(r))}"
              + (f"  ({note})" if note else ""))
        full = (mem.get("fullest") or {}).get(r)
        if full:
            print(f"    fullest device {full.get('device')} at "
                  f"{full.get('tag')}: "
                  f"{_fmt_bytes(full.get('bytes_in_use'))} in use"
                  f"{'' if full.get('synced') else ' (work in flight)'}, "
                  f"{_fmt_bytes(full.get('ledger_bytes'))} of it in the "
                  f"ledger, peak {_fmt_bytes(full.get('peak_bytes_in_use'))}")
    for r, rows in sorted((mem.get("top_allocations") or {}).items()):
        print(f"  top allocations (rank {r}):")
        for row in rows:
            print(f"    {row['path']:<52} {_fmt_bytes(row['bytes']):>12}")
    exes = mem.get("executables") or {}
    if exes:
        print("  compiled executables (memory_analysis; by temp bytes):")
        rows = sorted(exes.items(),
                      key=lambda kv: -(kv[1].get("temp_bytes") or 0))
        for key, a in rows[:10]:
            print(f"    {a.get('program', key):<36} "
                  f"args={_fmt_bytes(a.get('argument_bytes')):>10} "
                  f"out={_fmt_bytes(a.get('output_bytes')):>10} "
                  f"temp={_fmt_bytes(a.get('temp_bytes')):>10}")
    ooms = mem.get("oom_events") or []
    if ooms:
        print(f"  {len(ooms)} OOM memory_report event(s):")
        for ev in ooms[:5]:
            print(f"    rank {ev.get('rank')} context={ev.get('context')} "
                  f"ledger={_fmt_bytes(ev.get('ledger_total_bytes'))}")
            for fix in (ev.get("remediation") or [])[:3]:
                print(f"      -> {fix}")
    else:
        print("  no OOM events (healthy run)")


# ---------------------------------------------------------------------------
# merge / cross-rank skew


def _sync_key(ev: dict):
    """Match identity of an event that follows a cross-rank synchronization
    point (collective engine builds, the SPMD apply barrier, solver entry/
    exit) — or None for events with no cross-rank counterpart."""
    kind = ev.get("kind")
    if kind == "matvec_apply":
        return ("matvec_apply", ev.get("engine"))
    if kind in ("engine_init", "rank_shards"):
        return (kind, ev.get("engine"), ev.get("mode"))
    if kind in ("solver_start", "solver_end"):
        return (kind, ev.get("solver"))
    return None


def _sync_points(events: List[dict]) -> Dict[int, Dict[tuple, float]]:
    """Per rank: {match_key + occurrence ordinal: ts}.  Repeated events
    align POSITIONALLY — SPMD ranks execute the same program order, so the
    i-th occurrence on every rank is the same synchronization point."""
    pts: Dict[int, Dict[tuple, float]] = {}
    occ: Dict[int, Dict[tuple, int]] = {}
    for ev in events:                       # (rank, seq)-ordered
        k = _sync_key(ev)
        if k is None or "ts" not in ev:
            continue
        r = _rank_of(ev)
        i = occ.setdefault(r, {}).get(k, 0)
        occ[r][k] = i + 1
        pts.setdefault(r, {})[k + (i,)] = float(ev["ts"])
    return pts


def _median(vals: List[float]) -> float:
    return statistics.median(vals) if vals else 0.0


def estimate_skew(events: List[dict]) -> Dict[int, float]:
    """{rank: seconds} — each rank's estimated wall-clock offset relative
    to the lowest rank (median over matched sync events; the median is
    robust against the genuine compute skew the report is trying to
    surface).  Subtract a rank's offset from its ``ts`` to align."""
    pts = _sync_points(events)
    if not pts:
        return {}
    ranks = sorted(pts)
    r0 = ranks[0]
    offsets = {r0: 0.0}
    for r in ranks[1:]:
        common = set(pts[r0]) & set(pts[r])
        offsets[r] = _median([pts[r][k] - pts[r0][k] for k in common]) \
            if common else 0.0
    return offsets


def merge_events(events: List[dict]):
    """(merged, offsets): every event gains a skew-corrected ``ts_adj`` and
    the stream is ordered by ``(ts_adj, rank, seq)`` — one timeline for
    the whole multi-rank run."""
    offsets = estimate_skew(events)
    merged = []
    for ev in events:
        e = dict(ev)
        e["ts_adj"] = round(
            float(ev.get("ts", 0.0)) - offsets.get(_rank_of(ev), 0.0), 6)
        merged.append(e)
    merged.sort(key=lambda e: (e["ts_adj"], _rank_of(e), e.get("seq", 0)))
    return merged, offsets


def straggler_report(events: List[dict],
                     offsets: Optional[Dict[int, float]] = None) -> dict:
    """Per-apply straggler attribution over the aligned ``matvec_apply``
    events (the i-th apply on each rank is the same collective): the
    straggler is the rank whose skew-corrected event lands LAST — every
    other rank sat at the all_to_all barrier for (max − own) seconds — and
    its excess is max − median (how much the barrier would shrink if the
    straggler ran like a typical rank).

    Caveat: the timestamps are host DISPATCH times (the telemetry layer
    never adds a sync), so on deeply-async backends a slow device shows up
    only once queue back-pressure or a solver's block fetch re-couples the
    host to the device — interpret per-apply numbers there as block-level
    skew, not per-collective truth.  Eager loops and the CPU rig track the
    device closely and read directly."""
    if offsets is None:
        offsets = estimate_skew(events)
    per: Dict[int, List[tuple]] = {}
    for ev in events:
        if ev.get("kind") == "matvec_apply" and "ts" in ev:
            r = _rank_of(ev)
            per.setdefault(r, []).append(
                (float(ev["ts"]) - offsets.get(r, 0.0), ev.get("apply")))
    ranks = sorted(per)
    n = min((len(v) for v in per.values()), default=0)
    stats = {r: {"barrier_wait_ms": 0.0, "straggled": 0, "excess_ms": 0.0}
             for r in ranks}
    worst = []
    for i in range(n):
        ts = {r: per[r][i][0] for r in ranks}
        tmax = max(ts.values())
        tmed = _median(list(ts.values()))
        strag = max(ts, key=lambda r: ts[r])
        excess = (tmax - tmed) * 1e3
        for r in ranks:
            stats[r]["barrier_wait_ms"] += (tmax - ts[r]) * 1e3
        stats[strag]["straggled"] += 1
        stats[strag]["excess_ms"] += excess
        # carry the straggling EVENT's own apply field: a rank that ran
        # several engines restarts each engine's apply counter, so the
        # stream ordinal alone would not grep back to the actual event
        worst.append((excess, i, per[strag][i][1], strag))
    worst.sort(reverse=True, key=lambda w: w[0])
    for r in ranks:
        stats[r]["barrier_wait_ms"] = round(
            stats[r]["barrier_wait_ms"] / n, 4) if n else 0.0
        stats[r]["excess_ms"] = round(stats[r]["excess_ms"], 4)
    return {"applies": n, "ranks": ranks, "per_rank": stats,
            "worst": [{"ordinal": i, "apply": a, "rank": r,
                       "excess_ms": round(e, 4)}
                      for e, i, a, r in worst[:5] if e > 0]}


def rank_table(events: List[dict],
               offsets: Optional[Dict[int, float]] = None) -> dict:
    """The per-rank skew table: events, survivor states (from
    ``rank_shards``), eager applies + bytes exchanged (``matvec_apply``),
    plan-build wall (``engine_init``), double-buffer stalls (final metrics
    snapshot), estimated clock skew, mean time-at-barrier and straggler
    counts (:func:`straggler_report`)."""
    if offsets is None:
        offsets = estimate_skew(events)
    strag = straggler_report(events, offsets)
    # collective vs replica topology: ranks of ONE sharded job own disjoint
    # shard ids; overlapping ids mean rank-local replica engines (each rank
    # holds everything) — there the barrier columns measure relative
    # progress skew between replicas, not waits at a shared collective
    shard_sets = {}
    for ev in events:
        if ev.get("kind") == "rank_shards" and ev.get("shards") is not None:
            shard_sets[_rank_of(ev)] = set(ev["shards"])
    collective = True
    if len(shard_sets) > 1:
        seen: set = set()
        for s in shard_sets.values():
            if seen & s:
                collective = False
                break
            seen |= s
    rows = []
    for r in sorted({_rank_of(ev) for ev in events}):
        mine = [ev for ev in events if _rank_of(ev) == r]
        shards = [ev for ev in mine if ev.get("kind") == "rank_shards"]
        applies = [ev for ev in mine if ev.get("kind") == "matvec_apply"]
        inits = [ev for ev in mine if ev.get("kind") == "engine_init"]
        snaps = [ev for ev in mine if ev.get("kind") == "metrics_snapshot"]
        peaks = [int(ev.get("peak_bytes") or 0) for ev in mine
                 if ev.get("kind") == "memory_watermark"]
        db = None
        if snaps:
            hists = snaps[-1].get("metrics", {}).get("histograms", {})
            for name, h in hists.items():
                if name.split("{", 1)[0] == "double_buffer_stall_ms":
                    db = (db or 0.0) + float(h.get("sum", 0.0))
        st = strag["per_rank"].get(r, {})
        rows.append({
            "rank": r,
            "events": len(mine),
            "states": int(shards[-1]["states"])
            if shards and shards[-1].get("states") is not None else None,
            "plan_wall_s": round(sum(
                float(ev.get("build_structure_s") or 0.0)
                for ev in inits), 4) if inits else None,
            "applies": len(applies),
            "bytes_exchanged": int(sum(
                int(ev.get("bytes") or 0) for ev in applies)),
            "db_stall_ms": round(db, 3) if db is not None else None,
            "peak_hbm": max(peaks) if peaks else None,
            "skew_s": round(offsets.get(r, 0.0), 6),
            "barrier_wait_ms": st.get("barrier_wait_ms"),
            "straggled": st.get("straggled"),
        })
    return {"rows": rows, "straggler": strag, "collective": collective}


def _fmt_cell(v) -> str:
    return "-" if v is None else str(v)


def print_rank_report(table: dict, show_ranks: bool) -> None:
    strag = table["straggler"]
    if show_ranks:
        cols = ("rank", "events", "states", "applies", "bytes_exchanged",
                "plan_wall_s", "db_stall_ms", "peak_hbm", "skew_s",
                "barrier_wait_ms", "straggled")
        widths = {c: max(len(c), 12) for c in cols}
        widths["rank"] = widths["events"] = widths["applies"] = 7
        print("  ".join(f"{c:>{widths[c]}}" for c in cols))
        for row in table["rows"]:
            print("  ".join(f"{_fmt_cell(row.get(c)):>{widths[c]}}"
                            for c in cols))
    n = strag["applies"]
    if not n:
        print("no aligned matvec_apply events — straggler attribution "
              "needs a multi-rank run with eager applies")
        return
    if table.get("collective") is False:
        print("\nNOTE: ranks ran rank-local (replica) engines — no shared "
              "collective exists, so the columns below measure relative "
              "progress skew between replicas, not barrier waits")
    print(f"\nstraggler attribution over {n} aligned applies "
          "(excess = max - median arrival):")
    for r in strag["ranks"]:
        st = strag["per_rank"][r]
        print(f"  rank {r}: straggled {st['straggled']}/{n} applies, "
              f"total excess {st['excess_ms']:.3f} ms, "
              f"mean barrier wait {st['barrier_wait_ms']:.3f} ms")
    if strag["worst"]:
        w = strag["worst"][0]
        print(f"  worst apply: #{w['apply']} on rank {w['rank']} "
              f"(+{w['excess_ms']:.3f} ms over median)")


# ---------------------------------------------------------------------------
# diff


def diff_runs(base: Dict[str, Dict[str, float]],
              new: Dict[str, Dict[str, float]],
              threshold: float,
              gate_metrics: Optional[List[str]] = None,
              configs: Optional[List[str]] = None):
    """Compare per-config bench metrics.  Returns (rows, regressions):
    ``rows`` is every (config, metric, base, new, rel_change, gated) over
    the intersection; ``regressions`` the gated rows beyond threshold.
    Config selection matches by substring so `--config chain_16` finds
    `heisenberg_chain_16`."""
    gate = list(gate_metrics) if gate_metrics else list(_DEFAULT_GATE)
    rows, regressions = [], []
    common = [c for c in sorted(base) if c in new]
    if configs:
        common = [c for c in common
                  if any(sel in c for sel in configs)]

    def _gated(metric: str) -> bool:
        # exact name, or prefix when the gate entry ends in `*`
        # (`phase_*` — the --phases per-phase gate)
        return any(metric == g or (g.endswith("*")
                                   and metric.startswith(g[:-1]))
                   for g in gate)

    for cfg in common:
        for metric in sorted(set(base[cfg]) & set(new[cfg])):
            b, n = base[cfg][metric], new[cfg][metric]
            if not b:
                continue
            rel = (n - b) / abs(b)
            worse = -rel if _is_higher_better(metric) else rel
            gated = _gated(metric)
            rows.append((cfg, metric, b, n, rel, gated))
            if gated and worse > threshold:
                regressions.append((cfg, metric, b, n, rel))
    return rows, regressions, common


def print_diff(rows, regressions, common, threshold, all_metrics) -> None:
    if not common:
        print("diff: no common configs between the two runs", file=sys.stderr)
        return
    print(f"configs compared: {', '.join(common)}")
    print(f"{'config':<28} {'metric':<26} {'base':>12} {'new':>12} "
          f"{'change':>8}  gate")
    for cfg, metric, b, n, rel, gated in rows:
        if not (all_metrics or gated or abs(rel) > threshold):
            continue
        print(f"{cfg:<28} {metric:<26} {b:>12.4g} {n:>12.4g} "
              f"{rel:>+7.1%}  {'*' if gated else ''}")
    if regressions:
        print(f"\nREGRESSION: {len(regressions)} gated metric(s) beyond "
              f"{threshold:.0%}:")
        for cfg, metric, b, n, rel in regressions:
            print(f"  {cfg}: {metric} {b:.4g} -> {n:.4g} ({rel:+.1%})")
    else:
        print(f"\nno gated regression beyond {threshold:.0%}")


# ---------------------------------------------------------------------------
# trace (Chrome/Perfetto trace-event export of the merged span tree)

#: payload keys of a `span` event that are structure, not display args
_SPAN_STRUCT = ("seq", "ts", "proc", "rank", "n_ranks", "kind", "trace_id",
                "job_id", "span_id", "parent_span_id", "name", "cat", "t0",
                "dur_ms", "ts_adj")


def span_forest(events, offsets: Optional[Dict[int, float]] = None) -> Dict:
    """{rank: [root span record, ...]} from ``span`` events, skew-corrected
    into the merge's common clock.  Each record:
    ``{sid, parent, name, cat, t0, t1, args, children}`` with children
    sorted by start time.  A span whose parent never closed (crash,
    preemption) becomes a root — the tree degrades, it does not drop."""
    if offsets is None:
        offsets = estimate_skew(events)
    spans: Dict[tuple, dict] = {}
    for ev in events:
        if ev.get("kind") != "span" or not ev.get("span_id") \
                or ev.get("t0") is None:
            continue
        r = _rank_of(ev)
        t0 = float(ev["t0"]) - offsets.get(r, 0.0)
        spans[(r, str(ev["span_id"]))] = {
            "sid": str(ev["span_id"]),
            "parent": (str(ev["parent_span_id"])
                       if ev.get("parent_span_id") else None),
            "name": str(ev.get("name", "span")),
            "cat": str(ev.get("cat", "span")),
            "t0": t0,
            "t1": t0 + float(ev.get("dur_ms") or 0.0) / 1e3,
            "args": {k: v for k, v in ev.items() if k not in _SPAN_STRUCT},
            "children": [],
        }
    forest: Dict[int, list] = {}
    for (r, sid), rec in sorted(spans.items()):
        parent = spans.get((r, rec["parent"])) if rec["parent"] else None
        if parent is not None:
            parent["children"].append(rec)
        else:
            forest.setdefault(r, []).append(rec)
    for rec in spans.values():
        rec["children"].sort(key=lambda c: c["t0"])
    for roots in forest.values():
        roots.sort(key=lambda c: c["t0"])
    return forest


def _attributed_phase_ms(phases: Dict[str, dict], wall_ms: float,
                         measured_key: str) -> List[tuple]:
    """ONE shared implementation of the report-time phase attribution
    (obs/phases.py contract): ``[(phase, ms)]`` over the canonical order —
    measured walls verbatim (``measured_key`` names the field:
    ``wall_ms`` on raw ``apply_phases`` records, ``measured_wall_ms`` on
    the :func:`phases_summary` digest), the remainder split proportional
    to structural bytes, leftover appended as ``overhead``.  Both the
    Perfetto phase track and the watch phase line call this — the rule
    must not drift between them."""
    measured = {p: float(rec[measured_key]) for p, rec in phases.items()
                if rec.get(measured_key) is not None}
    rest = [p for p in _PHASE_ORDER if p in phases and p not in measured]
    rem = max(wall_ms - sum(measured.values()), 0.0)
    weights = {p: float(phases[p].get("bytes") or 0) for p in rest}
    wsum = sum(weights.values())
    out = []
    used = 0.0
    for p in _PHASE_ORDER:
        if p not in phases:
            continue
        if p in measured:
            ms = measured[p]
        elif wsum:
            ms = rem * weights[p] / wsum
        elif rest:
            ms = rem / len(rest)
        else:
            ms = 0.0
        out.append((p, ms))
        used += ms
    if wall_ms - used > 1e-9:
        out.append(("overhead", wall_ms - used))
    return out


def _phase_segments(pev: dict, t0: float, t1: float):
    """Split one apply's wall [t0, t1] into sequential phase intervals
    via :func:`_attributed_phase_ms` (approximate by construction and
    labeled as such in the track name), clamped into the apply span."""
    segs = []
    cur = t0
    for p, ms in _attributed_phase_ms(pev.get("phases") or {},
                                      (t1 - t0) * 1e3, "wall_ms"):
        d = max(min(ms / 1e3, t1 - cur), 0.0)
        if d > 0:
            segs.append((p, cur, cur + d))
        cur += d
    return segs


def perfetto_trace(events) -> dict:
    """The run as a Chrome/Perfetto trace-event JSON: one process per
    rank; track 0 the recorded span tree (solve > iteration > apply >
    chunk, B/E pairs), track 1 the per-apply phase split derived from
    each apply's ``apply_phases`` event (matched by the envelope
    ``span_id``), plus counter tracks (HBM in use, solver ritz/residual,
    lossy-tier drift).  Cross-rank alignment uses the skew-corrected
    merge, so the i-th apply lines up across rank tracks."""
    merged, offsets = merge_events(events)
    forest = span_forest(merged, offsets)
    ranks = sorted({_rank_of(ev) for ev in merged})
    # apply_phases events keyed by their apply span (envelope span_id)
    phase_evs: Dict[tuple, dict] = {}
    for ev in merged:
        if ev.get("kind") == "apply_phases" and ev.get("span_id"):
            phase_evs[(_rank_of(ev), str(ev["span_id"]))] = ev

    t_candidates = [rec["t0"] for roots in forest.values() for rec in roots]
    t_candidates += [ev["ts_adj"] for ev in merged if "ts_adj" in ev]
    t_base = min(t_candidates) if t_candidates else 0.0

    def us(t: float) -> float:
        return round((t - t_base) * 1e6, 1)

    te: List[dict] = []
    for r in ranks:
        te.append({"ph": "M", "pid": r, "tid": 0, "name": "process_name",
                   "args": {"name": f"rank {r}"}})
        te.append({"ph": "M", "pid": r, "tid": 0, "name": "thread_name",
                   "args": {"name": "spans"}})
        te.append({"ph": "M", "pid": r, "tid": 1, "name": "thread_name",
                   "args": {"name": "phases (attributed)"}})

    def walk(rec: dict, lo: float, hi: float, pid: int) -> None:
        # clamp into the parent and keep siblings sequential — sub-µs
        # clock rounding must never produce an unbalanced B/E pair
        t0 = min(max(rec["t0"], lo), hi)
        t1 = min(max(rec["t1"], t0), hi)
        te.append({"ph": "B", "pid": pid, "tid": 0, "ts": us(t0),
                   "name": rec["name"], "cat": rec["cat"],
                   "args": dict(rec["args"], span_id=rec["sid"])})
        cursor = t0
        for child in rec["children"]:
            walk(child, max(cursor, t0), t1, pid)
            cursor = max(cursor, min(max(child["t1"], child["t0"]), t1))
        te.append({"ph": "E", "pid": pid, "tid": 0, "ts": us(t1)})
        if rec["cat"] == "apply":
            pev = phase_evs.get((pid, rec["sid"]))
            if pev is not None:
                label = f"apply #{rec['args'].get('apply', '?')}"
                te.append({"ph": "B", "pid": pid, "tid": 1, "ts": us(t0),
                           "name": label, "cat": "apply"})
                for p, s0, s1 in _phase_segments(pev, t0, t1):
                    te.append({"ph": "B", "pid": pid, "tid": 1,
                               "ts": us(s0), "name": p, "cat": "phase"})
                    te.append({"ph": "E", "pid": pid, "tid": 1,
                               "ts": us(s1)})
                te.append({"ph": "E", "pid": pid, "tid": 1, "ts": us(t1)})

    for r in ranks:
        for root in forest.get(r, []):
            walk(root, root["t0"], max(root["t1"], root["t0"]), r)

    # counter (value) tracks from the gauge-bearing events
    for ev in merged:
        r, ts = _rank_of(ev), ev.get("ts_adj")
        if ts is None:
            continue
        kind = ev.get("kind")
        if kind == "memory_watermark" \
                and ev.get("bytes_in_use") is not None:
            te.append({"ph": "C", "pid": r, "ts": us(ts),
                       "name": "hbm_bytes_in_use",
                       "args": {"bytes": int(ev["bytes_in_use"])}})
        elif kind == "lanczos_trace":
            ritz = ev.get("ritz") or []
            res = ev.get("residual") or []
            if ritz:
                te.append({"ph": "C", "pid": r, "ts": us(ts),
                           "name": "ritz0",
                           "args": {"value": float(ritz[0])}})
            if res:
                te.append({"ph": "C", "pid": r, "ts": us(ts),
                           "name": "residual_max",
                           "args": {"value": float(max(res))}})
        elif kind == "compress_drift" and ev.get("rel_err") is not None:
            te.append({"ph": "C", "pid": r, "ts": us(ts),
                       "name": "compress_rel_err",
                       "args": {"value": float(ev["rel_err"])}})

    ident = {}
    for ev in merged:
        if ev.get("trace_id"):
            ident = {"trace_id": ev["trace_id"],
                     "job_id": ev.get("job_id")}
            break
    return {"traceEvents": te, "displayTimeUnit": "ms",
            "otherData": dict(ident, ranks=ranks,
                              skew_s={str(r): round(o, 6)
                                      for r, o in offsets.items()})}


def validate_trace_events(te: List[dict]) -> None:
    """Stack-check the B/E pairing per (pid, tid): every E matches the
    innermost open B and every track closes balanced.  Raises ValueError
    — the trace-check gate and the 2-process test call this on the
    export."""
    stacks: Dict[tuple, list] = {}
    for ev in te:
        ph = ev.get("ph")
        if ph not in ("B", "E"):
            continue
        key = (ev.get("pid"), ev.get("tid"))
        if ph == "B":
            stacks.setdefault(key, []).append(ev)
        else:
            if not stacks.get(key):
                raise ValueError(f"unbalanced E on track {key}")
            stacks[key].pop()
    for key, st in stacks.items():
        if st:
            raise ValueError(
                f"{len(st)} unclosed B event(s) on track {key}: "
                f"{[e.get('name') for e in st]}")


# ---------------------------------------------------------------------------
# watch (live terminal dashboard over the rank streams)

#: sliding window for the apply-rate column (seconds of event time)
_WATCH_WINDOW_S = 60.0


def empty_watch_base() -> dict:
    """Carried aggregates of events already TRIMMED from a live watch's
    window (see :func:`watch_fold`): total counts survive the trim, while
    rate/solver/phase state only ever needs the retained tail."""
    return {"n_events": 0, "applies": {}, "bytes": {},
            "health": {"warn": 0, "critical": 0, "faults": 0,
                       "io_retries": 0, "stalls": 0},
            "alerts": 0, "slo_firing": {}}


def watch_fold(base: dict, dropped: List[dict]) -> dict:
    """Fold trimmed events' countable state into ``base`` so a bounded
    live watch still reports exact lifetime totals."""
    for ev in dropped:
        base["n_events"] += 1
        r = _rank_of(ev)
        kind = ev.get("kind")
        if kind == "matvec_apply":
            base["applies"][r] = base["applies"].get(r, 0) + 1
            base["bytes"][r] = base["bytes"].get(r, 0) \
                + int(ev.get("bytes") or 0)
        elif kind in ("health", "solver_health"):
            lv = str(ev.get("level"))
            if lv in ("warn", "critical"):
                base["health"][lv] += 1
        elif kind == "fault_injected":
            base["health"]["faults"] += 1
        elif kind == "io_retry":
            base["health"]["io_retries"] += 1
        elif kind == "stall_report":
            base["health"]["stalls"] += 1
        elif kind == "slo_alert":
            # an alert's firing/clear pair may be split by the trim —
            # carry the latched firing state alongside the total so the
            # panel stays truthful across a bounded multi-hour watch
            name = str(ev.get("slo"))
            if ev.get("state") == "firing":
                base["alerts"] = base.get("alerts", 0) + 1
                base.setdefault("slo_firing", {})[name] = {
                    "burn": ev.get("burn"), "target": ev.get("target"),
                    "mode": ev.get("mode")}
            else:
                base.setdefault("slo_firing", {}).pop(name, None)
    return base


def watch_state(events, window_s: float = _WATCH_WINDOW_S,
                base: Optional[dict] = None) -> dict:
    """Aggregate one frame's worth of dashboard state from an event list
    (plus ``base``, the carried totals of already-trimmed events in live
    mode).  Pure function of its inputs (``now`` = the newest timestamp),
    so a recorded stream renders a deterministic frame — the golden-frame
    test pins the format."""
    offsets = estimate_skew(events)
    ranks = sorted({_rank_of(ev) for ev in events}
                   | set((base or {}).get("applies", ())))
    now = max((float(ev["ts"]) for ev in events if "ts" in ev),
              default=0.0)
    per_rank: Dict[int, dict] = {
        r: {"applies": 0, "recent": 0, "last_wall_ms": None,
            "bytes": 0, "hbm": None, "hbm_peak": None, "host": None}
        for r in ranks}
    solver = None
    solver_done = None
    health = {"warn": 0, "critical": 0, "faults": 0, "io_retries": 0,
              "stalls": 0}
    drift = None
    ident: Dict[str, str] = {}
    # solve-service state (serve/, DESIGN.md §26): latest status per
    # job_id, admission verdict tallies, last engine_pool occupancy
    serve_jobs: Dict[str, str] = {}
    serve_admissions: Dict[str, int] = {}
    serve_last_admission = None
    serve_pool = None
    # SLO burn-rate alert state (obs/slo.py): currently-firing SLOs
    # (latest firing event per name, cleared on state="clear") plus the
    # lifetime fired count — carried across live-mode trims via base
    slo_firing: Dict[str, dict] = dict((base or {}).get("slo_firing", {}))
    slo_alerts = int((base or {}).get("alerts", 0))
    # continuous-profiling state (obs/profile.py + obs/hlo.py): newest
    # HLO cost profile seen and trace-capture counts per kind
    prof_newest = None
    prof_captures: Dict[str, int] = {}
    for ev in events:
        r = _rank_of(ev)
        kind = ev.get("kind")
        if not ident and ev.get("trace_id"):
            ident = {"trace_id": str(ev["trace_id"]),
                     "job_id": str(ev.get("job_id") or "")}
        if kind == "matvec_apply":
            row = per_rank[r]
            row["applies"] += 1
            row["bytes"] += int(ev.get("bytes") or 0)
            if ev.get("wall_ms") is not None:
                row["last_wall_ms"] = float(ev["wall_ms"])
            if "ts" in ev and float(ev["ts"]) >= now - window_s:
                row["recent"] += 1
        elif kind == "lanczos_trace":
            solver = {"solver": str(ev.get("solver")),
                      "iter": ev.get("iter"),
                      "basis": ev.get("basis_size"),
                      "ritz0": (ev.get("ritz") or [None])[0],
                      "res_max": max(ev["residual"])
                      if ev.get("residual") else None}
        elif kind == "solver_end":
            solver_done = {"solver": str(ev.get("solver")),
                           "converged": bool(ev.get("converged")),
                           "iters": ev.get("iters")}
        elif kind in ("health", "solver_health"):
            lv = str(ev.get("level"))
            if lv in ("warn", "critical"):
                health[lv] += 1
        elif kind == "fault_injected":
            health["faults"] += 1
        elif kind == "io_retry":
            health["io_retries"] += 1
        elif kind == "stall_report":
            health["stalls"] += 1
        elif kind == "memory_watermark":
            row = per_rank[r]
            if ev.get("fullest"):
                # one device's in-use beside that device's peak
                row["hbm"] = int(ev["fullest"]["bytes_in_use"])
            elif ev.get("bytes_in_use") is not None:
                row["hbm"] = int(ev["bytes_in_use"])
            if ev.get("peak_bytes") is not None:
                row["hbm_peak"] = max(row["hbm_peak"] or 0,
                                      int(ev["peak_bytes"]))
        elif kind == "memory_ledger":
            if ev.get("total_bytes") is not None:
                per_rank[r]["host"] = int(ev["total_bytes"])
        elif kind == "compress_drift":
            if ev.get("rel_err") is not None:
                drift = float(ev["rel_err"])
        elif kind == "job_event":
            jid = str(ev.get("job_id") or "?")
            serve_jobs[jid] = str(ev.get("status"))
        elif kind == "admission":
            v = str(ev.get("verdict"))
            serve_admissions[v] = serve_admissions.get(v, 0) + 1
            serve_last_admission = {
                "job_id": str(ev.get("job_id") or "?"), "verdict": v,
                "eta_s": ev.get("eta_s"),
                "est_solve_s": ev.get("est_solve_s")}
        elif kind == "engine_pool":
            serve_pool = {
                "engines": ev.get("engines"),
                "pool_bytes": ev.get("pool_bytes"),
                "pool_max_bytes": ev.get("pool_max_bytes"),
                "builds": ev.get("builds"), "hits": ev.get("hits"),
                "evictions": ev.get("evictions")}
        elif kind == "slo_alert":
            name = str(ev.get("slo"))
            if ev.get("state") == "firing":
                slo_alerts += 1
                slo_firing[name] = {"burn": ev.get("burn"),
                                    "target": ev.get("target"),
                                    "mode": ev.get("mode")}
            else:
                slo_firing.pop(name, None)
        elif kind == "hlo_cost":
            prof_newest = {"program": str(ev.get("program")),
                           "fingerprint": str(ev.get("fingerprint",
                                                     ""))[:16],
                           "top_ops": list(ev.get("top_ops") or [])[:3]}
        elif kind == "profile_captured":
            cap = str(ev.get("capture") or "unknown")
            prof_captures[cap] = prof_captures.get(cap, 0) + 1
    n_events = len(events)
    if base:
        n_events += base["n_events"]
        for r, n in base["applies"].items():
            per_rank[r]["applies"] += n
        for r, b in base["bytes"].items():
            per_rank[r]["bytes"] += b
        for k, v in base["health"].items():
            health[k] += v
    strag = straggler_report(events, offsets)
    serve = None
    if serve_jobs or serve_admissions or serve_pool:
        counts: Dict[str, int] = {}
        for st in serve_jobs.values():
            counts[st] = counts.get(st, 0) + 1
        serve = {"jobs": counts, "n_jobs": len(serve_jobs),
                 "admissions": serve_admissions,
                 "last_admission": serve_last_admission,
                 "pool": serve_pool}
    slo = None
    if slo_alerts or slo_firing:
        slo = {"alerts_total": slo_alerts, "firing": slo_firing}
    profile = None
    if prof_newest or prof_captures:
        profile = {"newest": prof_newest, "captures": prof_captures}
    return {"ident": ident, "ranks": ranks, "n_events": n_events,
            "now": now, "window_s": window_s, "per_rank": per_rank,
            "phases": phases_summary(events), "solver": solver,
            "solver_done": solver_done, "straggler": strag,
            "health": health, "drift": drift, "serve": serve,
            "slo": slo, "profile": profile}


def _fmt_rate(n: int, window_s: float) -> str:
    return f"{n / window_s:.2f}/s"


def render_watch(state: dict) -> str:
    """One dashboard frame (plain text, ~10 lines): apply rate per rank,
    per-phase time split, solver convergence, straggler skew, health /
    fault counters, memory watermarks.  Format is pinned by the
    golden-frame test — extend by appending lines, not reshaping."""
    ident = state.get("ident") or {}
    head = (f"obs watch | trace {str(ident.get('trace_id', '-'))[:8]}"
            f" | job {str(ident.get('job_id', '-'))[:8]}"
            f" | {len(state['ranks'])} rank(s)"
            f" | {state['n_events']} events")
    lines = [head, "-" * len(head)]
    cells = []
    for r in state["ranks"]:
        row = state["per_rank"][r]
        wall = (f"{row['last_wall_ms']:.1f} ms"
                if row["last_wall_ms"] is not None else "-")
        cells.append(f"rank{r}: {row['applies']} "
                     f"({_fmt_rate(row['recent'], state['window_s'])}, "
                     f"last {wall})")
    lines.append("applies   " + "   ".join(cells) if cells
                 else "applies   (none yet)")
    for key, grp in sorted((state.get("phases") or {}).items()):
        parts = []
        wall = grp.get("mean_wall_ms") or 0.0
        for p, ms in _attributed_phase_ms(grp.get("phases") or {}, wall,
                                          "measured_wall_ms"):
            if wall <= 0 or ms <= 0:
                continue
            if p == "overhead" and ms <= 0.05 * wall:
                continue        # sub-noise remainder: not worth a column
            parts.append(f"{p} {100 * ms / wall:.0f}%")
        if parts:
            lines.append(f"phases    {key}: " + " | ".join(parts)
                         + f"  ({wall:.1f} ms/apply)")
    sv = state.get("solver")
    if sv is not None:
        ritz = (f"{sv['ritz0']:.8f}" if sv.get("ritz0") is not None
                else "-")
        res = (f"{sv['res_max']:.2e}" if sv.get("res_max") is not None
               else "-")
        done = state.get("solver_done")
        tail = ""
        if done and done.get("solver") == sv.get("solver"):
            tail = ("  [converged]" if done["converged"]
                    else "  [ended, not converged]")
        lines.append(f"solver    {sv['solver']}: iter {sv['iter']}, "
                     f"basis {sv['basis']}, ritz0 {ritz}, "
                     f"max res {res}{tail}")
    strag = state.get("straggler") or {}
    if strag.get("applies"):
        per = strag["per_rank"]
        worst_rank = max(per, key=lambda r: per[r]["barrier_wait_ms"])
        w = (strag.get("worst") or [{}])[0] if strag.get("worst") else {}
        worst_txt = (f" (worst apply #{w.get('apply')} rank "
                     f"{w.get('rank')} +{w.get('excess_ms'):.1f} ms)"
                     if w else "")
        lines.append(
            f"skew      rank{worst_rank} waits "
            f"{per[worst_rank]['barrier_wait_ms']:.2f} ms/apply at the "
            f"barrier over {strag['applies']} aligned applies"
            f"{worst_txt}")
    h = state["health"]
    drift = state.get("drift")
    lines.append(f"health    warn {h['warn']}, critical {h['critical']} | "
                 f"faults {h['faults']}, io_retries {h['io_retries']}, "
                 f"stalls {h['stalls']} | drift "
                 + (f"{drift:.2e}" if drift is not None else "-"))
    mems = []
    for r in state["ranks"]:
        row = state["per_rank"][r]
        if row["hbm"] is None and row["hbm_peak"] is None \
                and row["host"] is None:
            continue
        mems.append(f"rank{r}: hbm {_fmt_bytes(row['hbm'])} "
                    f"(peak {_fmt_bytes(row['hbm_peak'])}, "
                    f"host ledger {_fmt_bytes(row['host'])})")
    if mems:
        lines.append("memory    " + " | ".join(mems))
    serve = state.get("serve")
    if serve:
        # the solve-service queue panel (lines appended, never reshaped
        # — the golden frame of serve-less runs is unchanged)
        order = ("queued", "running", "done", "failed", "rejected")
        jobs = serve.get("jobs") or {}
        parts = [f"{jobs[s]} {s}" for s in order if jobs.get(s)]
        parts += [f"{n} {s}" for s, n in sorted(jobs.items())
                  if s not in order]
        adm = serve.get("admissions") or {}
        adm_txt = ", ".join(f"{v} {adm[v]}" for v in
                            ("accept", "queue", "reject") if adm.get(v)) \
            or "-"
        last = serve.get("last_admission")
        last_txt = ""
        if last:
            eta = (f" eta {last['eta_s']:.1f}s"
                   if last.get("eta_s") is not None else "")
            last_txt = (f" (last {last['job_id']}: "
                        f"{last['verdict']}{eta})")
        lines.append(f"serve     {serve['n_jobs']} job(s): "
                     + (", ".join(parts) if parts else "-")
                     + f" | admissions: {adm_txt}{last_txt}")
        pool = serve.get("pool")
        if pool:
            lines.append(
                f"pool      {pool.get('engines', 0)} engine(s), "
                f"{_fmt_bytes(pool.get('pool_bytes'))} / "
                f"{_fmt_bytes(pool.get('pool_max_bytes'))} | "
                f"builds {pool.get('builds', 0)}, "
                f"hits {pool.get('hits', 0)}, "
                f"evictions {pool.get('evictions', 0)}")
    slo = state.get("slo")
    if slo:
        # the SLO/alerts panel: appended ONLY when an alert ever fired,
        # so the golden frame of alert-free runs stays byte-identical
        firing = slo.get("firing") or {}
        if firing:
            parts = []
            for name, info in sorted(firing.items()):
                burn = info.get("burn")
                burn_txt = (f" (burn {burn}x)"
                            if burn not in (None, "") else "")
                parts.append(f"{name}{burn_txt}")
            lines.append(f"slo       FIRING: " + ", ".join(parts)
                         + f" | {slo['alerts_total']} alert(s) lifetime")
        else:
            lines.append(f"slo       ok (all clear) | "
                         f"{slo['alerts_total']} alert(s) lifetime")
    prof = state.get("profile")
    if prof:
        # the profiling panel: appended ONLY when the run captured an
        # HLO cost profile or a trace window, so the golden frame of
        # profile-less runs stays byte-identical
        newest = prof.get("newest")
        parts = []
        if newest:
            hot = ",".join(str(o.get("name")) for o in
                           (newest.get("top_ops") or []))
            parts.append(f"{newest['program']} [{newest['fingerprint']}]"
                         + (f" hot: {hot}" if hot else ""))
        caps = prof.get("captures") or {}
        if caps:
            parts.append("captures: " + ", ".join(
                f"{v} {k}" for k, v in sorted(caps.items())))
        lines.append("profile   " + " | ".join(parts))
    return "\n".join(lines)


def watch_frame(events, window_s: float = _WATCH_WINDOW_S) -> str:
    """One rendered frame from an event list (the pure composition the
    golden test pins)."""
    return render_watch(watch_state(events, window_s))


#: live-mode window bound: beyond this many retained events the oldest
#: half is folded into the carried totals (watch_fold) and dropped, so a
#: multi-hour watch holds constant memory and O(window) work per frame
_WATCH_MAX_EVENTS = 60_000


def _watch_seed(files: List[str]):
    """Initial live-mode load that seeds the follow state with the byte
    offset actually CONSUMED (an append landing mid-read is picked up by
    the next poll instead of being skipped — the bug a
    ``getsize``-after-``load_events`` seed would have) and buffers a torn
    final line exactly like :func:`_follow_poll`."""
    events: List[dict] = []
    state: Dict[str, tuple] = {}
    partial: Dict[str, str] = {}
    for f in files:
        ident = _stat_id(f)
        if ident is None:
            continue
        try:
            with open(f, "rb") as fh:
                data = fh.read()
        except OSError:
            continue
        state[f] = (ident, len(data), data[:64])
        lines = data.decode("utf-8", "replace").split("\n")
        if lines[-1]:
            partial[f] = lines[-1]
        for line in lines[:-1]:
            if not line.strip():
                continue
            try:
                events.append(json.loads(line))
            except json.JSONDecodeError:
                pass
    return events, state, partial


def watch_run(path: str, once: bool, interval: float,
              window_s: float) -> int:
    """The ``watch`` subcommand: render a frame; with ``--once`` print it
    and exit, else refresh in place, tailing every rank stream with the
    same rotation-safe follow machinery as ``tail --follow`` (late-joining
    ranks are picked up each poll)."""
    if once:
        try:
            events = list(load_events(path))
        except FileNotFoundError as e:
            print(f"watch: {e}", file=sys.stderr)
            return 2
        print(watch_frame(events, window_s))
        return 0
    # live mode: an empty/not-yet-created run dir just renders an empty
    # frame until the first rank starts writing
    files = _run_files(path) if os.path.isdir(path) else [path]
    events, state, partial = _watch_seed(files)
    base = empty_watch_base()
    try:
        while True:
            frame = render_watch(watch_state(events, window_s, base))
            # home + clear-to-end: repaint in place without flicker
            sys.stdout.write("\x1b[H\x1b[2J" + frame
                             + f"\n\n(refreshing every {interval:g}s — "
                               "Ctrl-C to stop)\n")
            sys.stdout.flush()
            time.sleep(interval)
            if os.path.isdir(path):
                files = _run_files(path)
            events.extend(_follow_poll(files, state, partial))
            if len(events) > _WATCH_MAX_EVENTS:
                cut = len(events) - _WATCH_MAX_EVENTS // 2
                watch_fold(base, events[:cut])
                del events[:cut]
    except KeyboardInterrupt:
        return 0


# ---------------------------------------------------------------------------
# tail


def _fmt_event(ev: dict) -> str:
    envelope = ("seq", "ts", "proc", "kind")
    ts = time.strftime("%H:%M:%S", time.localtime(ev.get("ts", 0)))
    payload = " ".join(f"{k}={_short(v)}" for k, v in ev.items()
                       if k not in envelope)
    return (f"{ts} p{ev.get('proc', 0)} #{ev.get('seq', 0):<5} "
            f"{ev.get('kind', '?'):<18} {payload}")


def _short(v, cap: int = 60) -> str:
    s = json.dumps(v, default=repr) if isinstance(v, (dict, list)) else str(v)
    return s if len(s) <= cap else s[: cap - 3] + "..."


def _stat_id(path: str):
    """(inode, device) of a file, or None when it vanished mid-poll."""
    try:
        st = os.stat(path)
        return (st.st_ino, st.st_dev)
    except OSError:
        return None


def tail_run(path: str, n: int, follow: bool) -> None:
    evs = load_events(path)
    for ev in evs[-n:]:
        print(_fmt_event(ev))
    if not follow:
        return
    if not os.path.isdir(path) and not path.endswith(".jsonl"):
        print("--follow needs a run directory or .jsonl file",
              file=sys.stderr)
        return
    files = _run_files(path) if os.path.isdir(path) else [path]
    # per-file follow state: (inode id, byte offset, head-of-file bytes).
    # All three are checked every poll so a rotated/recreated file is
    # reopened from 0 instead of silently losing every event the new
    # writer appends: a new inode catches rename-style rotation, size <
    # offset catches in-place truncation seen while still small, and the
    # head fingerprint catches in-place truncation that REGREW past the
    # old offset between two polls (same inode, larger size — invisible
    # to the other two checks).  A file vanishing between the glob and
    # the stat (mid-rotation) is simply picked up by a later poll.
    state = {}
    for f in files:
        try:
            state[f] = (_stat_id(f), os.path.getsize(f), _head_bytes(f))
        except OSError:
            continue
    partial: Dict[str, str] = {}
    try:
        while True:
            time.sleep(0.5)
            if os.path.isdir(path):  # pick up files of late-joining ranks
                files = _run_files(path)
            for ev in _follow_poll(files, state, partial):
                print(_fmt_event(ev))
    except KeyboardInterrupt:
        pass


def _head_bytes(path: str, n: int = 64) -> bytes:
    """First ``n`` bytes of a file (the rotation fingerprint), or b''."""
    try:
        with open(path, "rb") as fh:
            return fh.read(n)
    except OSError:
        return b""


def _follow_poll(files: List[str], state: Dict[str, tuple],
                 partial: Dict[str, str]) -> List[dict]:
    """One --follow poll step over ``files``, mutating the per-file
    ``state``/``partial`` maps; returns the newly complete events."""
    out: List[dict] = []
    for f in files:
        ident = _stat_id(f)
        if ident is None:
            continue
        old_ident, off, head = state.get(f, (None, 0, b""))
        try:
            size = os.path.getsize(f)
        except OSError:     # vanished between stat and size
            continue
        head_now = _head_bytes(f)
        if ident != old_ident or size < off \
                or not head_now.startswith(head):
            # rotated (new inode), truncated in place, or truncated AND
            # regrown past the old offset (same inode, changed head):
            # restart from the top of the NEW file; a torn fragment from
            # the old one can never complete
            off = 0
            partial.pop(f, None)
        if size <= off:
            state[f] = (ident, off, head_now)
            continue
        with open(f) as fh:
            fh.seek(off)
            chunk = fh.read(size - off)
        state[f] = (ident, size, head_now)
        # a read can land mid-write: keep the torn final fragment buffered
        # until its newline arrives instead of dropping the event
        data = partial.pop(f, "") + chunk
        lines = data.split("\n")
        if lines[-1]:
            partial[f] = lines[-1]
        for line in lines[:-1]:
            if not line.strip():
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                pass
    return out


# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# slo / postmortem


def _fmt_burn(b) -> str:
    if b is None:
        return "-"
    if b == float("inf") or b == "inf":
        return "inf"
    return f"{float(b):.1f}x"


def print_slo(statuses: List[dict]) -> None:
    """Render the :func:`obs.slo.evaluate` status list: one row per SLO
    (state, mode, resolved target, sample count) plus the per-window
    burn against its threshold — the multi-window rule fires only when
    every window exceeds its bound."""
    print(f"{'SLO':<26} {'state':<9} {'mode':<10} {'target':>12} "
          f"{'samples':>8}  burn (per window)")
    for st in statuses:
        tgt = st.get("target")
        tgt_txt = "-" if tgt is None else f"{float(tgt):.6g}"
        wins = ", ".join(
            f"{w['window_s']:g}s {_fmt_burn(w['burn'])}/{w['max_burn']:g}x"
            for w in st.get("windows") or [])
        print(f"{st['name']:<26} {st['state']:<9} {st['mode']:<10} "
              f"{tgt_txt:>12} {st['samples']:>8}  {wins}")
    firing = [st["name"] for st in statuses if st["state"] == "firing"]
    if firing:
        print(f"\nFIRING: {', '.join(firing)}")
    else:
        print("\nno SLO firing")


def scan_postmortems(path: str) -> List[dict]:
    """Flight-recorder bundles of a run: ``rank_*/postmortem/*.json``
    under a run directory (or one bundle file), each re-hashed against
    the sha16 in its filename (the content-address contract of
    ``obs/flight.py``).  Standalone — reads files, imports nothing."""
    if os.path.isdir(path):
        files = [f for f in sorted(glob.glob(os.path.join(
            path, "rank_*", "postmortem", "*.json")))
            if os.path.basename(f) != "context.json"]
    else:
        files = [path]
    out = []
    for f in files:
        name = os.path.basename(f)
        stem = name[: -len(".json")] if name.endswith(".json") else name
        claimed = stem.rsplit("-", 1)[-1]
        try:
            with open(f, "rb") as fh:
                data = fh.read()
            valid = hashlib.sha256(data).hexdigest()[:16] == claimed
            bundle = json.loads(data.decode())
        except (OSError, ValueError) as e:
            out.append({"path": f, "valid": False, "error": repr(e),
                        "bundle": None})
            continue
        out.append({"path": f, "valid": valid, "bundle": bundle})
    return out


def print_postmortems(entries: List[dict]) -> None:
    for e in entries:
        b = e.get("bundle") or {}
        mark = "ok " if e["valid"] else "BAD"
        print(f"[{mark}] {e['path']}")
        if not e["valid"]:
            why = e.get("error") or ("content address mismatch - "
                                     "torn write or tampering")
            print(f"      verification FAILED ({why})")
        if not b:
            continue
        print(f"      reason={b.get('reason')} exit_code={b.get('exit_code')}"
              f" signum={b.get('signum')} rank={b.get('rank')}"
              f"/{b.get('n_ranks')}")
        ident = (f"trace_id={b.get('trace_id')}"
                 + (f" job_id={b.get('job_id')}" if b.get("job_id") else ""))
        print(f"      {ident}")
        if b.get("span_path"):
            print(f"      died in: {b['span_path']}")
        sp = b.get("span") or {}
        if sp:
            attrs = " ".join(f"{k}={v}" for k, v in sorted(sp.items())
                             if k not in ("name", "kind", "span_id"))
            print(f"      deepest span: {sp.get('name')}"
                  + (f" ({attrs})" if attrs else ""))
        evs = b.get("events") or []
        print(f"      {len(evs)} ring event(s), "
              f"{len(b.get('open_spans') or [])} open span(s)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="obs_report", description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("summarize", help="one run -> human/JSON summary")
    p.add_argument("run", help="run dir, .jsonl file, or detail .json")
    p.add_argument("--json", action="store_true",
                   help="print the machine-readable summary dict")

    p = sub.add_parser("merge", help="multi-rank run -> one ordered, "
                                     "skew-corrected timeline")
    p.add_argument("run", help="run dir with rank_*/ (or events.p*.jsonl)")
    p.add_argument("-o", "--out", default=None, metavar="OUT.jsonl",
                   help="write the merged JSONL here (default: stdout)")

    p = sub.add_parser("report", help="cross-rank skew + straggler report")
    p.add_argument("run")
    p.add_argument("--ranks", action="store_true",
                   help="include the per-rank skew table (events, survivor "
                        "states, bytes exchanged, plan wall, stalls, "
                        "per-rank peak HBM, time-at-barrier)")
    p.add_argument("--memory", action="store_true",
                   help="include the memory section (ledger top "
                        "allocations, watermark peaks, executable "
                        "analyses, OOM reports)")
    p.add_argument("--phases", action="store_true",
                   help="include the per-(engine, mode) phase table from "
                        "apply_phases events (bytes/gathers per phase, "
                        "measured plan-stream waits)")
    p.add_argument("--json", action="store_true",
                   help="print the machine-readable table dict")

    p = sub.add_parser("roofline", help="analytical roofline over the "
                                        "run's apply_phases events, plus "
                                        "the autotuner's tune_config / "
                                        "retune rows (priced vs tuned vs "
                                        "measured)")
    p.add_argument("run", help="run dir or .jsonl with apply_phases events")
    p.add_argument("--calibration", default=None, metavar="PATH",
                   help="rate-calibration JSON (tools/gather_bound.py); "
                        "default: the content-addressed sidecar, else the "
                        "DESIGN.md §2 documented defaults")
    p.add_argument("--json", action="store_true")

    p = sub.add_parser("profile", help="HLO cost profile of a run's "
                                       "compiled applies; with a second "
                                       "argument, an op-by-op "
                                       "differential diff (exit 1 on "
                                       "gated regression)")
    p.add_argument("base", help="profile artifact .json, run dir, or "
                                ".jsonl with hlo_cost events")
    p.add_argument("new", nargs="?", default=None,
                   help="candidate (same forms) — omit to just render "
                        "the base profile")
    p.add_argument("--program", default=None, metavar="SUBSTR",
                   help="select by program-name substring when a run "
                        "compiled several (default: the newest)")
    p.add_argument("--threshold", type=float, default=0.25,
                   help="per-op relative growth that gates as a "
                        "regression (default 0.25; all HLO costs are "
                        "cost-like — growth is the regression)")
    p.add_argument("--top", type=int, default=10,
                   help="rows per table (default 10)")
    p.add_argument("--json", action="store_true",
                   help="print the machine-readable profile/diff dict")

    p = sub.add_parser("diff", help="two runs -> regression report "
                                    "(exit 1 on gated regression)")
    p.add_argument("base", help="baseline run (dir/.jsonl/.json)")
    p.add_argument("new", help="candidate run (dir/.jsonl/.json)")
    p.add_argument("--threshold", type=float, default=0.2,
                   help="gated relative regression bound (default 0.2)")
    p.add_argument("--metric", action="append", default=None,
                   help="gate on this metric (repeatable; default device_ms)")
    p.add_argument("--config", action="append", default=None,
                   help="only configs whose name contains this substring")
    p.add_argument("--memory", action="store_true",
                   help="also gate on memory regressions (table_bytes, "
                        "executable temp/peak bytes, watermark peak — all "
                        "direction-aware: growth is the regression)")
    p.add_argument("--phases", action="store_true",
                   help="also gate on every phase_* bench metric "
                        "(per-phase bytes/gathers/ms — growth is the "
                        "regression)")
    p.add_argument("--all-metrics", action="store_true",
                   help="print every common metric, not just gated/changed")

    p = sub.add_parser("trace", help="Perfetto trace-event export of the "
                                     "merged span tree")
    p.add_argument("run", help="run dir with rank_*/ (or a .jsonl file)")
    p.add_argument("-o", "--out", default=None, metavar="OUT.json",
                   help="write the trace JSON here (default: stdout)")

    p = sub.add_parser("watch", help="live terminal dashboard over the "
                                     "rank streams")
    p.add_argument("run", help="run dir (or .jsonl) of a live or "
                               "recorded run")
    p.add_argument("--once", action="store_true",
                   help="render a single frame and exit")
    p.add_argument("--interval", type=float, default=1.0,
                   help="refresh period in seconds (default 1.0)")
    p.add_argument("--window", type=float, default=_WATCH_WINDOW_S,
                   help="apply-rate sliding window in seconds of event "
                        "time (default 60)")

    p = sub.add_parser("tail", help="view the last events of a run")
    p.add_argument("run")
    p.add_argument("-n", type=int, default=20)
    p.add_argument("--follow", action="store_true",
                   help="keep reading as the run appends")

    p = sub.add_parser("slo", help="burn-rate SLO evaluation over a "
                                   "recorded run (exit 1 when firing)")
    p.add_argument("run", help="run dir or .jsonl event file")
    p.add_argument("--target", action="append", default=None,
                   metavar="NAME=VALUE",
                   help="pin an explicit SLO objective by name "
                        "(repeatable; e.g. steady_apply_ms=12.5 — "
                        "unpinned thresholds self-baseline from the "
                        "run's earliest quartile)")
    p.add_argument("--json", action="store_true",
                   help="print the machine-readable status list")

    p = sub.add_parser("postmortem", help="read crash flight-recorder "
                                          "bundles (rank_*/postmortem/)")
    p.add_argument("run", help="run dir (all ranks scanned) or one "
                               "bundle .json")
    p.add_argument("--json", action="store_true",
                   help="print the machine-readable bundle list")

    args = ap.parse_args(argv)

    if args.cmd == "summarize":
        summary = run_summary(load_events(args.run))
        if args.json:
            print(json.dumps(summary, indent=1, sort_keys=True))
        else:
            print_summary(summary)
        return 0

    if args.cmd == "merge":
        merged, offsets = merge_events(load_events(args.run))
        ranks = ", ".join(f"rank {r}: {off:+.6f}s"
                          for r, off in sorted(offsets.items()))
        print(f"[obs_report] merged {len(merged)} events from "
              f"{len(offsets)} rank(s); clock-skew estimate: {ranks or '-'}",
              file=sys.stderr)
        out = open(args.out, "w") if args.out else sys.stdout
        try:
            for ev in merged:
                out.write(json.dumps(ev) + "\n")
        finally:
            if args.out:
                out.close()
        return 0

    if args.cmd == "report":
        events = load_events(args.run)
        table = rank_table(events)
        if args.memory:
            table["memory"] = memory_summary(events)
        if args.phases:
            table["phases"] = phases_summary(events)
        if args.json:
            print(json.dumps(table, indent=1, sort_keys=True))
        else:
            print_rank_report(table, show_ranks=args.ranks)
            if args.memory:
                print_memory_section(table["memory"])
            if args.phases:
                print_phases_section(table["phases"])
        return 0

    if args.cmd == "roofline":
        # the model lives in the package (obs/roofline.py) — imported
        # lazily so every other subcommand stays standalone
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from distributed_matvec_tpu.obs import roofline as _roofline

        events = load_events(args.run)
        cal = _roofline.resolve_calibration(args.calibration)
        report = _roofline.roofline_report(events, cal)
        if not report["groups"]:
            print(f"roofline: no apply_phases events in {args.run} — run "
                  "with the obs layer on (DMT_PHASES defaults on)",
                  file=sys.stderr)
            return 2
        if args.json:
            print(json.dumps(report, indent=1, sort_keys=True))
        else:
            _roofline.print_roofline(report)
        return 0

    if args.cmd == "profile":
        hlo_mod = _load_hlo()
        base = _resolve_profile(hlo_mod, args.base, args.program)
        if base is None:
            print(f"profile: no hlo profile in {args.base} — compile "
                  "with the obs + artifact layers on (both default on) "
                  "so precompile() writes hlo-profile artifacts",
                  file=sys.stderr)
            return 2
        if not args.new:
            if args.json:
                print(json.dumps(base, indent=1, sort_keys=True))
            else:
                hlo_mod.print_profile(base, top=args.top)
            return 0
        new = _resolve_profile(hlo_mod, args.new, args.program)
        if new is None:
            print(f"profile: no hlo profile in {args.new}",
                  file=sys.stderr)
            return 2
        diff = hlo_mod.diff_profiles(base, new,
                                     threshold=args.threshold,
                                     top=args.top)
        if args.json:
            print(json.dumps(diff, indent=1, sort_keys=True))
        else:
            print(f"base {base.get('program')} "
                  f"[{str(base.get('fingerprint', ''))[:16]}]  ->  "
                  f"new {new.get('program')} "
                  f"[{str(new.get('fingerprint', ''))[:16]}]")
            hlo_mod.print_profile_diff(diff)
        if diff["regressions"]:
            if not args.json:
                print(f"\nREGRESSION: {len(diff['regressions'])} "
                      f"op-axis(es) grew beyond {args.threshold:.0%}")
            return 1
        if not args.json:
            print(f"\nno per-op regression beyond {args.threshold:.0%}")
        return 0

    if args.cmd == "trace":
        trace = perfetto_trace(load_events(args.run))
        n_spans = sum(1 for ev in trace["traceEvents"]
                      if ev.get("ph") == "B" and ev.get("tid") == 0)
        validate_trace_events(trace["traceEvents"])
        if args.out:
            with open(args.out, "w") as f:
                json.dump(trace, f)
            other = trace["otherData"]
            print(f"[obs_report] wrote {args.out}: {n_spans} span(s) "
                  f"across rank(s) {other.get('ranks')}, "
                  f"trace_id={other.get('trace_id')} — open in "
                  "ui.perfetto.dev", file=sys.stderr)
        else:
            print(json.dumps(trace))
        if n_spans == 0:
            print("[obs_report] no span events in the run — record with "
                  "tracing on (DMT_TRACE defaults on)", file=sys.stderr)
            return 2
        return 0

    if args.cmd == "watch":
        return watch_run(args.run, args.once, args.interval, args.window)

    if args.cmd == "slo":
        targets = {}
        for t in args.target or []:
            name, sep, val = t.partition("=")
            if not sep:
                ap.error(f"--target expects NAME=VALUE, got {t!r}")
            try:
                targets[name] = float(val)
            except ValueError:
                ap.error(f"--target {name}: not a number: {val!r}")
        slo_mod = _load_slo()
        statuses = slo_mod.evaluate(load_events(args.run),
                                    slo_mod.default_slos(targets))
        if args.json:
            print(json.dumps(statuses, indent=1, sort_keys=True,
                             default=str))
        else:
            print_slo(statuses)
        return 1 if any(st["state"] == "firing" for st in statuses) else 0

    if args.cmd == "postmortem":
        entries = scan_postmortems(args.run)
        if args.json:
            print(json.dumps(entries, indent=1, sort_keys=True))
        else:
            print_postmortems(entries)
        if not entries:
            print(f"postmortem: no bundles under {args.run} (no crash "
                  "recorded — a clean run leaves none)", file=sys.stderr)
            return 2
        return 0 if all(e["valid"] for e in entries) else 1

    if args.cmd == "diff":
        base = bench_metrics(load_events(args.base))
        new = bench_metrics(load_events(args.new))
        gate = list(args.metric) if args.metric else list(_DEFAULT_GATE)
        if args.memory:
            gate += [m for m in _MEMORY_GATE if m not in gate]
        if args.phases:
            gate += [m for m in _PHASE_GATE if m not in gate]
        rows, regressions, common = diff_runs(
            base, new, args.threshold, gate, args.config)
        print_diff(rows, regressions, common, args.threshold,
                   args.all_metrics)
        if not common:
            return 2
        return 1 if regressions else 0

    tail_run(args.run, args.n, args.follow)
    return 0


if __name__ == "__main__":
    sys.exit(main())
