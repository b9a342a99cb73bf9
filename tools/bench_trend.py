#!/usr/bin/env python
"""bench_trend — track and gate the cross-PR benchmark trajectory.

The repo accumulates per-round benchmark artifacts (BENCH_r0*.json,
BENCH_STREAM_r05.json, BENCH_DETAIL.json), but nothing tracked the
*trajectory*: a PR that quietly gave back half of round 5's streamed
speedup would pass every per-run gate.  This tool closes that loop:

* ``bench.py`` appends ONE compact record per bench run to
  ``PROGRESS.jsonl`` (the repo's append-only progress ledger — trend
  records carry ``"kind": "bench_trend"`` and readers here skip every
  other line, so the driver's own records are untouched)::

      {"kind": "bench_trend", "ts": ..., "mode": "smoke|full|serve",
       "backend": "cpu", "configs": {name: {metric: value, ...}}}

* ``trend`` renders the per-(config, metric) trajectory across records;
* ``gate`` compares the NEWEST record against the best earlier record of
  the same (mode, backend) — direction-aware exactly like
  ``obs_report diff`` (ms/bytes up is a regression, iters-per-second /
  speedups down is) — and exits 1 beyond the threshold.  Configs whose
  ``n_states`` changed between records are skipped (a re-scoped config is
  a different experiment, not a regression).

Subcommands::

    append --detail BENCH_DETAIL.json [--progress PATH] [--mode M]
           [--backend B]
    trend  [--progress PATH] [--config C ...] [--metric M ...] [--last N]
           [--json]
    gate   [--progress PATH] [--threshold 0.3] [--metric M ...]
           [--config C ...] [--baseline best|prev]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import obs_report  # noqa: E402  (direction rules live in ONE shared
#   table, distributed_matvec_tpu/obs/directions.py, loaded by
#   obs_report — both tools judge every metric through the same entry)

KIND = "bench_trend"

#: Metrics worth carrying across PRs (compact: one line per run).  Any
#: ``phase_*`` metric rides along too (per-phase bytes/gathers from the
#: apply_phases instrumentation — what a plan-compression PR gates on).
METRIC_WHITELIST = (
    "n_states", "device_ms", "batch4_ms_per_vector", "lanczos_iters_per_s",
    "lanczos_e0", "engine_init_s", "table_bytes", "peak_hbm_bytes",
    "fused_steady_apply_ms", "streamed_steady_apply_ms",
    "stream_steady_speedup", "plan_bytes", "plan_build_s",
    "plan_stream_stall_ms", "apply_wall_ms", "speedup_vs_numpy",
    "plan_bytes_encoded", "compress_ratio", "compressed_steady_apply_ms",
    "compress_steady_speedup", "compress_rel_err", "compress_drift_max",
    "pipelined_steady_apply_ms", "pipelined_steady_speedup",
    "barrier_ms", "overlap_fraction", "pipeline_depth",
    "hybrid_plan_bytes", "hybrid_steady_apply_ms",
    "hybrid_steady_speedup", "hybrid_stream_term_fraction",
    "hybrid_bit_identical",
    "autotuned_steady_apply_ms", "autotuned_steady_speedup",
    "tune_search_s", "best_hand_steady_apply_ms",
    "autotuned_bit_identical",
    "serve_jobs", "serve_jobs_done", "serve_wall_s",
    "serve_solves_per_min", "serve_p50_latency_ms",
    "serve_p99_latency_ms", "serve_engine_builds", "serve_engine_hits",
    "serve_batch_speedup", "serve_e0_max_rel_err", "solo_wall_s",
    "resume_reshard_s", "resume_rebuild_plan_s",
    "kpm_moments_per_s", "kpm_dos_rel_err", "kpm_n_moments",
    "kpm_apply_ms", "evolve_steps_per_s", "evolve_norm_drift",
    "evolve_energy_drift", "evolve_steps",
    "slo_alert_count",
    "hlo_flops", "hlo_bytes", "profile_overhead_pct",
)

#: Default gated metrics (exact names; ``*`` suffix = prefix match, as in
#: ``obs_report diff``).  ``compress_ratio`` guards the plan codec: a PR
#: that quietly gives back the encoded-bytes win fails the gate even if
#: wall clocks hold.  The drift pair (``compress_rel_err`` one-shot vs
#: fused, ``compress_drift_max`` worst probe-cadence sample — both
#: cost-like, error growth is the regression per obs_report's direction
#: rule) guards the lossy tiers' NUMERICS: quantized coefficients whose
#: error quietly grows fail the gate even when wall clocks and ratios
#: hold.  Lossless runs record 0.0, which the gate skips as a baseline —
#: the pair only arms on quantized-tier records.  The pipelined pair
#: (``barrier_ms`` time-at-barrier, ``pipelined_steady_apply_ms`` wall —
#: both cost-like under obs_report's direction rule) guards the overlap
#: win: a PR that quietly re-exposes the staging latency the pipeline
#: hides fails the gate even when the sequential walls hold.
#: The serve pair (``serve_solves_per_min`` higher-is-better via the
#: shared direction table in distributed_matvec_tpu/obs/directions.py,
#: ``serve_p99_latency_ms`` cost-like) guards the solve service's
#: throughput/latency: a PR that quietly halves serving throughput or
#: doubles tail latency fails the gate even when single-solve walls hold.
#: The elastic pair (``resume_reshard_s`` — the D→D′ checkpoint
#: redistribution wall, ``resume_rebuild_plan_s`` — the per-D′ streamed
#: plan rebuild on resume; both cost-like seconds under the shared
#: direction table in distributed_matvec_tpu/obs/directions.py) guards
#: the elastic-resume path: a PR that quietly makes topology-portable
#: restores expensive fails the gate even when steady applies hold.
#: The hybrid pair (``hybrid_plan_bytes`` — the partial-term plan's
#: encoded bytes, ``hybrid_steady_apply_ms`` — its steady apply wall;
#: both cost-like under the shared direction table in
#: distributed_matvec_tpu/obs/directions.py) guards the per-term split:
#: a PR that quietly streams terms the split priced as recompute (bytes
#: creep back up) or slows the merged chunk program fails the gate even
#: when the pure tiers hold.
#: ``autotuned_steady_apply_ms`` (cost-like) guards the §30 closed loop:
#: a PR that degrades the search's pick — a pricing-model skew, a knob
#: grid hole, a posterior that walks rates the wrong way — shows up as
#: the tuned leg's wall creeping above its trend baseline even when
#: every hand-set leg holds.
DEFAULT_GATE = ("device_ms", "streamed_steady_apply_ms",
                "compressed_steady_apply_ms", "compress_ratio",
                "lanczos_iters_per_s", "compress_rel_err",
                "compress_drift_max", "barrier_ms",
                "pipelined_steady_apply_ms", "autotuned_steady_apply_ms",
                "hybrid_plan_bytes", "hybrid_steady_apply_ms",
                "serve_solves_per_min", "serve_p99_latency_ms",
                "resume_reshard_s", "resume_rebuild_plan_s",
                # dynamics throughputs (DESIGN.md §29; both
                # higher-is-better via the shared direction table):
                # a PR that quietly slows the KPM moment recurrence or
                # the Krylov evolution step loop fails the gate even
                # when raw apply walls hold
                "kpm_moments_per_s", "evolve_steps_per_s",
                # SLO burn-rate alerts fired during the bench run
                # (obs/slo.py via bench.py's closing check_slos pass):
                # gated ZERO-TOLERANTLY below — the healthy baseline is
                # exactly 0, which the relative gate would skip, so any
                # alert on a previously alert-free config regresses
                "slo_alert_count",
                # measured profiling overhead (obs/profile.py ledger,
                # cost-like percent under the shared direction table):
                # a PR whose instrumentation starts costing real apply
                # time fails the gate even when the walls themselves
                # still squeak under their own bounds.  Off-mode runs
                # record 0.0 (skipped as a baseline); the min-baseline
                # floor below keeps sub-quarter-percent jitter from
                # gating noise
                "profile_overhead_pct")

#: Incident counters whose healthy baseline is exactly zero: gated
#: absolutely (any increase beyond threshold x baseline regresses, so a
#: zero baseline means ANY occurrence fails) instead of being skipped by
#: the zero-baseline rule above.
GATE_ZERO_TOLERANT = ("slo_alert_count",)

#: Absolute noise floors per gated metric: a baseline below the floor is
#: scheduler jitter, not a trajectory (``barrier_ms`` on a healthy
#: pipeline is sub-millisecond, where a 30% relative bound would gate
#: pure noise against the all-time best) — such series are skipped, the
#: same way exactly-zero baselines are.
GATE_MIN_BASELINE = {"barrier_ms": 1.0,
                     # elastic resume walls on the CPU rig are fractions
                     # of a second; sub-50 ms baselines are scheduler
                     # jitter, not a trajectory
                     "resume_reshard_s": 0.05,
                     "resume_rebuild_plan_s": 0.05,
                     # measured profiling overhead under a quarter
                     # percent is timer jitter, not a trajectory
                     "profile_overhead_pct": 0.25}


def _keep(metric: str) -> bool:
    return metric in METRIC_WHITELIST or metric.startswith("phase_")


def compact_record(detail: dict, mode: str, backend: str,
                   ts: Optional[float] = None,
                   trace_id: Optional[str] = None,
                   job_id: Optional[str] = None,
                   obs_dir: Optional[str] = None) -> dict:
    """One trend record from a BENCH_DETAIL-style dict
    (``{config_key: {metrics...}}``, ``main`` included).

    ``trace_id``/``job_id``/``obs_dir`` stamp the record with its RUN
    identity: a gated trend regression greps straight back to the exact
    run directory (and Perfetto trace) that produced it, instead of "some
    earlier bench run"."""
    configs: Dict[str, dict] = {}
    for key, rec in sorted(detail.items()):
        if not isinstance(rec, dict) or "error" in rec:
            continue
        name = str(rec.get("config", key))
        vals = {m: v for m, v in rec.items()
                if _keep(m) and isinstance(v, (int, float))
                and not isinstance(v, bool)}
        if vals:
            configs[name] = vals
    out = {"kind": KIND, "ts": round(ts if ts is not None else time.time(),
                                     3),
           "mode": str(mode), "backend": str(backend), "configs": configs}
    if trace_id:
        out["trace_id"] = str(trace_id)
    if job_id:
        out["job_id"] = str(job_id)
    if obs_dir:
        out["obs_dir"] = str(obs_dir)
    return out


def append_record(path: str, record: dict) -> bool:
    """Append one record line (soft-fail: an unwritable checkout must not
    cost the bench run)."""
    try:
        with open(path, "a") as f:
            f.write(json.dumps(record, sort_keys=True) + "\n")
    except OSError as e:
        print(f"[bench_trend] append to {path} failed: {e!r}",
              file=sys.stderr)
        return False
    return True


def load_records(path: str) -> List[dict]:
    """The ``bench_trend`` records of a PROGRESS.jsonl (other lines —
    the driver's own progress records — are skipped), oldest first."""
    if not os.path.exists(path):
        return []
    out = []
    with open(path) as f:
        for ln, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue        # a torn/foreign line is not ours to judge
            if isinstance(rec, dict) and rec.get("kind") == KIND:
                out.append(rec)
    out.sort(key=lambda r: r.get("ts", 0.0))
    return out


def _comparable(records: List[dict], newest: dict) -> List[dict]:
    """Earlier records of the newest record's (mode, backend)."""
    return [r for r in records[:-1]
            if r.get("mode") == newest.get("mode")
            and r.get("backend") == newest.get("backend")]


def gate(records: List[dict], threshold: float,
         gate_metrics: Optional[List[str]] = None,
         configs: Optional[List[str]] = None,
         baseline: str = "best"):
    """(rows, regressions) for the newest record vs its baseline.

    ``baseline="best"`` (default) compares against the best earlier value
    per (config, metric) — the trajectory must not give back ground;
    ``"prev"`` compares against the immediately preceding record only.
    """
    gates = list(gate_metrics) if gate_metrics else list(DEFAULT_GATE)

    def _gated(metric: str) -> bool:
        return any(metric == g or (g.endswith("*")
                                   and metric.startswith(g[:-1]))
                   for g in gates)

    rows, regressions = [], []
    if len(records) < 2:
        return rows, regressions, None
    newest = records[-1]
    earlier = _comparable(records, newest)
    if baseline == "prev":
        earlier = earlier[-1:]
    if not earlier:
        return rows, regressions, newest
    for cfg, vals in sorted(newest.get("configs", {}).items()):
        if configs and not any(sel in cfg for sel in configs):
            continue
        for metric, nv in sorted(vals.items()):
            if not _gated(metric):
                continue
            hib = obs_report._is_higher_better(metric)
            cand = []
            for r in earlier:
                old = r.get("configs", {}).get(cfg)
                if not old or metric not in old:
                    continue
                # a config whose basis size changed is a different
                # experiment — never a trend regression
                if ("n_states" in old and "n_states" in vals
                        and old["n_states"] != vals["n_states"]):
                    continue
                cand.append(float(old[metric]))
            if not cand:
                continue
            b = max(cand) if hib else min(cand)
            if metric in GATE_ZERO_TOLERANT:
                # zero IS the meaningful baseline here (see the constant)
                rel = ((float(nv) - b) / abs(b)) if b else (
                    float("inf") if float(nv) > 0 else 0.0)
                rows.append((cfg, metric, b, float(nv), rel))
                if float(nv) > b + threshold * abs(b):
                    regressions.append((cfg, metric, b, float(nv), rel))
                continue
            if not b:
                continue
            if abs(b) < GATE_MIN_BASELINE.get(metric, 0.0):
                continue     # below the metric's noise floor: not a trend
            rel = (float(nv) - b) / abs(b)
            worse = -rel if hib else rel
            rows.append((cfg, metric, b, float(nv), rel))
            if worse > threshold:
                regressions.append((cfg, metric, b, float(nv), rel))
    return rows, regressions, newest


def render_trend(records: List[dict], configs: Optional[List[str]],
                 metrics: Optional[List[str]], last: int) -> None:
    recs = records[-last:]
    if not recs:
        print("no bench_trend records yet — run bench.py (it appends one "
              "per run) or `bench_trend append --detail BENCH_DETAIL.json`")
        return
    print(f"{len(records)} record(s); showing last {len(recs)} "
          f"(oldest -> newest):")
    for r in recs:
        when = time.strftime("%Y-%m-%d %H:%M", time.localtime(r["ts"]))
        ident = ""
        if r.get("trace_id"):
            ident = f"  trace={str(r['trace_id'])[:8]}"
            if r.get("obs_dir"):
                ident += f" dir={r['obs_dir']}"
        print(f"  {when}  mode={r.get('mode'):<12} "
              f"backend={r.get('backend'):<4} "
              f"configs={len(r.get('configs', {}))}{ident}")
    series: Dict[tuple, List[Optional[float]]] = {}
    for i, r in enumerate(recs):
        for cfg, vals in r.get("configs", {}).items():
            if configs and not any(sel in cfg for sel in configs):
                continue
            for m, v in vals.items():
                if m == "n_states":
                    continue
                if metrics and not any(sel in m for sel in metrics):
                    continue
                series.setdefault((cfg, m), [None] * len(recs))[i] = float(v)
    if not series:
        print("no matching (config, metric) series")
        return
    print(f"\n  {'config':<26} {'metric':<28} {'first':>10} {'last':>10} "
          f"{'change':>8}  trajectory")
    for (cfg, m), vals in sorted(series.items()):
        present = [v for v in vals if v is not None]
        if not present:
            continue
        first, lastv = present[0], present[-1]
        rel = (lastv - first) / abs(first) if first else 0.0
        traj = " ".join("-" if v is None else f"{v:.4g}" for v in vals)
        print(f"  {cfg:<26} {m:<28} {first:>10.4g} {lastv:>10.4g} "
              f"{rel:>+7.1%}  {traj}")


def default_progress_path() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "PROGRESS.jsonl")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench_trend", description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("append", help="append one compact record from a "
                                      "bench detail JSON")
    p.add_argument("--detail", required=True,
                   help="BENCH_DETAIL-style JSON ({config: {metrics}})")
    p.add_argument("--progress", default=None, metavar="PATH")
    p.add_argument("--mode", default="manual")
    p.add_argument("--backend", default="unknown")

    p = sub.add_parser("trend", help="render the cross-run trajectory")
    p.add_argument("--progress", default=None, metavar="PATH")
    p.add_argument("--config", action="append", default=None)
    p.add_argument("--metric", action="append", default=None)
    p.add_argument("--last", type=int, default=8)
    p.add_argument("--json", action="store_true")

    p = sub.add_parser("gate", help="newest record vs the trajectory "
                                    "(exit 1 on regression)")
    p.add_argument("--progress", default=None, metavar="PATH")
    p.add_argument("--threshold", type=float, default=0.3,
                   help="relative regression bound (default 0.3 — looser "
                        "than obs-check's 0.2: trend records span "
                        "machine-state drift, not one warm process)")
    p.add_argument("--metric", action="append", default=None,
                   help="gate on this metric (repeatable; `*` suffix = "
                        "prefix match; default: device_ms, "
                        "streamed_steady_apply_ms, lanczos_iters_per_s)")
    p.add_argument("--config", action="append", default=None)
    p.add_argument("--baseline", choices=("best", "prev"), default="best")

    args = ap.parse_args(argv)
    progress = args.progress or default_progress_path()

    if args.cmd == "append":
        with open(args.detail) as f:
            detail = json.load(f)
        rec = compact_record(detail, args.mode, args.backend)
        if not rec["configs"]:
            print("[bench_trend] no usable configs in the detail JSON",
                  file=sys.stderr)
            return 2
        ok = append_record(progress, rec)
        print(f"[bench_trend] appended {len(rec['configs'])} config(s) "
              f"to {progress}" if ok else "[bench_trend] append failed")
        return 0 if ok else 1

    records = load_records(progress)

    if args.cmd == "trend":
        if args.json:
            print(json.dumps(records[-args.last:], indent=1,
                             sort_keys=True))
        else:
            render_trend(records, args.config, args.metric, args.last)
        return 0

    rows, regressions, newest = gate(records, args.threshold, args.metric,
                                     args.config, args.baseline)
    if newest is None:
        print("[bench_trend] fewer than 2 records — nothing to gate")
        return 0
    if not rows:
        print("[bench_trend] no comparable gated series (first run of "
              "this mode/backend, or configs changed size) — pass")
        return 0
    print(f"gated series vs {args.baseline} of "
          f"{len(_comparable(records, newest))} earlier "
          f"{newest.get('mode')}/{newest.get('backend')} record(s):")
    for cfg, metric, b, n, rel in rows:
        mark = "REGRESSED" if (cfg, metric, b, n, rel) in regressions else ""
        print(f"  {cfg:<26} {metric:<28} {b:>10.4g} -> {n:>10.4g} "
              f"({rel:+.1%}) {mark}")
    if regressions:
        print(f"\nREGRESSION: {len(regressions)} gated series beyond "
              f"{args.threshold:.0%}")
        if newest.get("trace_id"):
            # the run identity stamped by bench.py: grep the regressed
            # run's own telemetry instead of guessing which run it was
            print(f"  regressed run: trace_id={newest['trace_id']}"
                  + (f" job_id={newest['job_id']}"
                     if newest.get("job_id") else "")
                  + (f" obs_dir={newest['obs_dir']}"
                     if newest.get("obs_dir") else ""))
        return 1
    print(f"\nno trend regression beyond {args.threshold:.0%}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
