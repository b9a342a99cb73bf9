"""obs — process-wide telemetry: metrics registry, structured event log,
spans that also lie on the profiler's host line.

The reference answers "why was this run slow?" with tree timers and comm
diagnostics behind ``--kDisplayTimings``/``--kVerboseComm``; after the
warm-start caches of DESIGN.md §16 the same question here spans artifact
hits, AOT executable reuse, host↔device transfer volume, and solver
convergence — none of it visible from a wall clock.  This package is the
observability spine those signals report through (the per-phase accounting
arXiv:2112.09017 credits for its scaling wins, plus the compile/retrace
visibility GSPMD (arXiv:2105.04663) treats as a first-class signal):

* :mod:`~.metrics` — counters / gauges / fixed-bucket histograms with
  labeled series (``matvec_apply_ms{engine=local}``,
  ``artifact_cache{event=hit}``, ``bytes_h2d``, ``retrace_count``);
  :func:`snapshot` turns the registry into plain data.
* :mod:`~.events` — append-only JSONL per process
  (``<run_dir>/rank_<r>/events.jsonl``, rank-tagged envelope, monotonic
  ``seq``, soft-fail writes) and an in-memory ring buffer.
* :mod:`~.health` — numerical-health probes (deferred-fetch NaN/Inf +
  norm reductions on engine applies, exchange overflow/invalid counters)
  and the solver watchdog (``solver_health`` events; ``DMT_HEALTH=strict``
  raises :class:`~.health.HealthError` on critical conditions).
* :mod:`~.phases` / :mod:`~.roofline` — per-apply phase attribution
  (``apply_phases`` events: plan H2D / compute / exchange / accumulate
  with exact structural byte/gather/flop counts, apply HLO byte-identical
  on or off) and the analytical roofline model over them (calibrated
  rates, binding-resource naming, pipelined-apply speedup estimates) —
  DESIGN.md §22.
* :mod:`~.trace` — end-to-end solve tracing (DESIGN.md §24): one
  ``trace_id`` per run (file-agreed across ranks through the shared run
  directory), a ``job_id`` namespacing knob (``DMT_JOB_ID``), and
  parent-linked spans (solve > iteration > apply > chunk) stamped into
  every event's envelope; one ``span`` event per closed span, its
  duration monotonic, its counts added while it was open, and (for the
  leaf kinds) a ``jax.profiler.TraceAnnotation`` of the same name, so a
  device trace shows the program's spans on the device's clock.
* ``tools/obs_report.py`` — the reader: ``summarize`` one run, ``merge`` /
  ``report --ranks`` a multi-rank one (skew-corrected timeline, per-rank
  straggler attribution), ``diff`` two runs as a CI perf gate,
  ``roofline`` the phase/cost-model report, ``trace`` a Perfetto export
  of the merged span tree, ``watch`` a live terminal dashboard over the
  rank streams, ``tail`` a live one.

Config: ``DMT_OBS_DIR`` (or ``obs_dir``) points the sink at a run
directory; unset ⇒ in-memory only; ``DMT_OBS=off`` disables the layer
entirely, at which point every instrument is the shared no-op
:data:`~.metrics.NULL` and the instrumented hot paths add **zero
device-side work** (no syncs, no fetches — guard-tested).
"""

from .events import (emit, event_path, events, flush, obs_enabled,
                     reset, run_dir)
from .export import (merge_openmetrics, parse_openmetrics,
                     render_openmetrics, start_exporter, stop_exporter,
                     textfile_path, write_textfile)
from .flight import (flight_dump, install_fatal_handlers, list_bundles,
                     postmortem_dir, read_bundle, reset_flight,
                     verify_bundle)
from .health import (HealthError, drain as drain_health, health_event_count,
                     health_mode, probes_enabled, record as record_health,
                     reset_health)
from .hlo import (diff_profiles, executable_costs, hottest_ops,
                  load_profile, record_executable_costs, reset_hlo)
from .profile import (measured_overhead_pct, overhead_snapshot,
                      profile_due, profile_mode, reset_profile,
                      sample_window, stamp_profile_dir, trigger_capture)
from .memory import (MemoryReport, OomError, attach_oom,
                     build_memory_report, emit_ledger, executable_analyses,
                     last_watermark, ledger_entries, ledger_total,
                     ledger_tree, record_executable_analysis, reset_memory,
                     sample_watermark, track, track_tree, watermark_due)
from .metrics import (DEFAULT_BUCKETS, NULL, counter, gauge, histogram,
                      reset_metrics, series_name)
from .metrics import snapshot as _metrics_snapshot
from .phases import (PHASES, emit_apply_phases, phases_enabled, zero_counts)
from .slo import (SloSpec, check_slos, default_slos, reset_slo)
from .slo import evaluate as evaluate_slos
from .trace import (current_span_id, deepest_span, job_id, open_spans,
                    reset_trace, span, span_path, trace_enabled, trace_id)

__all__ = [
    "emit",
    "event_path",
    "events",
    "flush",
    "obs_enabled",
    "reset",
    "run_dir",
    "counter",
    "gauge",
    "histogram",
    "snapshot",
    "series_name",
    "reset_metrics",
    "NULL",
    "DEFAULT_BUCKETS",
    "HealthError",
    "drain_health",
    "health_event_count",
    "health_mode",
    "probes_enabled",
    "record_health",
    "reset_health",
    "MemoryReport",
    "OomError",
    "attach_oom",
    "build_memory_report",
    "emit_ledger",
    "executable_analyses",
    "last_watermark",
    "ledger_entries",
    "ledger_total",
    "ledger_tree",
    "record_executable_analysis",
    "reset_memory",
    "sample_watermark",
    "track",
    "track_tree",
    "watermark_due",
    "PHASES",
    "emit_apply_phases",
    "phases_enabled",
    "zero_counts",
    "current_span_id",
    "deepest_span",
    "job_id",
    "open_spans",
    "reset_trace",
    "span",
    "span_path",
    "trace_enabled",
    "trace_id",
    "merge_openmetrics",
    "parse_openmetrics",
    "render_openmetrics",
    "start_exporter",
    "stop_exporter",
    "textfile_path",
    "write_textfile",
    "flight_dump",
    "install_fatal_handlers",
    "list_bundles",
    "postmortem_dir",
    "read_bundle",
    "reset_flight",
    "verify_bundle",
    "SloSpec",
    "check_slos",
    "default_slos",
    "evaluate_slos",
    "reset_slo",
    "diff_profiles",
    "executable_costs",
    "hottest_ops",
    "load_profile",
    "record_executable_costs",
    "reset_hlo",
    "measured_overhead_pct",
    "overhead_snapshot",
    "profile_due",
    "profile_mode",
    "reset_profile",
    "sample_window",
    "stamp_profile_dir",
    "trigger_capture",
]


def snapshot() -> dict:
    """The metrics registry as plain data — after draining any pending
    health-probe fetches, so a closing ``metrics_snapshot`` always carries
    the final overflow/invalid/nonfinite counter totals."""
    drain_health()
    return _metrics_snapshot()


def reset_all() -> None:
    """Reset events, metrics, health, memory, trace, SLO, flight, HLO
    and profiling state (test isolation helper); also stops a running
    exporter."""
    stop_exporter()
    reset()
    reset_metrics()
    reset_health()
    reset_memory()
    reset_trace()
    reset_slo()
    reset_flight()
    reset_hlo()
    reset_profile()
