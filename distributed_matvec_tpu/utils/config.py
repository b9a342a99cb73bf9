"""Typed runtime configuration — the analog of the reference's ``config const``
flag system (``/root/reference/src/CommonParameters.chpl:1-7`` plus per-module
knobs, e.g. ``DistributedMatrixVector.chpl:456-460``).

Chapel ``config const`` values are compile-time defaults overridable on the
command line (``--kFlag=value``).  Here they are dataclass fields overridable
via environment variables (``DMT_<NAME>=value``) or programmatically through
:func:`get_config` / :func:`set_config`.
"""

from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass

__all__ = ["RuntimeConfig", "get_config", "set_config", "update_config"]


@dataclass
class RuntimeConfig:
    # -- observability (CommonParameters.chpl:2) ----------------------------
    display_timings: bool = False          # kDisplayTimings
    log_debug: bool = False                # logDebug gating (FFI.chpl:78-80)
    profile_dir: str = ""                  # non-empty → jax.profiler traces
    #   (the device-side analog of the reference's kVerboseComm/CommDiagnostics
    #    hooks, DistributedMatrixVector.chpl:19)
    obs: str = "on"                        # telemetry layer (obs/): metrics
    #   registry + structured event sink.  "off" (DMT_OBS=off) disables the
    #   whole layer — every instrument becomes a shared no-op object and the
    #   hot paths add zero device-side work
    obs_dir: str = ""                      # event-sink run directory
    #   (DMT_OBS_DIR): non-empty → append-only JSONL stream per process at
    #   <obs_dir>/rank_<r>/events.jsonl; empty → in-memory only
    health: str = "on"                     # numerical-health watchdog
    #   (DMT_HEALTH): "on" emits `health`/`solver_health` events and logs
    #   critical conditions but continues; "strict" raises HealthError on
    #   critical; "off" disables the probes entirely (obs off implies off)
    health_every: int = 16                 # engine-apply probe cadence
    #   (DMT_HEALTH_EVERY): every Nth eager apply piggybacks one fused
    #   NaN/Inf-count + output-norm reduction on the result; the scalar is
    #   fetched DEFERRED so no sync is added to the hot path
    memory_every: int = 64                 # device-memory watermark cadence
    #   (DMT_MEMORY_EVERY): every Nth eager apply polls
    #   device.memory_stats() into hbm_bytes_in_use/hbm_peak_bytes gauges
    #   and a memory_watermark event; backends without stats (CPU) latch
    #   off after the first miss (obs/memory.py)
    trace: str = "on"                      # end-to-end solve tracing
    #   (DMT_TRACE, obs/trace.py): "on" stamps every event's envelope with
    #   trace_id/job_id/span_id and emits one `span` event per closed span
    #   (solve > iteration > apply > chunk) — pure host bookkeeping, the
    #   apply HLO is byte-identical on or off (guard-tested by `make
    #   trace-check`); "off" disables stamping + span events while the
    #   rest of the obs layer keeps running (obs off implies off)
    job_id: str = ""                       # job-namespacing id
    #   (DMT_JOB_ID): stamped into every event envelope; empty defaults to
    #   the run's trace id.  The groundwork the solve service needs to
    #   multiplex many concurrent jobs' telemetry through shared engines
    obs_port: int = 0                      # OpenMetrics exporter base port
    #   (DMT_OBS_PORT, obs/export.py): >0 → each rank serves GET /metrics
    #   (Prometheus text format, fresh registry snapshot per scrape) and
    #   GET /healthz on port obs_port + rank; rank 0's /metrics also
    #   aggregates every peer's textfile under the shared run directory.
    #   0 (the default) binds nothing, and DMT_OBS=off never touches a
    #   socket regardless — the provable-no-op contract
    flight_ring: int = 256                 # flight-recorder ring depth
    #   (DMT_FLIGHT_RING, obs/flight.py): how many of the newest in-memory
    #   events a post-mortem bundle carries alongside the open-span stack,
    #   metrics snapshot and config identity when a rank dies (OOM, stall
    #   exit 76, preemption exit 75, quarantine, fatal signals)
    phases: str = "on"                     # per-apply phase attribution
    #   (DMT_PHASES): "on" emits one `apply_phases` event per eager apply
    #   (host-side structural counts only — the apply HLO is byte-identical
    #   on or off, guard-tested by `make roofline-check`); "off" disables
    #   the events (obs off implies off)
    profile: str = "off"                   # continuous profiling plane
    #   (DMT_PROFILE, obs/profile.py): "sampled" captures a bounded
    #   jax.profiler trace window every profile_every-th eager apply into
    #   <run_dir>/rank_<r>/profiles/ (plus triggered deep capture);
    #   "triggered" keeps only the incident-driven capture path; "off"
    #   (default) is a provable no-op — the apply HLO is byte-identical
    #   on or off, guard-tested by `make profile-check`
    profile_every: int = 64                # sampled-profile cadence
    #   (DMT_PROFILE_EVERY): every Nth eager apply runs inside a trace
    #   window when profile=sampled — same cadence pattern as
    #   health_every, skipping apply 0 (compile noise)
    profile_overhead_pct: float = 2.0      # measured-overhead budget
    #   (DMT_PROFILE_OVERHEAD_PCT): when the trace windows' own measured
    #   start/stop cost exceeds this percent of the un-profiled apply
    #   wall (after ≥2 windows), sampling latches OFF for the process
    #   and emits `profile_overhead_latch` — profiling must never become
    #   the regression it is hunting

    # -- enumeration (CommonParameters.chpl:5-6) ----------------------------
    is_representative_batch_size: int = 10240   # kIsRepresentativeBatchSize
    enumeration_backend: str = "auto"           # auto | native (C++) | numpy

    # -- matvec engine (DistributedMatrixVector.chpl:456-460,55-57) ---------
    remote_buffer_size: int = 150_000      # kRemoteBufferSize → fused-mode all_to_all cap
    all_to_all_capacity_factor: float = 1.25  # padding headroom over mean bucket size

    # -- device/layout ------------------------------------------------------
    matvec_batch_size: int = 1 << 16       # row block B fed to the off-diag kernel
    ell_build_budget_gb: float = 12.0      # device-memory budget for the ELL
    #   structure build; when the one-pass build's full-width [T, N_pad]
    #   buffers would exceed it, the engine switches to the two-pass
    #   low-memory build (count → pack), enabling ELL for bases like
    #   square_6x6 whose packed tables fit HBM but whose full-width
    #   intermediates do not
    matvec_mode: str = "ell"               # "ell" (precomputed structure) |
    #   "compact" (4 B/entry, isotropic real sectors) | "streamed"
    #   (DistributedEngine: fused-class structure resolved once into a
    #   host-RAM plan, streamed H2D per apply — no per-apply orbit scan) |
    #   "fused" (recompute structure every apply) | "hybrid"
    #   (DistributedEngine: per-term recompute-vs-stream split priced by
    #   the calibrated cost model — cheap-orbit terms recompute on device
    #   beside the streamed terms' decode, one merged exchange; see the
    #   `hybrid` knob below and DESIGN.md §28)
    stream_plan_ram_gb: float = 8.0        # host-RAM budget for a streamed
    #   engine's resolved plan; beyond it the plan is demoted to the
    #   artifact-cache sidecar (disk tier) and chunks are read back per
    #   apply — with the artifact layer off the plan stays in RAM with a
    #   warning (pure host-RAM streaming never writes disk)
    stream_compress: str = "off"           # streamed-plan codec tier
    #   (DMT_STREAM_COMPRESS, ops/plan_codec.py): "off" (raw arrays, rok
    #   still bitpacked — bit-identical to fused), "lossless" (bitpacked
    #   indices + f64 dictionary coefficients; decoded values are exact,
    #   gated by the measured-error gate), "f32"/"bf16" (quantized
    #   coefficients, f64 accumulation — for operators whose coefficients
    #   don't repeat enough to dictionary-code).  The plan sidecar, the
    #   host-RAM copy, and the per-apply H2D stream all carry the ENCODED
    #   bytes; decode happens on device inside the chunk program
    pipeline: str = "off"                  # pipelined distributed applies
    #   (DMT_PIPELINE, DESIGN.md §25): software-pipeline depth for the
    #   fused/streamed DistributedEngine apply — "off" (sequential
    #   compute-then-exchange per chunk, bit-identical to every earlier
    #   round), an integer >= 2 (streamed: that many chunks in flight —
    #   plan staging prefetched by worker threads, produce/exchange split
    #   programs with bounded send slots, exchange decomposed into
    #   ppermute rounds; fused: the in-program software pipeline —
    #   chunk i's staged exchange overlaps chunk i+1's gather/multiply
    #   inside one lax.scan), or "auto" (consult the roofline
    #   calibration: on when the priced overlappable time is worth it,
    #   obs/roofline.choose_pipeline_depth).  Accumulation order is
    #   UNCHANGED at any depth, so pipelined applies stay bit-identical
    #   to sequential ones (gated by `make pipeline-check`)
    hybrid: str = "auto"                   # hybrid-mode term split policy
    #   (DMT_HYBRID, DESIGN.md §28): which Hamiltonian terms a
    #   mode="hybrid" DistributedEngine STREAMS (compressed plan slices)
    #   versus RECOMPUTES on device inside the chunk program — "auto"
    #   prices every term off the calibrated roofline (recompute flops at
    #   the measured flop rate vs encoded plan bytes + decode gathers at
    #   the measured H2D/gather rates, obs/roofline.choose_hybrid_split),
    #   "all-stream" / "all-recompute" pin the degenerate splits (equal
    #   to the pure streamed / pure recompute tiers — gate-tested), and
    #   "stream:i,j,..." pins an explicit streamed term set (tests and
    #   controlled experiments).  The resolved split is baked into the
    #   engine fingerprint (v4), so each split mix compiles and caches
    #   as its own static program
    tune: str = "off"                      # self-tuning runtime (DMT_TUNE,
    #   DESIGN.md §30): "off" (every knob is hand-set — all prior
    #   behavior), "static" (at streamed/hybrid engine build, price the
    #   full knob cross-product — row-chunk size × pipeline depth ×
    #   stream_compress tier × hybrid split × prefetch workers ×
    #   plan RAM/disk tier — through the calibrated roofline and take
    #   the argmin; the choice is allgather-agreed across ranks, stamped
    #   into the engine fingerprint via the knobs it sets, and cached as
    #   a content-addressed tuning artifact so repeat builds skip the
    #   search), "live" (static, plus each apply window's measured phase
    #   walls refine a per-(device kind, mode) rate posterior; when
    #   measured-vs-priced drifts outside tune/live.DRIFT_BAND the
    #   engine re-tunes at the next safe boundary — never mid-apply).
    #   Only bit-identity-preserving knob values are ever auto-selected
    #   (compress off|lossless, order-preserving pipeline depths), and
    #   explicitly passed constructor/config knobs always win over tuned
    #   ones.  DMT_TUNE_WINDOW overrides the live update window (8)
    split_gather: str = "auto"             # triple-f32 gathers: auto | on | off
    #   (auto = on for the TPU backend; see ops/split_gather.py)
    term_loop: str = "auto"                # ELL/compact per-term loop form
    #   (a test hook): auto (LocalEngine's ELL levels: lax.scan, the form
    #   that is no slower on the chip and builds faster into a solver's
    #   programs, and, where the gather table is cut into ranges, above the
    #   VMEM line, one gather a column, the form whose tables the compiler
    #   places in VMEM — engine.ell_term_loop; compact mode and
    #   DistributedEngine:
    #   unrolled until the estimated gather scratch would exceed ~2 GB, then
    #   lax.scan — engine.unroll_terms_ok) | scan (force the serialized
    #   form everywhere) | unroll (force one gather a column wherever the
    #   width permits), so that tests exercise both forms at small sizes.
    complex_pair: str = "auto"             # (re,im)-f64 pair engines for
    #   complex sectors: auto | on | off.  auto = pair form on the TPU
    #   backend (whose compiler refuses complex128 — see below),
    #   native c128 elsewhere.  "on" forces pair everywhere (useful for
    #   testing), "off" forces native c128 (subject to the TPU guard).
    allow_complex_on_tpu: bool = False     # override the c128-on-TPU guard
    #   (the TPU compiler refuses any complex128 program with an internal
    #    x64-rewriter error while f64 and c64 compile; engines refuse
    #    native-c128 sectors on the TPU backend unless this is set — with
    #    complex_pair="auto" they run in pair form instead)

    # -- solvers (solve/lanczos.py) -----------------------------------------
    lanczos_reorth: str = "selective"      # per-iteration reorthogonalization
    #   policy: "selective" (window MGS against the trailing rows, escalated
    #   to full MGS blocks when the accumulated ω-recurrence orthogonality
    #   estimate crosses √ε — the Simon semiorthogonality bound; chain_20 is
    #   reorth-bound at ~26× the apply cost) | "full" (the pre-round-9
    #   behavior: full MGS sweeps every iteration)

    # -- fault tolerance (utils/faults.py / preempt.py, parallel/heartbeat.py)
    fault: str = ""                        # deterministic fault injection
    #   (DMT_FAULT): "site[:p=..][:n=..][:skip=..][:seed=..][:delay=..],..."
    #   arms named failure sites on the I/O and comms edges; empty (the
    #   default) resolves to a shared no-op registry — provably inert,
    #   same guard style as DMT_OBS=off
    io_retries: int = 3                    # bounded retry attempts for
    #   idempotent I/O reads (disk-tier plan chunks, artifact loads);
    #   backoff doubles from io_retry_base_s per attempt
    io_retry_base_s: float = 0.05
    heartbeat_s: float = 0.0               # >0 → cross-rank heartbeat
    #   watchdog beat interval (DMT_HEARTBEAT_S); a peer rank whose beat
    #   goes stale past heartbeat_timeout_s triggers a stall_report event
    #   + abort (EXIT_STALLED) instead of an infinite all_to_all wait
    heartbeat_timeout_s: float = 120.0
    preempt: str = "auto"                  # SIGTERM/SIGINT preemption latch
    #   (DMT_PREEMPT): "auto" installs checkpoint-and-exit handlers around
    #   solves (apps/diagonalize exits EXIT_PREEMPTED=75 so a supervisor
    #   relaunches the same argv and resumes); "off" leaves signal
    #   dispositions alone

    # -- artifact cache (utils/artifacts.py) --------------------------------
    artifact_cache: str = "on"             # default-on content-addressed
    #   cache of basis representatives, engine structure sidecars, and the
    #   XLA compilation cache ("off" disables the whole layer; explicit
    #   structure_cache= paths are unaffected either way)
    artifact_dir: str = ""                 # cache root override (also
    #   DMT_ARTIFACT_DIR); default ~/.cache/distributed_matvec_tpu/artifacts
    artifact_max_gb: float = 8.0           # per-sidecar size cap for
    #   DEFAULT-path structure saves: tables beyond this are rebuilt per
    #   process instead of silently filling the cache disk (explicit
    #   structure_cache= paths are never capped)

    # -- solve service (serve/, DESIGN.md §26) ------------------------------
    serve_pool_gb: float = 2.0             # engine-pool byte budget
    #   (DMT_SERVE_POOL_GB): resident engines (device tables + host-RAM
    #   streamed plans) beyond it are evicted LRU — the artifact_max_gb
    #   analog for WARM engines rather than on-disk sidecars
    serve_block_width: int = 6             # max jobs packed into one
    #   batched lanczos_block call (DMT_SERVE_BLOCK_WIDTH): the multi-RHS
    #   block width cap — wider amortizes gathers further but raises the
    #   per-step cost every still-running job pays
    serve_accept_horizon_s: float = 30.0   # admission verdict boundary
    #   (DMT_SERVE_ACCEPT_HORIZON_S): a job whose priced queue-wait ETA
    #   exceeds this is admitted with verdict "queue" (ETA attached)
    #   instead of "accept"; jobs that do not fit at all are rejected



_ENV_PREFIX = "DMT_"
_config: RuntimeConfig | None = None


def _from_env(cfg: RuntimeConfig) -> RuntimeConfig:
    for f in dataclasses.fields(cfg):
        env = os.environ.get(_ENV_PREFIX + f.name.upper())
        if env is None:
            continue
        if f.type in ("bool", bool):
            value = env.lower() in ("1", "true", "yes", "on")
        elif f.type in ("int", int):
            value = int(env)
        elif f.type in ("float", float):
            value = float(env)
        else:
            value = env
        setattr(cfg, f.name, value)
    return cfg


def get_config() -> RuntimeConfig:
    global _config
    if _config is None:
        _config = _from_env(RuntimeConfig())
    return _config


def set_config(cfg: RuntimeConfig) -> None:
    global _config
    _config = cfg


def update_config(**kwargs) -> RuntimeConfig:
    cfg = get_config()
    for k, v in kwargs.items():
        if not hasattr(cfg, k):
            raise AttributeError(f"unknown config field {k!r}")
        setattr(cfg, k, v)
    return cfg
