# Test/bench harness — the analog of the reference Makefile's check targets
# (/root/reference/Makefile:79-126).  Everything runs from a plain checkout;
# no install step needed.

PYTHON ?= python

# obs-check scratch + gate (see tools/obs_report.py; threshold is the
# relative regression bound on the gated metrics)
OBS_CHECK_DIR ?= /tmp/dmt_obs_check
OBS_THRESHOLD ?= 0.2
# health-check gate: max relative probe overhead on chain-16 device_ms
HEALTH_THRESHOLD ?= 0.02

.PHONY: check check-fast check-solve smoke dryrun bench warm-cache \
	obs-check health-check mem-check stream-check fault-check \
	roofline-check compress-check trace-check pipeline-check \
	hybrid-check serve-check elastic-check dynamics-check tune-check \
	slo-check profile-check clean

check:
	$(PYTHON) -m pytest tests/ -q
	$(MAKE) obs-check
	$(MAKE) health-check
	$(MAKE) mem-check
	$(MAKE) stream-check
	$(MAKE) compress-check
	$(MAKE) roofline-check
	$(MAKE) pipeline-check
	$(MAKE) hybrid-check
	$(MAKE) trace-check
	$(MAKE) serve-check
	$(MAKE) dynamics-check
	$(MAKE) fault-check
	$(MAKE) elastic-check
	$(MAKE) tune-check
	$(MAKE) slo-check
	$(MAKE) profile-check

check-fast:
	$(PYTHON) -m pytest tests/ -q -x -k "not distributed and not reference"

check-solve:
	$(PYTHON) -m pytest tests/test_solve.py tests/test_reference_configs.py -q

smoke:
	JAX_PLATFORMS=cpu $(PYTHON) bench.py --smoke

dryrun:
	$(PYTHON) __graft_entry__.py

bench:
	$(PYTHON) bench.py

# Pre-build the artifact caches (basis / structure / XLA) for the bench
# configs so engine construction in later processes is seconds, not minutes.
warm-cache:
	$(PYTHON) tools/warm_cache.py --configs cpu

# CI perf gate: run the smoke bench with the telemetry sink ON, check the
# event stream summarizes (engine-init split, cache hit rates, solver
# traces), and fail if chain-16 device_ms regressed more than
# OBS_THRESHOLD against the recorded BENCH_DETAIL.json.  The fresh detail
# goes to a scratch path so the recorded artifact stays the baseline.
# NB: the baseline is wall-clock from the machine that recorded it — on
# markedly different hardware, re-record BENCH_DETAIL.json (make smoke) or
# raise OBS_THRESHOLD rather than chasing cross-machine timing noise.
# Wall-clock on a shared host is noisy, so the gate retries: a spurious
# spike passes on a later attempt, a GENUINE regression fails all three.
obs-check:
	rm -rf $(OBS_CHECK_DIR) && mkdir -p $(OBS_CHECK_DIR)
	@ok=1; for i in 1 2 3; do \
	  JAX_PLATFORMS=cpu DMT_OBS_DIR=$(OBS_CHECK_DIR)/run$$i \
	    $(PYTHON) bench.py --smoke \
	    --detail-out $(OBS_CHECK_DIR)/new$$i.json || exit 1; \
	  $(PYTHON) tools/obs_report.py summarize $(OBS_CHECK_DIR)/run$$i \
	    || exit 1; \
	  if $(PYTHON) tools/obs_report.py diff BENCH_DETAIL.json \
	      $(OBS_CHECK_DIR)/new$$i.json --config chain_16 \
	      --metric device_ms --threshold $(OBS_THRESHOLD); then \
	    ok=0; break; \
	  else \
	    echo "obs-check: attempt $$i gated as regressed; retrying" \
	      "(timing noise vs a genuine regression resolves by attempt 3)"; \
	  fi; \
	done; exit $$ok

# Memory-observability gate (tools/mem_check.py): chain-16 smoke run,
# asserting the device-memory ledger reconciles with ell_nbytes exactly
# and with the apply executable's memory_analysis() within tolerance,
# that the obs stream carries memory_ledger/memory_analysis events the
# capacity planner can read, and that a healthy run emits ZERO
# OOM/critical memory events.
mem-check:
	JAX_PLATFORMS=cpu $(PYTHON) tools/mem_check.py

# Streamed-mode gate (tools/stream_check.py): bit-identity of streamed vs
# fused applies (single + batch + <x,Hx>), exchange counters preserved, a
# direction-aware obs_report diff gate on the steady-state (second+)
# streamed speedup (retried — timing noise vs genuine regression resolves
# by attempt 3), DMT_ARTIFACT_CACHE=off pure host-RAM streaming with zero
# disk writes, and the plan sidecar save/restore round-trip.
stream-check:
	JAX_PLATFORMS=cpu $(PYTHON) tools/stream_check.py

# Compressed-plan-stream gate (tools/compress_check.py): lossless/f32
# codec round trip, the measured-error gate (lossless <= 1e-12 vs fused,
# measured 0.0; f32 <= 1e-6), off-tier bit-identity with bitpacked rok,
# encoded plan bytes >= 2.5x smaller gated via `obs_report diff --phases`
# (phase_plan_h2d_bytes down, compute flat), and the PROGRESS.jsonl
# trend gate guarding compress_ratio.  Deterministic, ~40 s on CPU.
compress-check:
	JAX_PLATFORMS=cpu $(PYTHON) tools/compress_check.py

# Phase-attribution gate (tools/roofline_check.py): apply HLO
# byte-identity with phase probes on vs off (local ell + distributed
# fused), `obs_report roofline` model-vs-measured reconciliation on a
# live streamed run (phase walls sum to the measured apply wall within
# 10%, binding resource named, pipelined-apply estimate finite), and the
# bench_trend gate passing on an appended record AND firing on a
# synthetic 10x regression.  Deterministic, ~30 s on the CPU rig.
roofline-check:
	JAX_PLATFORMS=cpu $(PYTHON) tools/roofline_check.py

# Pipelined-apply gate (tools/pipeline_check.py): bit-identity of
# pipelined vs sequential applies (fused + streamed, single + k=3 batch,
# counters preserved), the PR-7 pipelined-apply estimate reconciling
# against the measured pipelined wall within 25% (retried for timing
# noise), a REAL 2-process run with a deterministic 8 ms/chunk staging
# latency injected on rank 1 showing the `report --ranks` time-at-barrier
# cut >= 2x with pipeline_depth=4 (the straggling rank's steady applies
# faster too), and the PROGRESS.jsonl trend gate firing on a synthetic
# barrier_ms regression.  Deterministic, ~45 s on the CPU rig.
pipeline-check:
	JAX_PLATFORMS=cpu $(PYTHON) tools/pipeline_check.py

# Hybrid-split gate (tools/hybrid_check.py, DESIGN.md §28): degenerate
# all-stream/all-recompute splits equal the existing streamed apply
# bit-for-bit (plan bytes equal / strictly below), a pinned mixed split
# stays bit-identical to pure streamed at pipeline depths {0, 2} with
# counters preserved, the auto split prices deterministically at the
# documented default rates (artifact cache off => no measured sidecar),
# single-chunk hybrid plans resolve pipeline auto to sequential,
# `obs_report diff --phases` shows plan_h2d bytes DOWN with the merged
# exchange/accumulate counts exactly flat, the offline per-term pricer
# reaches a genuine mix under the TPU rates (recommendation flips to
# hybrid when it beats both pure tiers; price_job prices hybrid specs),
# and the PROGRESS.jsonl trend gate fires on a synthetic 3x
# hybrid_plan_bytes regression.  Deterministic, ~45 s on the CPU rig.
hybrid-check:
	JAX_PLATFORMS=cpu $(PYTHON) tools/hybrid_check.py

# Tracing gate (tools/trace_check.py): apply HLO byte-identity with
# tracing on vs off (local ell; streamed result bit-identity rides
# along), DMT_OBS=off emits zero spans (provable no-op), a REAL 2-rank
# recorded run agrees on one trace id and exports a Perfetto JSON with
# balanced B/E pairs nesting chunk < apply < iteration < solve on both
# rank tracks, and `obs_report watch --once` renders a dashboard frame
# from it.  Deterministic, ~60 s on the CPU rig.
trace-check:
	JAX_PLATFORMS=cpu $(PYTHON) tools/trace_check.py

# Solve-service gate (tools/serve_check.py): a scripted bench.py --serve
# load-gen leg (8 mixed jobs, 3 bases) asserting per-job eigenvalues
# match sequential solo runs at rtol 1e-12, measured engine-pool sharing
# (builds < jobs), batched throughput beating solo (retried for timing
# noise), the obs_report watch queue panel rendering; a SIGTERM drain of
# a spool-backed apps/solve_service.py slowed via DMT_FAULT
# (exit 75, in-flight jobs respooled as queued, relaunch drains them —
# the job-level PR 6 checkpoint contract); and the bench_trend gate
# passing on the recorded serve metrics then FIRING on a synthetic 10x
# throughput/latency regression.  Deterministic seeds, ~90 s on CPU.
serve-check:
	JAX_PLATFORMS=cpu $(PYTHON) tools/serve_check.py

# Telemetry-plane gate (tools/slo_check.py, DESIGN.md §31): a clean
# chain-12 solve where the registry snapshot, a REAL ephemeral-port
# /metrics scrape, the textfile, and the events.jsonl metrics_snapshot
# agree EXACTLY (OpenMetrics parity) with zero SLO alerts; DMT_OBS=off
# binding no socket and writing nothing (provable no-op); a 6-job
# spool drained clean vs under DMT_FAULT=solver_block:delay — the SAME
# pinned serve_p99_latency_ms target passes then fails `obs_report slo`
# (exit 1) with slo_alert events in the burned stream; and a forced
# heartbeat stall (exit 76) leaving exactly one valid content-addressed
# post-mortem bundle naming the stuck chunk span (`obs_report
# postmortem` verifies).  Deterministic (the injected delay dwarfs
# scheduler noise), ~60 s on the CPU rig.
slo-check:
	JAX_PLATFORMS=cpu $(PYTHON) tools/slo_check.py

# Dynamics gate (tools/dynamics_check.py, DESIGN.md §29): KPM moments
# on a streamed chain_12 engine match the dense matrix's own Chebyshev
# recurrence at 1e-12 with the plan provably built ONCE (engine_init
# counted once across bounds + every moment), the Jackson-kernel DOS
# matches the exact spectrum through the SAME kernel within the
# stochastic tolerance, exp(-iHt) matches dense expm at rtol 1e-10
# with unitarity drift < 1e-12/step, the max_basis_size-capped
# thick-restart block Lanczos reaches the full-memory E0 at rtol
# 1e-12 with every restart inside the cap, a SIGTERMed mid-trajectory
# apps/dynamics.py run exits 75 and resumes bit-consistently, and the
# kpm_moments_per_s / evolve_steps_per_s trend gate passes then FIRES
# on a synthetic 10x regression.  Deterministic, ~25 s on the CPU rig.
dynamics-check:
	JAX_PLATFORMS=cpu $(PYTHON) tools/dynamics_check.py

# Chaos gate (tools/fault_check.py): the ROADMAP's resumed-run
# bit-consistency acceptance as a repeatable gate — kill a 2-device solve
# mid-iteration (SIGTERM → EXIT_PREEMPTED with a safe-point checkpoint;
# SIGKILL → cadence checkpoint), resume with the same argv, and assert the
# resumed E0 matches an uninterrupted run to rtol 1e-12; then inject each
# DMT_FAULT site (artifact read, checkpoint write/rename, exchange, plan
# upload, disk-tier plan-chunk read incl. a checksum-corrupt sidecar) and
# assert the documented retry/degrade/rebuild behavior, bit-identically.
# Deterministic seeds, < 90 s on the CPU rig.
fault-check:
	JAX_PLATFORMS=cpu $(PYTHON) tools/fault_check.py

# Elastic-solve gate (tools/elastic_check.py): topology-portable
# checkpoints on the 2↔4 virtual-device CPU rig — SIGKILL a 4-device
# solve mid-iteration and resume on 2 (and the reverse), resumed E0 ==
# uninterrupted E0 at rtol 1e-12 with a solver_checkpoint{resharded}
# event; a chain_16 solve rides a full shrink+grow cycle under a dumb
# supervisor with no operator intervention; matching-D restores stay
# reshard-free; an injected ckpt_reshard fault degrades the restore to a
# fresh (still-correct) solve; a SIGTERMed 2-device solve service drains
# its respooled jobs on 1 device with admission re-priced against the
# live capacity; streamed plans rebuilt at D′ emit plan_reshard; and
# resume_reshard_s / resume_rebuild_plan_s gate in bench_trend
# (pass on repeat, fire on a synthetic 10x regression).  ~90 s warm
# on CPU, up to ~4 min cold.
elastic-check:
	JAX_PLATFORMS=cpu $(PYTHON) tools/elastic_check.py

# Self-tuning gate (tools/tune_check.py, DESIGN.md §30): a 10x-wrong
# flop-rate calibration flips the static argmin, the live posterior
# converges measured-vs-priced to within 25% in <=4 windows and its
# re-search lands exactly on the correctly-calibrated rig's config; a
# REAL live-mode engine seeded with a poisoned tuned artifact under a
# 50x-optimistic calibration drifts at the first window close and
# re-keys ONLY one apply after a window boundary (never mid-apply),
# with every apply correct vs the dense reference and bit-identical
# per knob token; the learned posterior reaches tools/capacity.py
# (price_job rate_source == "posterior"); and the bench_trend gate
# passes on a repeat autotuned_steady_apply_ms record then FIRES on a
# synthetic 3x regression.  Isolated artifact root, deterministic,
# ~5 s on the CPU rig; retried for timing noise in the live leg.
tune-check:
	@ok=1; for i in 1 2 3; do \
	  if JAX_PLATFORMS=cpu $(PYTHON) tools/tune_check.py; then \
	    ok=0; break; \
	  else \
	    echo "tune-check: attempt $$i failed; retrying (live-leg" \
	      "timing noise vs a genuine break resolves by attempt 3)"; \
	  fi; \
	done; exit $$ok

# Continuous-profiling gate (tools/profile_check.py, DESIGN.md §32):
# every precompile() miss records an HLO cost profile whose phase
# buckets sum EXACTLY to the executable's cost_analysis() totals,
# content-addressed next to the XLA cache and round-tripping through
# load_profile; the apply HLO is byte-identical with
# DMT_PROFILE=sampled vs off; sampled trace windows at a cadence priced
# from the rig's own measured capture cost stay under the 2% overhead
# budget (re-priced and retried in-process — the capture stop cost is
# noisy on a shared host); `obs_report roofline` gains the hlo-ms third
# column summing to the measured wall; a forced bench_trend gate
# failure triggers a flight-recorder bundle naming the hottest ops; and
# tools/profile_diff.py passes on a self-diff then FIRES naming a
# synthetically 10x-regressed op in its top rows.  ~60 s on the CPU rig
# (the overhead leg must amortize real profiler captures).
profile-check:
	JAX_PLATFORMS=cpu $(PYTHON) tools/profile_check.py

# Numerical-health gate (tools/health_check.py): chain-16 smoke applies
# with probes on vs off in ONE process (same warm engine — cross-process
# wall-clock would measure cache state, not probe cost), asserting the
# probe overhead on device_ms stays under HEALTH_THRESHOLD and that a
# healthy probes-on Lanczos solve emits ZERO health warnings.  Retries
# live inside the tool (same noise rationale as obs-check above).
health-check:
	JAX_PLATFORMS=cpu $(PYTHON) tools/health_check.py \
	  --threshold $(HEALTH_THRESHOLD)

clean:
	find . -name '__pycache__' -type d -exec rm -rf {} + 2>/dev/null; true
	rm -f distributed_matvec_tpu/enumeration/_native_*.so
