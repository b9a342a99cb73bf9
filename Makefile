# Test harness — the analog of the reference Makefile's check targets
# (/root/reference/Makefile:79-126).  Everything runs from a plain checkout;
# no install step needed.  Nothing here measures time: speed is measured
# on the chip by BENCHMARK.json + benchmark/ and recorded in
# PERF_LEDGER.jsonl and PERF.md; the gates below assert results, counts
# and state transitions, which repeat exactly on the CPU.

PYTHON ?= python

.PHONY: check check-fast check-solve smoke dryrun warm-cache \
	health-check mem-check stream-check fault-check \
	roofline-check compress-check trace-check pipeline-check \
	hybrid-check serve-check elastic-check dynamics-check tune-check \
	slo-check profile-check clean

check:
	$(PYTHON) -m pytest tests/ -q
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

# The benchmark's own CPU rehearsal: the harness end to end at toy size
# against references that share no code with the engine; reads no device
# number.  The traced run goes in a process of its own: its build-span
# reader refuses a span store that holds other runs' toy builds of the
# same few ms (PERF.md §7; on the chip a run is one process with one
# build).  When a `benchmark` PR hands each run its own span events this
# becomes one pytest call.
SMOKE_TRACED = benchmark/tests/test_rehearsal.py::test_traced_run_reports_the_per_layer_metrics
smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest benchmark/tests/test_rehearsal.py \
	  benchmark/tests/test_correct.py -q --deselect $(SMOKE_TRACED)
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest $(SMOKE_TRACED) -q

dryrun:
	$(PYTHON) __graft_entry__.py

# Pre-build the artifact caches (basis / structure / XLA) for the small
# CPU configs so engine construction in later processes is seconds.
warm-cache:
	$(PYTHON) tools/warm_cache.py --configs cpu

# Memory-observability gate (tools/mem_check.py): chain-16 smoke run,
# asserting the device-memory ledger reconciles with ell_nbytes exactly
# and with the apply executable's memory_analysis() within tolerance,
# that the obs stream carries memory_ledger/memory_analysis events the
# capacity planner can read, and that a healthy run emits ZERO
# OOM/critical memory events.
mem-check:
	JAX_PLATFORMS=cpu $(PYTHON) tools/mem_check.py

# Streamed-mode gate (tools/stream_check.py): bit-identity of streamed vs
# fused applies (single + batch + <x,Hx>), exchange counters preserved,
# the streamed apply's structural counts (no orbit-scan gathers, fewer
# compute flops, exactly plan_bytes host-to-device),
# DMT_ARTIFACT_CACHE=off pure host-RAM streaming with zero disk writes,
# and the plan sidecar save/restore round-trip.
stream-check:
	JAX_PLATFORMS=cpu $(PYTHON) tools/stream_check.py

# Compressed-plan-stream gate (tools/compress_check.py): lossless/f32
# codec round trip, the measured-error gate (lossless <= 1e-12 vs fused,
# measured 0.0; f32 <= 1e-6), off-tier bit-identity with bitpacked rok,
# encoded plan bytes >= 2.5x smaller gated via `obs_report diff --phases`
# (phase_plan_h2d_bytes down, compute flat).  Deterministic, ~40 s on CPU.
compress-check:
	JAX_PLATFORMS=cpu $(PYTHON) tools/compress_check.py

# Phase-attribution gate (tools/roofline_check.py): apply HLO
# byte-identity with phase probes on vs off (local ell + distributed
# fused), `obs_report roofline` model-vs-measured reconciliation on a
# live streamed run (the attributed phase walls sum to the apply wall
# they divide, binding resource named, pipelined-apply estimate finite).
# Deterministic, ~15 s on the CPU rig.
roofline-check:
	JAX_PLATFORMS=cpu $(PYTHON) tools/roofline_check.py

# Pipelined-apply gate (tools/pipeline_check.py): bit-identity of
# pipelined vs sequential applies (fused + streamed, single + k=3 batch,
# counters preserved), and the pipelined streamed apply's per-phase
# bytes / gathers / flops equal to the sequential one's with the
# `pipeline` record and the roofline side-by-side present.  The
# time-at-barrier cut under an injected straggler on a real 2-process
# run is tests/test_engine_pipelined.py's.  Deterministic, ~10 s.
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
# hybrid when it beats both pure tiers; price_job prices hybrid specs).
# Deterministic, ~45 s on the CPU rig.
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

# Solve-service gate (tools/serve_check.py): one burst of 8 mixed jobs
# (3 bases) through the scheduler, asserting per-job eigenvalues match
# sequential solo runs at rtol 1e-12, engine-pool sharing (builds <
# jobs) and batching (batches < jobs) by count, the obs_report watch
# queue panel rendering; and a SIGTERM drain of a spool-backed
# apps/solve_service.py slowed via DMT_FAULT (exit 75, in-flight jobs
# respooled as queued, relaunch drains them — the job-level PR 6
# checkpoint contract).  Deterministic seeds, ~25 s on CPU.
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
# postmortem` verifies).  Deterministic: the clean legs pin the
# self-baselined wall-clock SLOs out of reach, so only the injected
# delay can burn (it dwarfs scheduler noise).  ~140 s on the CPU rig.
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
# 1e-12 with every restart inside the cap, and a SIGTERMed
# mid-trajectory apps/dynamics.py run exits 75 and resumes
# bit-consistently.  Deterministic, ~25 s on the CPU rig.
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
# live capacity; and streamed plans rebuilt at D′ emit plan_reshard.
# ~90 s warm on CPU, up to ~4 min cold.
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
# per knob token; and the learned posterior reaches tools/capacity.py
# (price_job rate_source == "posterior").  Isolated artifact root,
# ~5 s on the CPU rig.  The live leg's drift is the tuner reading real
# apply walls against a 50x lie, so it is retried (it leaves with the
# cost model, ROADMAP D7).
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
# DMT_PROFILE=sampled vs off; profile_every=8 over 16 applies captures
# exactly two stamped trace windows; `obs_report roofline` gains the
# hlo-ms third column summing to the wall it is normalised to; a
# triggered capture dumps a flight-recorder bundle naming the hottest
# ops; and tools/profile_diff.py passes on a self-diff then FIRES naming
# a synthetically 10x-regressed op in its top rows.  ~15 s on the CPU rig.
profile-check:
	JAX_PLATFORMS=cpu $(PYTHON) tools/profile_check.py

# Numerical-health gate (tools/health_check.py): a healthy probes-on
# Lanczos solve of a 16-site chain converges and emits ZERO health
# warnings.
health-check:
	JAX_PLATFORMS=cpu $(PYTHON) tools/health_check.py

clean:
	find . -name '__pycache__' -type d -exec rm -rf {} + 2>/dev/null; true
	rm -f distributed_matvec_tpu/enumeration/_native_*.so
