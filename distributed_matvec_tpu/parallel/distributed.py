"""Multi-device matvec engine: y = H·x over hash-sharded shards of the basis.

TPU-native redesign of the reference's distributed engine
(``/root/reference/src/DistributedMatrixVector.chpl``): ``matrixVectorProduct``
(:1072-1093) runs per-locale SPMD producers that generate ``(β, c·x[α])``
amplitudes, radix-partition them by owning locale (:265-311), push them
through bounded RDMA buffers (:313-436) and accumulate on the owner with
atomics — ~900 lines of hand-rolled flow control.  Here the Hilbert dimension
is sharded over a 1-D ``jax.sharding.Mesh`` (state σ lives on shard
``hash64(σ) % D``, exactly ``localeIdxOf``, StatesEnumeration.chpl:129-136)
and the exchange is a single XLA ``all_to_all`` over ICI inside ``shard_map``.

Three modes, mirroring :class:`~.engine.LocalEngine`:

* ``"ell"`` (default) — **static routing plan**.  Because the sparsity
  structure is fixed per (operator, basis), the cross-shard communication
  schedule can be *precompiled*: at build time each shard computes, for every
  local row, which (peer, local-index) each neighbor amplitude lives at; the
  per-peer query lists are exchanged once on the host.  Every subsequent
  matvec is then

      send buffer  S[q] = x_local[queries_from_q]     (static gather)
      R = all_to_all(S)                               (one collective, pure x values)
      y = diag·x + Σ_t coeff[t] · concat(x_local, R)[g_idx[t]]

  — no u64 hashing, no sort, no searchsorted, no scatter at matvec time.
  This replaces the reference's *dynamic* producer/consumer routing with a
  compile-time communication plan, the way XLA itself handles sharded matmuls.
  Where the peers read nearly all of each shard (:func:`exchange_gathers`)
  the plan indexes the all-gathered shards instead: every peer receives the
  whole of ``x_local``, the terms gather from that table of D·M rows, and no
  send buffer is packed.

* ``"compact"`` — the ELL routing plan with 4 B/entry sign-tagged indices
  for isotropic real sectors (coefficients derived as ``W·s·n(j)/n(i)`` at
  matvec time; remote norms are STATIC and exchanged once at plan time, so
  the per-apply ``all_to_all`` still carries only x values) — per-shard
  capacity ~3× over ELL.

* ``"fused"`` — dynamic bucketing for bases whose ELL tables exceed HBM: per
  row chunk, generate amplitudes (scatter form), sort by owner, compact into
  fixed-capacity ``[D, C]`` buffers (capacity from ``remote_buffer_size`` ×
  ``all_to_all_capacity_factor`` — the analog of ``kRemoteBufferSize``,
  DistributedMatrixVector.chpl:456), ``all_to_all``, then
  ``searchsorted`` + ``segment_sum`` on the owner.  Overflowed contributions
  are *counted* and surfaced (the reference instead blocks on a full buffer);
  the first apply checks the counter and fails loudly.

* ``"streamed"`` — the fused exchange with the structure resolved ONCE: a
  build pass runs the fused-class per-chunk program (orbit scan + routing +
  receive-side lookup) a single time, spills the resulting plan — per
  (row, term) routed exchange slot + coefficient, plus the per-device
  receive layout — to host RAM (optional artifact-cache disk tier), and
  every subsequent apply double-buffers those chunks H2D and skips
  ``state_info`` entirely: the N·T·|G| scan term becomes a bandwidth-bound
  stream, and the ``all_to_all`` carries amplitudes only.  Device-resident
  memory matches fused (no tables); steady-state applies run at plan-stream
  bandwidth.  Bit-identical to ``fused`` for single vectors and k ≤ 4
  batches (same chunking, same bucket math, same accumulation order).

* ``"hybrid"`` — the per-term recompute-vs-stream split (DESIGN.md §28):
  each Hamiltonian term takes whichever tier is cheapest for *it*, priced
  by the calibrated roofline (``obs/roofline.choose_hybrid_split`` —
  recompute flops at the measured flop rate vs encoded plan bytes +
  decode gathers at the measured H2D/gather rates).  The build resolves
  the FULL structure once (exactly the streamed build), then stores only
  the streamed term subset's compressed plan slices — plan bytes and
  build-output volume shrink by the recompute share — while the chunk
  program re-derives the cheap terms' structure on device beside the
  streamed terms' decode and merges both into ONE send buffer: the
  recompute entries take, per exchange bucket, exactly the slots the
  streamed entries left free, which are provably the full plan's merged
  slots — so the apply stays bit-identical to pure streamed (the gate)
  while the split mix compiles as one static program per fingerprint
  (GSPMD's one-program argument, PAPERS.md).  Split policy via
  ``DMT_HYBRID`` / ``hybrid_split=`` (auto | all-stream | all-recompute |
  stream:<terms>); the resolved mask is baked into fingerprint v4.

The chunked modes (fused, streamed, hybrid) additionally accept
``pipeline_depth``
(``DMT_PIPELINE``, DESIGN.md §25): a software pipeline that keeps chunk
*i*'s amplitude exchange in flight while chunk *i+1*'s local
gather/multiply runs — plan fetches prefetched by worker threads,
produce/exchange split programs, the exchange decomposed into staged
``ppermute`` rounds — with exchanges retiring strictly in chunk order, so
pipelined applies are bit-identical to sequential ones at every depth.

Both modes keep the reference's invariant check: a nonzero amplitude routed
to a state absent from the basis raises (DistributedMatrixVector.chpl:113-118).

Layouts: ``x`` and ``y`` live in *hashed* layout ``[D, M]`` (shard-padded,
pad slots zero); :class:`~.shuffle.HashedLayout` converts to/from the global
sorted (*block*) order.  Batches ``[D, M, k]`` are supported end-to-end.
"""

from __future__ import annotations

import math
import re
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..models.operator import Operator
from ..obs import counter, emit, histogram, obs_enabled
from ..obs import trace as obs_trace
from ..obs import health as obs_health
from ..obs import profile as obs_profile
from ..obs import memory as obs_memory
from ..obs import phases as obs_phases
from ..ops import kernels as K
from ..ops.bits import build_sorted_lookup, hash64, state_index_bucketed
from ..ops.split_gather import prep_gather, split_gather_enabled
from ..utils import faults
from ..utils.config import get_config
from ..utils.logging import log_debug
from ..utils.timers import TreeTimer
from .engine import (SENTINEL_STATE, analyze_bound_apply, apply_diag_jit,
                     attach_traced_counter_check,
                     check_complex_backend, choose_ell_split,
                     emit_engine_init, gather_coefficients_jit,
                     gather_row_bytes, oom_reraise, operator_counts,
                     precompile, raise_deferred_failure,
                     record_structure_cache, register_engine_memory,
                     compact_magnitude, unroll_terms_ok, use_pair_complex)
from .mesh import SHARD_AXIS, make_mesh, shard_spec
from .shuffle import HashedLayout

__all__ = ["DistributedEngine"]


def _sidecar_name(d: int, kind: str) -> str:
    """The per-D plan-sidecar naming convention — the ONE definition.
    ``_structure_sidecar`` / ``_stream_sidecar`` build names through it
    and ``_emit_plan_reshard`` parses device counts back out through the
    inverse ``_SIDECAR_RE`` right below; a rename happens here and in
    that regex, nowhere else."""
    return f".dist{d}.{kind}.h5"


#: inverse of :func:`_sidecar_name` — captures the device count of a
#: sidecar of either kind; keep in lockstep with the format above
_SIDECAR_RE = re.compile(r"\.dist(\d+)\.(?:stream|structure)\.h5$")


def _round_up(n: int, b: int) -> int:
    return max(((n + b - 1) // b) * b, b)


def _pspec(ndim: int) -> P:
    """PartitionSpec splitting axis 0 over the mesh, replicating the rest."""
    return P(SHARD_AXIS, *([None] * (ndim - 1)))


def exchange_gathers(D: int, M: int, C: int) -> bool:
    """The ELL exchange's form, from the plan's own numbers: True where
    all-gathering the D shards of M rows moves no more values than the
    packed ``all_to_all`` of C values a peer, ``(D - 1)·M <= D·C``; the
    same inequality says the all-gathered table (D·M rows) is no longer
    than the receive-extended one (M + D·C).  One device exchanges
    nothing."""
    return D > 1 and (D - 1) * M <= D * C


def _close_plan_files(files: dict) -> None:
    """Close a streamed engine's lazily-opened disk-tier sidecar handles
    (weakref.finalize target — a long-lived process constructing many
    disk-tier engines must not accumulate open descriptors)."""
    for f in files.values():
        try:
            f.close()
        except Exception:
            pass
    files.clear()


def _plan_chunk_crc(pc: dict) -> int:
    """CRC32 over one (chunk, shard) plan record's arrays in the fixed
    ``_STREAM_ARRAYS`` order — the per-chunk integrity check the disk tier
    verifies on every read (a torn/bit-rotted sidecar chunk must trigger
    the rebuild-from-structure fallback, not corrupt a solve silently)."""
    import zlib

    c = 0
    for k in DistributedEngine._STREAM_ARRAYS:
        c = zlib.crc32(np.ascontiguousarray(pc[k]).tobytes(), c)
    return c


def _bucket_positions(key: jax.Array, D: int) -> jax.Array:
    """Rank of each entry within its ``key`` bucket (keys in [0, D]; D marks
    dead entries).  Shared by the fused apply and the streamed plan build so
    their routing — and therefore the exchange layout — is bit-identical.

    For small meshes the key takes only D+1 values, so a one-hot cumsum
    gives the rank in one O(N·D) vector pass — measured 16% faster than the
    stable argsort it replaces at chain_32_symm, and bit-identical (cumsum
    rank = stable-sort position).  The O(N·D) intermediates grow with mesh
    size, so large meshes keep the O(N log N) sort (the crossover is near
    the sizes where N·D·4B per chunk stops fitting in cache)."""
    if D <= 16:
        onehot = (key[:, None] == jnp.arange(D)[None, :])
        pos_all = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
        return jnp.take_along_axis(
            pos_all, jnp.clip(key, 0, D - 1)[:, None], 1)[:, 0]
    order = jnp.argsort(key, stable=True)
    key_s = key[order]
    starts = jnp.searchsorted(key_s, jnp.arange(D + 1))
    pos_s = (jnp.arange(key_s.shape[0])
             - starts[jnp.clip(key_s, 0, D)])
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0]))
    return pos_s[inv]


def _staged_all_to_all(sb, axis_name: str):
    """The monolithic ``all_to_all`` decomposed into D−1 ``ppermute``
    rounds plus the local bucket copy — the overlappable-collective-stages
    decomposition of "Memory-efficient array redistribution through
    portable collective communication" (PAPERS.md), used by the pipelined
    apply schedules (DESIGN.md §25).

    ``sb`` is one shard's ``[D, Cap, ...]`` bucketed send buffer; the
    result is ELEMENT-IDENTICAL to ``all_to_all(sb, axis, 0, 0,
    tiled=True)``: round ``r`` moves each shard ``i``'s bucket for peer
    ``(i+r) % D`` and lands it at receive slot ``(i−r) % D``, so the
    reassembled layout — and every accumulation that follows — is
    bit-identical to the monolithic exchange.  What changes is the
    *schedule*: each round is an independent collective the compiler's
    latency-hiding scheduler can start early and overlap with unrelated
    compute (the fused pipeline's chunk-ahead gather/multiply), where the
    single fat ``all_to_all`` is one barrier-shaped rendezvous."""
    D = sb.shape[0]
    if D == 1:
        return sb
    i = jax.lax.axis_index(axis_name)
    out = jnp.zeros_like(sb)
    mine = jax.lax.dynamic_slice_in_dim(sb, i, 1, axis=0)
    out = jax.lax.dynamic_update_slice_in_dim(out, mine, i, axis=0)
    for r in range(1, D):
        perm = [(j, (j + r) % D) for j in range(D)]
        payload = jax.lax.dynamic_slice_in_dim(sb, (i + r) % D, 1, axis=0)
        got = jax.lax.ppermute(payload, axis_name, perm)
        out = jax.lax.dynamic_update_slice_in_dim(out, got, (i - r) % D,
                                                  axis=0)
    return out


class _PlanPrefetcher:
    """Depth-bounded background staging of streamed plan chunks — the
    pipelined apply's H2D side (DESIGN.md §25).

    The sequential apply fetches chunk ``ci+1`` inline between chunk
    dispatches, so every millisecond of plan I/O (RAM-dict walk, disk-tier
    read + CRC, retry backoff) lands on the apply's critical path.  Here
    worker threads run the FETCH (:meth:`DistributedEngine.
    _fetch_plan_chunk` — GIL-releasing I/O, deliberately NOT the
    ``device_put`` staging, which would contend with the apply thread's
    dispatches) up to ``depth`` chunks ahead of the consumer (the
    backpressure keeps host staging memory bounded at ``depth`` chunks —
    the H2D analog of the send-slot discipline), and the consumer's
    measured ``get`` wait is the apply's time-at-barrier: ~0 when the
    fetch hid behind chunk compute, the exposed latency otherwise.

    One worker when the plan lives on the DISK tier (h5py handles are not
    thread-safe — reads stay serialized, the CRC check + retry backoff
    still overlap compute); ``min(depth, 4)`` workers for the RAM tier,
    unless the autotuner priced a specific ``prefetch_workers`` count
    (DESIGN.md §30), which then bounds it.
    Workers NEVER run the corrupt-chunk degrade path (it can dispatch
    collective build programs and mutate the engine's plan state): a read
    failure is delivered as a ``degrade`` marker and the consumer repairs
    on the APPLY thread exactly as the sequential schedule would; any
    other worker failure is re-raised on the apply thread."""

    def __init__(self, eng, nchunks: int, depth: int, start: int = 0):
        import threading

        self._eng = eng
        self._n = int(nchunks)
        self._depth = max(int(depth), 1)
        self._cv = threading.Condition()
        self._ready: dict = {}
        self._consumed = int(start) - 1
        self._next = int(start)
        self._stop = False
        tuned_w = getattr(eng, "_tune_workers", None)
        n_workers = 1 if eng._plan_disk is not None \
            else min(tuned_w or self._depth, self._depth, 4)
        self._threads = [
            threading.Thread(target=self._work, daemon=True,
                             name=f"dmt-plan-prefetch-{k}")
            for k in range(min(n_workers, self._n) or 1)]
        for t in self._threads:
            t.start()

    def _work(self) -> None:
        while True:
            with self._cv:
                while (not self._stop and self._next < self._n
                       and self._next > self._consumed + self._depth):
                    self._cv.wait()
                if self._stop or self._next >= self._n:
                    return
                ci = self._next
                self._next += 1
            t0 = time.perf_counter()
            try:
                res = ("ok", self._eng._fetch_plan_chunk(ci, degrade=False),
                       (time.perf_counter() - t0) * 1e3)
            except (OSError, KeyError, ValueError) as e:
                # a read failure whose HANDLING (degrade/rebuild) belongs
                # on the apply thread — marker, not a repair
                res = ("degrade", e, (time.perf_counter() - t0) * 1e3)
            except BaseException as e:   # re-raised by the consumer
                res = ("err", e, 0.0)
            with self._cv:
                self._ready[ci] = res
                self._cv.notify_all()

    def get(self, ci: int):
        """Block until chunk ``ci`` is fetched.  Returns
        ``(kind, value, stage_ms, wait_ms)`` — ``kind`` is ``"ok"``
        (value = the fetched host arrays) or ``"degrade"`` (value = the
        read failure; the consumer repairs on the apply thread);
        ``stage_ms`` is the worker's fetch wall (the work the pipeline
        HID), ``wait_ms`` the consumer's exposed wait (the
        time-at-barrier sample).  Worker errors re-raise here."""
        t0 = time.perf_counter()
        with self._cv:
            while ci not in self._ready:
                self._cv.wait()
            kind, val, stage_ms = self._ready.pop(ci)
            self._consumed = max(self._consumed, ci)
            self._cv.notify_all()
        if kind == "err":
            self.close()
            raise val
        return kind, val, stage_ms, (time.perf_counter() - t0) * 1e3

    def close(self, join: bool = False) -> None:
        """Stop the workers.  ``join=True`` additionally waits them out —
        the degrade path joins before repairing so no worker still holds
        the shared (thread-unsafe) h5py handles it is about to touch."""
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        if join:
            for t in self._threads:
                t.join()


class DistributedEngine:
    """Hash-sharded distributed matvec over a ``jax.sharding.Mesh``.

    Usage::

        eng = DistributedEngine(operator, n_devices=8)
        xh = eng.to_hashed(x)          # block [N] → hashed [D, M]
        yh = eng.matvec(xh)            # one all_to_all per application
        y = eng.from_hashed(yh)

    Semantics match ``matrixVectorProduct``
    (DistributedMatrixVector.chpl:1072-1093); distribution matches
    ``localeIdxOf`` hashing (StatesEnumeration.chpl:129-136).
    """

    def __init__(self, operator: Operator, mesh: Optional[Mesh] = None,
                 n_devices: Optional[int] = None,
                 batch_size: Optional[int] = None,
                 mode: Optional[str] = None,
                 structure_cache: Optional[str] = None,
                 layout: Optional[HashedLayout] = None,
                 shards_path: Optional[str] = None,
                 pipeline_depth=None,
                 hybrid_split=None):
        _t_init = time.perf_counter()
        basis = operator.basis
        #: True when the representatives came from the artifact-cache
        #: checkpoint rather than a fresh enumeration (always False for
        #: shard-native and pre-built bases).
        self.basis_restored = False
        cfg = get_config()
        mode = mode or cfg.matvec_mode
        if mode not in ("ell", "compact", "fused", "streamed", "hybrid"):
            raise ValueError(f"unknown engine mode {mode!r}")
        if not operator.is_hermitian:
            raise ValueError("the engine requires a Hermitian operator")
        self.operator = operator
        self.mode = mode
        self.mesh = mesh if mesh is not None else make_mesh(n_devices)
        self.n_devices = self.mesh.devices.size
        # Cross-process coordination is keyed off the MESH, not the job: a
        # rank-local mesh (every device addressable) needs no collective
        # agreement even inside a multi-process jax.distributed job — e.g.
        # per-rank replica engines on backends whose CPU client cannot run
        # cross-process computations at all (the 2-process obs test rig).
        self._multi = any(d.process_index != jax.process_index()
                          for d in self.mesh.devices.flat)
        self.real = operator.effective_is_real
        # Complex sectors: (re, im)-f64 pair form on a TPU mesh (vectors get
        # a trailing axis of 2), native c128 elsewhere.  Both are decided by
        # the platform the MESH runs on (a CPU mesh on a TPU host never
        # touches the hanging TPU compiler).
        platform = self.mesh.devices.flat[0].platform
        self.pair = (not self.real) and use_pair_complex(platform)
        if not self.pair:
            check_complex_backend(self.real, platform=platform)
        self._dtype = jnp.float64 if (self.real or self.pair) \
            else jnp.complex128
        self.timer = TreeTimer("DistributedEngine")
        # pre-build watermark: the delta against the post-init sample in
        # register_engine_memory is the construction's device footprint
        obs_memory.sample_watermark("engine_init_start/distributed")

        D = self.n_devices
        self._shards_path = shards_path
        if shards_path is not None:
            # shard-native construction: per-shard representative/norm rows
            # come straight from the sharded-enumeration file
            # (enumeration/sharded.py) — the global array NEVER exists, the
            # regime the reference's distributed enumeration targets
            # (StatesEnumeration.chpl:305-514, README.md:69-116).  The
            # global block-order layout is materialized lazily only if a
            # caller insists on to_hashed/from_hashed.
            from ..enumeration.sharded import load_shard, shard_manifest
            man = shard_manifest(shards_path)
            if man is None:
                raise ValueError(f"no shard manifest at {shards_path}")
            if man["n_shards"] != D:
                raise ValueError(
                    f"shard file has {man['n_shards']} shards, mesh has {D}")
            counts = np.asarray(man["counts"], np.int64)
            self.n_states = int(man["total"])
            M = _round_up(int(counts.max()), 128)   # = HashedLayout padding
            self.layout = None

            def shard_rows(d):
                s, w = load_shard(shards_path, d)
                a = np.full(M, SENTINEL_STATE, np.uint64)
                a[: s.size] = s
                nn = np.ones(M)
                nn[: w.size] = w
                return a, nn
        else:
            if not basis.is_built:
                from ..utils.artifacts import make_or_restore_basis
                self.basis_restored = make_or_restore_basis(basis)
            reps, norms = basis.representatives, basis.norms
            # several engines over the SAME basis (H + observables) can
            # share one layout: the hash partition is a pure function of
            # (reps, D), so recomputing it per engine would repeat O(N)
            # host hashing
            if layout is not None:
                if layout.n_shards != D or layout.n_global != reps.size:
                    raise ValueError(
                        f"shared layout is for {layout.n_global} states on "
                        f"{layout.n_shards} shards, engine needs "
                        f"{reps.size} on {D}")
                self.layout = layout
            else:
                self.layout = HashedLayout(reps, D)
            counts = self.layout.counts
            M = self.layout.shard_size
            self.n_states = reps.size
            alphas_all = self.layout.to_hashed(reps, fill=SENTINEL_STATE)
            norms_all = self.layout.to_hashed(norms, fill=1.0)

            def shard_rows(d):
                return alphas_all[d], norms_all[d]

        self.shard_size = M
        self.counts = counts
        from ..utils.artifacts import ensure_compilation_cache
        ensure_compilation_cache()
        with self.timer.scope("transfer"), \
                obs_trace.span("engine_init/transfer", kind="phase"):
            self.tables = K.device_tables(operator, pair=self.pair)
        counter("bytes_h2d", path="engine_tables").inc(sum(
            a.nbytes for a in jax.tree_util.tree_leaves(self.tables)))
        self.num_terms = int(self.tables.off.x.shape[0])
        self._sh1 = shard_spec(self.mesh, 2)
        self._sh2 = shard_spec(self.mesh, 3)

        # Per-shard sorted representative/norm/diag rows ([M], SENTINEL
        # pad), shipped to their device one shard at a time; this process
        # loads only its addressable shards.  The diag program is the
        # process-wide shared one — no per-engine retrace.
        alpha_rows = [None] * D
        norm_rows = [None] * D
        diag_rows = [None] * D
        for d in range(D):
            if not self._shard_addressable(d):
                continue
            a, w = shard_rows(d)
            alpha_rows[d], norm_rows[d] = a, w
            dd = np.asarray(apply_diag_jit(self.tables.diag, jnp.asarray(a)))
            diag_rows[d] = np.where(a != SENTINEL_STATE, dd, 0.0)
        self._alphas = self._assemble_sharded(alpha_rows)
        self._norms = self._assemble_sharded(norm_rows)
        self._diag = self._assemble_sharded(diag_rows)

        b = min(batch_size or cfg.matvec_batch_size, M)
        self.batch_size = _round_up(min(b, M), 8)
        # Overflow/invalid counters are validated once per compiled program
        # (keyed by row-chunk size B): fused wide batches compile shrunk-B
        # programs with proportionally shrunk all_to_all capacity, and a
        # shrunk program can overflow where the base one didn't (higher
        # relative bucket skew), so a single global flag is not enough.
        self._checked: set = set()
        self._last_program_key = None
        self._last_capacity: Optional[int] = None
        self._warned_traced_check = False
        self._deferred_failure: Optional[str] = None
        self._apply_idx = 0
        #: streamed mode's per-apply chunk timeline (stall + dispatch ms),
        #: drained by _matvec_impl into the apply_phases event
        self._stream_timeline: list = []
        #: pipelined applies (fused/streamed, DESIGN.md §25): resolved
        #: depth (0 = sequential — the bit-identical default); the
        #: constructor argument wins over ``config.pipeline``
        #: (``DMT_PIPELINE``); resolved per mode below once the chunk
        #: count is known
        self._pipeline_req = pipeline_depth
        self.pipeline_depth = 0
        self._plan_remote_unique: Optional[int] = None
        #: the ELL exchange: "packed" (a send buffer of C values a peer and
        #: an ``all_to_all``) or "gathered" (the shards all-gathered); set
        #: by the plan build from :func:`exchange_gathers`, or restored
        self.exchange_form = "packed"
        self._n_my_shards = sum(
            1 for d in range(D) if self._shard_addressable(d))

        # -- self-tuning runtime (DESIGN.md §30) ---------------------------
        #: the adopted knob config (tune/space.TunedConfig) when
        #: tune=static|live; the live controller; and a re-tune proposal
        #: awaiting the next safe boundary (the top of an apply, or a
        #: serve-pool acquire — NEVER mid-apply).  Tuned knobs flow into
        #: the plan through the SAME fields a hand-set engine uses
        #: (batch_size, codec tier, hybrid token), so the fingerprint —
        #: and therefore the sidecar and bit-identity story — is
        #: identical to hand-setting the same values.
        self._tuned = None
        self._tuner = None
        self._retune_pending = None
        self._tune_cal: Optional[dict] = None
        self._tune_compress: Optional[str] = None
        self._tune_hybrid_split = None
        self._tune_workers: Optional[int] = None
        self._tune_plan_tier: Optional[str] = None
        tune_knob = str(cfg.tune).strip().lower() or "off"
        if tune_knob not in ("off", "0", "false", "no", "static", "live"):
            raise ValueError(
                f"unknown tune setting {cfg.tune!r}: pick off | static | "
                "live (DMT_TUNE / config.tune)")
        self._tune_mode = tune_knob \
            if (tune_knob in ("static", "live")
                and mode in ("streamed", "hybrid")) else "off"
        if self._tune_mode != "off":
            self._init_autotune(batch_size, pipeline_depth, hybrid_split)

        # Row provider for the plan builds: this process's shards come from
        # the rows already loaded above; PEER shards are fetched on demand
        # (shard-file read, or a view of the global layout) one at a time —
        # the build never holds all shards host-side (VERDICT r3 missing #3:
        # per-rank RSS stays ~1/D at the scale that motivates distribution).
        def row_provider(d):
            if alpha_rows[d] is not None:
                return alpha_rows[d], norm_rows[d]
            return shard_rows(d)

        def agree_restored(restored: bool) -> bool:
            """All-or-nothing cache restore across ranks: per-rank sidecars
            are written without a barrier, so one rank can restore while
            another must rebuild — and a half-restored job would hang in
            _plan_stream's collectives.  Rebuild everywhere unless every
            rank restored."""
            if not self._multi:
                return restored
            # ALWAYS join the collective when multi-process — a rank whose
            # cache root failed to resolve (structure_cache None) must still
            # meet the others at the allgather or the job hangs here
            try:
                from jax.experimental import multihost_utils as mhu
                return bool(int(np.min(mhu.process_allgather(
                    np.int32(restored)))))
            except Exception as e:
                # backends without multiprocess host computations (the CPU
                # DCN test rig): the conservative agreement is a rebuild on
                # every rank — the same deterministic answer everywhere, so
                # the _plan_stream collectives stay aligned
                log_debug(f"restore agreement unavailable ({e!r}); "
                          "rebuilding on all ranks")
                return False

        #: True when the plan came from a ``structure_cache`` restore
        #: (explicit path or the default artifact cache) rather than a
        #: fresh host-coordinated build.
        self.structure_restored = False
        # the CALLER's cache path, before per-mode resolution: sidecar
        # names bake in the device count (`.dist{D}.…`), so this is where
        # a topology change (resume at D′ next to a D-era sidecar) is
        # detectable — see _emit_plan_reshard
        cache_arg = structure_cache
        soft_save = structure_cache is None
        if mode in ("ell", "compact"):
            structure_cache = self._resolve_structure_cache(structure_cache)
        if mode == "ell":
            self.structure_restored = agree_restored(
                self._try_load_structure(structure_cache))
            record_structure_cache(self.structure_restored,
                                   structure_cache is not None)
            if not self.structure_restored:
                _t_build = time.perf_counter()
                with self.timer.scope("build_plan"), \
                        obs_trace.span("engine_init/build_plan",
                                       kind="build",
                                       **operator_counts(self.operator)
                                       ) as build_span:
                    try:
                        self._plan_stream(row_provider, compact=False)
                    except Exception as e:
                        if not obs_memory.is_resource_exhausted(e):
                            getattr(self, "_plan_stage_h",
                                    obs_memory.NULL_HANDLE).release()
                        oom_reraise(e, engine="distributed", mode=mode,
                                    phase="init",
                                    n_states=int(self.n_states))
                    build_span.add(**self._plan_counts)
                self._save_structure(structure_cache, soft=soft_save)
                self._emit_plan_reshard(cache_arg,
                                        time.perf_counter() - _t_build)
            self._matvec = self._make_ell_matvec()
            self._checked.add(None)  # static plan: no data-dependent capacity
        elif mode == "compact":
            if not self.real or self.pair:
                raise ValueError(
                    "compact mode requires a real sector (use mode='ell' "
                    "for complex-character momentum sectors)")
            self.structure_restored = agree_restored(
                self._try_load_structure(structure_cache))
            record_structure_cache(self.structure_restored,
                                   structure_cache is not None)
            if not self.structure_restored:
                # W sample strided across this process's shards (the hash
                # partition makes any shard an unbiased basis sample), so
                # shard-native engines never touch the global basis.  The
                # verdict is agreed across ranks BEFORE raising: a
                # rank-local raise (or a rank whose shards are all empty)
                # must not strand the peers in the next collective.
                from .engine import compact_magnitudes
                my = [d for d in range(D) if alpha_rows[d] is not None]
                per = max(1, 4096 // max(len(my), 1))
                smp = [alpha_rows[d][np.linspace(
                    0, int(counts[d]) - 1,
                    min(per, int(counts[d]))).astype(np.int64)]
                    for d in my if counts[d]]
                vals = compact_magnitudes(
                    operator,
                    sample_states=np.concatenate(smp) if smp
                    else np.zeros(0, np.uint64))
                if self._multi:
                    from jax.experimental import multihost_utils as mhu
                    pad = np.full(8, np.nan)
                    pad[: min(vals.size, 8)] = vals[:8]
                    allv = mhu.process_allgather(pad)
                    vals = np.unique(allv[np.isfinite(allv)])
                if vals.size > 1:
                    raise ValueError(
                        f"compact mode needs a single off-diagonal "
                        f"magnitude, found {vals[:5]}; use mode='ell'")
                self._c_W = float(vals[0]) if vals.size else 0.0
                _t_build = time.perf_counter()
                with self.timer.scope("build_plan"), \
                        obs_trace.span("engine_init/build_plan",
                                       kind="build",
                                       **operator_counts(self.operator)):
                    try:
                        self._plan_stream(row_provider, compact=True)
                    except Exception as e:
                        if not obs_memory.is_resource_exhausted(e):
                            getattr(self, "_plan_stage_h",
                                    obs_memory.NULL_HANDLE).release()
                        oom_reraise(e, engine="distributed", mode=mode,
                                    phase="init",
                                    n_states=int(self.n_states))
                self._save_structure(structure_cache, soft=soft_save)
                self._emit_plan_reshard(cache_arg,
                                        time.perf_counter() - _t_build)
                self._c_n_all_shards = None   # only needed by the save above
            self._matvec = self._make_compact_matvec()
            self._checked.add(None)  # static plan: no data-dependent capacity
        else:
            # Per-shard bucketed lookup over each shard's REAL prefix
            # (SENTINEL pads sort last, so real entries are alphas[d][:count]
            # and would otherwise pile into the last bucket and inflate
            # `probes` for every shard).  The directory width is forced
            # globally from the largest shard so every shard shares one
            # shift and the stacked [D, 2^b+1] table is uniform.
            from ..ops.bits import choose_dir_bits
            n_bits = basis.number_bits
            b_global = choose_dir_bits(int(counts.max()), n_bits)
            pair_rows = [None] * D
            dir_rows = [None] * D
            probes = 0
            self._lk_shift = None
            for d in range(D):
                if alpha_rows[d] is None:
                    continue
                lk = build_sorted_lookup(alpha_rows[d][: counts[d]], n_bits,
                                         dir_bits=b_global)
                self._lk_shift = lk[2]
                probes = max(probes, lk[3])
                pr = np.full((M, 2), 0xFFFFFFFF, np.uint32)
                pr[: counts[d]] = lk[0]
                if 0 < counts[d] < M:
                    # pad with the last real row: a probe that clamps past
                    # the prefix then can't spuriously match SENTINEL queries
                    pr[counts[d]:] = lk[0][-1]
                pair_rows[d] = pr
                dir_rows[d] = lk[1]
            if self._multi:
                # probes is data-dependent per shard; the program constant
                # must agree across processes
                from jax.experimental import multihost_utils
                probes = int(np.max(multihost_utils.process_allgather(
                    np.int32(probes))))
            self._lk_probes = probes
            self._lk_pair = self._assemble_sharded(pair_rows)
            self._lk_dir = self._assemble_sharded(dir_rows)
            self._capacity = self._fused_capacity()
            if mode == "fused":
                self.pipeline_depth = self._resolve_pipeline_depth(
                    -(-M // self.batch_size))
                self._matvec = self._make_fused_matvec()
            else:
                # streamed: resolve the fused-class structure ONCE (per
                # construction or artifact-cache restore) into a host-RAM
                # plan, then stream it back per apply — the orbit scan and
                # routing math never run again.  The row provider and
                # (lazily compiled) build program are KEPT for the
                # engine's life: a corrupt disk-tier chunk read degrades
                # to a per-chunk rebuild from structure instead of
                # crashing a solve mid-apply (DESIGN.md §21).
                self._row_provider = row_provider
                self._stream_build_prog = None
                self._plan_repaired: dict = {}
                from ..ops import plan_codec as _PC
                self._compress = (str(cfg.stream_compress).strip().lower()
                                  or "off")
                if self._compress not in _PC.TIERS:
                    raise ValueError(
                        f"unknown stream_compress tier "
                        f"{cfg.stream_compress!r}; set tune=static "
                        "(DMT_TUNE=static) to let the autotuner pick a "
                        "value-exact tier, or pick one of "
                        f"{'|'.join(_PC.TIERS)}")
                if self._tune_compress is not None:
                    # the autotuner's tier (off|lossless only — both
                    # value-exact); a hand-pinned DMT_STREAM_COMPRESS or
                    # non-default config value was never overridden above
                    self._compress = self._tune_compress
                #: hybrid mode's resolved [T] stream mask (True = the
                #: term's entries travel in the plan stream, False = the
                #: term recomputes on device inside the chunk program);
                #: None for the pure streamed mode.  Resolved by policy
                #: (and, for "auto", the per-term cost model over the
                #: build census) or restored from the sidecar codec spec.
                self._hybrid_mask: Optional[np.ndarray] = None
                #: the codec tier the plan actually encodes at: the
                #: configured stream_compress tier, except that hybrid
                #: plans REQUIRE a compacted encoding (a term subset
                #: cannot ride the raw [B, T] layout), so compress "off"
                #: maps to "lossless" — value-exact f64 decode, still
                #: bit-identical to the off-tier streamed apply
                self._codec_tier = self._compress
                if mode == "hybrid":
                    if self._compress == "off":
                        self._codec_tier = "lossless"
                    self._init_hybrid_policy(
                        hybrid_split if hybrid_split is not None
                        else self._tune_hybrid_split)
                stream_cache = self._resolve_structure_cache(structure_cache)
                self.structure_restored = agree_restored(
                    self._try_load_stream_plan(stream_cache))
                record_structure_cache(self.structure_restored,
                                       stream_cache is not None)
                if not self.structure_restored:
                    _t_build = time.perf_counter()
                    with self.timer.scope("build_plan"), \
                            obs_trace.span("engine_init/build_plan",
                                           kind="build",
                                           **operator_counts(
                                               self.operator)):
                        try:
                            self._build_stream_plan(row_provider)
                            if mode == "hybrid":
                                self._hybrid_mask = \
                                    self._resolve_hybrid_mask()
                            self._encode_stream_plan()
                        except Exception as e:
                            if not obs_memory.is_resource_exhausted(e):
                                getattr(self, "_plan_stage_h",
                                        obs_memory.NULL_HANDLE).release()
                            oom_reraise(e, engine="distributed", mode=mode,
                                        phase="init",
                                        n_states=int(self.n_states))
                    self._save_stream_plan(stream_cache, soft=soft_save)
                    self._emit_plan_reshard(cache_arg,
                                            time.perf_counter() - _t_build)
                self._upload_codec_tables()
                if mode == "hybrid":
                    self._setup_hybrid_recompute()
                self._register_stream_plan()
                import weakref
                weakref.finalize(self, _close_plan_files, self._plan_files)
                self.pipeline_depth = self._resolve_pipeline_depth(
                    self._plan_nchunks_v)
                self._matvec = self._make_streamed_matvec()
                # overflow/invalid are structural and validated at plan time
                # (build or restore) — applies revalidate nothing
                self._last_program_key = mode
                self._last_capacity = self._capacity
                self._checked.add(mode)
        # per-rank shard census — the survivor-count column of the
        # cross-rank skew table (`obs_report report --ranks`): how many
        # basis states this rank's addressable shards actually carry
        my_shards = [d for d in range(D) if self._shard_addressable(d)]
        emit("rank_shards", engine="distributed", mode=self.mode,
             n_shards=int(D), shard_size=int(M), shards=my_shards,
             states=int(sum(int(counts[d]) for d in my_shards)),
             **({} if self._plan_remote_unique is None
                else {"remote_entries": int(self._plan_remote_unique)}))
        emit_engine_init(self, "distributed",
                         init_s=time.perf_counter() - _t_init)
        register_engine_memory(self, "distributed")
        self.timer.report()  # tree print, gated by display_timings

    @classmethod
    def from_shards(cls, operator: Operator, shards_path: str,
                    mesh: Optional[Mesh] = None,
                    n_devices: Optional[int] = None,
                    batch_size: Optional[int] = None,
                    mode: Optional[str] = None,
                    structure_cache: Optional[str] = None
                    ) -> "DistributedEngine":
        """Engine straight from a sharded-enumeration file — the basis is
        never built globally (see ``enumeration/sharded.py``); vectors are
        born hashed (:meth:`random_hashed`) and the solvers never leave the
        hashed space.  ``to_hashed``/``from_hashed`` still work for
        moderate sizes by materializing the global layout lazily.

        All modes work shard-native: the plan builds stream peer shards
        from the file one at a time (never all host-side), and
        ``structure_cache`` checkpoints the packed tables (or the
        streamed plan) per shard keyed by the shard manifest's
        fingerprint.  ``fused`` stays the default (no build cost); pick
        ``ell``/``compact`` for the fastest repeated applies, or
        ``streamed`` when their tables exceed HBM but the plan fits
        host RAM/disk."""
        return cls(operator, mesh=mesh, n_devices=n_devices,
                   batch_size=batch_size, mode=mode or "fused",
                   shards_path=shards_path, structure_cache=structure_cache)

    def _require_layout(self) -> HashedLayout:
        """The global block-order layout; for shard-native engines it is
        materialized on first use (O(N) host memory — fine at test sizes,
        intentionally NOT on the scale path)."""
        if self.layout is None:
            from ..enumeration.sharded import load_shard
            log_debug("materializing global layout from shards "
                      f"({self.n_states} states)")
            states = np.concatenate(
                [load_shard(self._shards_path, d)[0]
                 for d in range(self.n_devices)])
            states.sort()
            self.layout = HashedLayout(states, self.n_devices)
        return self.layout


    # ------------------------------------------------------------------
    # ELL/compact modes: static routing plan (streaming two-pass build)
    # ------------------------------------------------------------------

    def _shard_addressable(self, d: int) -> bool:
        """Whether mesh device ``d`` belongs to THIS process — in a
        multi-controller run each process packs and supplies only its own
        shards (the SPMD per-locale setup of Diagonalize.chpl:298-325)."""
        devs = list(self.mesh.devices.flat)
        return devs[d].process_index == jax.process_index()

    def _put_shard(self, piece, d):
        """One shard's host piece → a [1, ...] single-device array on mesh
        device ``d`` (the unit :meth:`_assemble_sharded` stitches), or
        None when ``d`` belongs to another process."""
        if not self._shard_addressable(d):
            return None
        devs = list(self.mesh.devices.flat)
        piece = np.ascontiguousarray(np.asarray(piece))
        counter("bytes_h2d", path="shard_put").inc(piece.nbytes)
        with obs_trace.span("device_wait", kind="phase", at="shard_put"):
            return jax.device_put(piece[None], devs[d])

    def _assemble_sharded(self, shards):
        """[D, ...] device array from per-shard pieces via
        ``make_array_from_single_device_arrays`` — no global host copy
        exists at any point, and in a multi-process run each process only
        supplies its own addressable shards (None placeholders stand in
        for remote ones).  Pieces may be host arrays or already-placed
        ``_put_shard`` results (so builders can ship each shard to its
        device as soon as it is packed and free the host staging before
        packing the next one)."""
        D = self.n_devices
        arrs, shape_tail = [], None
        for d, s in enumerate(shards):
            if s is None:
                continue
            a = s if isinstance(s, jax.Array) else self._put_shard(s, d)
            if a is not None:
                arrs.append(a)
                shape_tail = a.shape[1:]
        spec = shard_spec(self.mesh, len(shape_tail) + 1)
        return jax.make_array_from_single_device_arrays(
            (D,) + shape_tail, spec, arrs)

    def _plan_stream(self, row_provider, compact: bool) -> None:
        """Memory-bounded two-pass routing-plan build (ELL and compact),
        SHARD-LOCAL: this process builds only its addressable shards' tables
        and never holds all shards' representative arrays at once.

        Replaces the reference's per-matvec radix partition + buffer routing
        (DistributedMatrixVector.chpl:265-311, :559-735) with a one-time
        static query plan — built STREAMING: pass 1 walks each own shard's
        row chunks keeping only per-row nnz counts and per-peer UNIQUE
        remote target states (deduplicated incrementally, bounded by the
        dedup'd size); then each peer's sorted rows are visited ONCE
        (``row_provider(p)`` — a shard-file read for shard-native engines,
        a view of the layout otherwise) to resolve the unique targets into
        indices, query lists and, for compact mode, target norms.  Pass 2
        packs entries straight into per-shard final tables that go to their
        device one shard at a time, mapping each entry's exchange slot by
        binary search over the pass-1 unique-state lists — no global
        arrays, no [D, M] scratch.  Peak host staging is O(B·T) chunk
        scratch + one peer's rows + one shard's packed table — the
        distributed analog of :meth:`LocalEngine._build_ell_lowmem`,
        honoring the reference's bounded-buffer property
        (DistributedMatrixVector.chpl:456) at build time.

        In a multi-controller run the per-shard builds proceed in parallel
        (each rank packs its own shards — the per-locale concurrency of the
        reference's enumeration applied to the plan build) and only the
        small coordination data crosses processes: the bad-entry count, the
        nnz histogram, the capacity, and the query lists each destination
        shard must serve (one bounded allgather per source shard).

        Remote queries are DEDUPLICATED per (shard, peer): entries reading
        the same remote x share one exchange slot, so the per-apply
        ``all_to_all`` moves at most M values per peer pair instead of one
        per matrix element (the dense plan gave every reference its own
        slot — a ~T× larger exchange for dense operators).
        """
        D, M, T = self.n_devices, self.shard_size, self.num_terms
        from ..enumeration.host import shard_index as shard_index_host

        multi = self._multi
        if multi:
            from jax.experimental import multihost_utils as mhu
        my_shards = [d for d in range(D) if self._shard_addressable(d)]

        Bc = min(M, max(self.batch_size, 8))
        nchunks = (M + Bc - 1) // Bc

        # the build's staged stream buffers go in the memory ledger for its
        # duration: double-buffered chunk uploads plus the gathered
        # (betas, cf) fetches — what an OOM during the plan build points at
        _mem_h = obs_memory.NULL_HANDLE
        if obs_enabled():
            cfb = 16 if (self.pair or not self.real) else 8
            stage = 2 * (Bc * 16 + Bc * T * (8 + cfb))
            _mem_h = obs_memory.track(
                f"plan/{obs_memory.next_instance('plan_stream')}/staging",
                stage, kind="staging", chunks=int(nchunks))
        # kept on self so the __init__ guard can drop the entry when a
        # NON-OOM build failure unwinds (the staging is freed with the
        # frame then; only a genuine OOM should keep it for forensics)
        self._plan_stage_h = _mem_h

        # ONE fixed-shape gather program (every chunk is padded to Bc rows),
        # AOT-compiled once per (shapes, pair) process-wide and shared with
        # any other engine build over the same shapes; compile time lands in
        # the timer's `compile` scope under `build_plan`.
        gather_chunk = precompile(
            "dist_gather_chunk", (self.pair,),
            gather_coefficients_jit,
            (self.tables, jnp.zeros(Bc, jnp.uint64), jnp.ones(Bc)),
            self.timer)

        def chunks(d):
            """Yield (s, e, n_c, betas, cf, nz) per row chunk, all padded
            to Bc rows (SENTINEL rows carry cf == 0).  Double-buffered:
            chunk ci+1's upload + device pass is dispatched before chunk
            ci's results are fetched, so the device computes ahead while
            the host runs the routing math."""
            a_d, nn_d = row_provider(d)

            def launch(ci):
                s, e = ci * Bc, min((ci + 1) * Bc, M)
                a_c, n_c = a_d[s:e], nn_d[s:e]
                if e - s < Bc:
                    a_c = np.concatenate(
                        [a_c, np.full(Bc - (e - s), SENTINEL_STATE,
                                      np.uint64)])
                    n_c = np.concatenate([n_c, np.ones(Bc - (e - s))])
                counter("bytes_h2d", path="plan_chunk_stream").inc(
                    a_c.nbytes + n_c.nbytes)
                with self.timer.scope("transfer"):
                    a_dev, n_dev = jnp.asarray(a_c), jnp.asarray(n_c)
                return s, e, a_c, n_c, gather_chunk(self.tables, a_dev,
                                                    n_dev)

            pending = launch(0) if nchunks else None
            for ci in range(nchunks):
                nxt = launch(ci + 1) if ci + 1 < nchunks else None
                s, e, a_c, n_c, (betas_d, cf_d) = pending
                # the fetch below is where the double-buffering either paid
                # off (device finished while the host routed the previous
                # chunk → ~0 stall) or didn't — record the wait, it is the
                # stream's whole performance story
                _t_fetch = time.perf_counter()
                with self.timer.scope("transfer"), \
                        obs_trace.span("device_wait", kind="phase",
                                       at="plan_chunk_fetch"):
                    betas, cf = np.asarray(betas_d), np.asarray(cf_d)
                histogram("double_buffer_stall_ms").observe(
                    (time.perf_counter() - _t_fetch) * 1e3)
                counter("bytes_d2h", path="plan_chunk_stream").inc(
                    betas.nbytes + cf.nbytes)
                if self.pair:
                    # plan building is host-side math — c128 is fine here
                    cf = K.complex_from_pair(cf)
                nz = (cf != 0) & (a_c != SENTINEL_STATE)[:, None]
                yield s, e, n_c, betas, cf, nz
                pending = nxt

        # -- pass 1: row-nnz counts, per-peer unique remote targets, local
        #    sector check — own shards only, chunk-streamed ----------------
        with obs_trace.span("plan/count", kind="phase"):
            nnz = {d: np.zeros(M, np.int32) for d in my_shards}
            pend = {d: [[] for _ in range(D)] for d in my_shards}
            bad = 0

            def fold_unique(lst):
                if len(lst) > 1:
                    lst[:] = [np.unique(np.concatenate(lst))]

            for d in my_shards:
                a_d, _ = row_provider(d)
                for s, e, n_c, betas, cf, nz in chunks(d):
                    nnz[d][s:e] = nz.sum(axis=1)[: e - s]
                    flat_b = betas[nz]
                    owner = shard_index_host(flat_b, D)
                    loc = owner == d
                    if loc.any():
                        lb = flat_b[loc]
                        ip = np.searchsorted(a_d, lb)
                        np.clip(ip, 0, M - 1, out=ip)
                        bad += int((a_d[ip] != lb).sum())
                    for p in range(D):
                        if p == d:
                            continue
                        sel = owner == p
                        if sel.any():
                            acc = pend[d][p]
                            acc.append(np.unique(flat_b[sel]))
                            if sum(a.size for a in acc) > \
                                    max(1 << 22, 4 * acc[0].size):
                                fold_unique(acc)
                    log_debug(f"plan pass1 shard {d}: rows {e}/{M}")
                for p in range(D):
                    fold_unique(pend[d][p])
            # every chunk's results were fetched
            obs_memory.sample_watermark("plan/count", synced=True)

        # -- pass 1b: resolve unique targets against each peer's rows (one
        #    peer resident at a time) ------------------------------------
        with obs_trace.span("plan/resolve", kind="phase"):
            queries = {d: [None] * D for d in my_shards}
            qstate = {d: [None] * D for d in my_shards}
            qnorm = {d: [None] * D for d in my_shards}
            for p in range(D):
                peer = None
                for d in my_shards:
                    if p == d:
                        continue
                    if not pend[d][p]:
                        queries[d][p] = np.zeros(0, np.int32)
                        qstate[d][p] = np.zeros(0, np.uint64)
                        qnorm[d][p] = np.zeros(0)
                        continue
                    if peer is None:
                        peer = row_provider(p)
                    a_p, n_p = peer
                    ub = pend[d][p][0]
                    ip = np.searchsorted(a_p, ub)
                    np.clip(ip, 0, M - 1, out=ip)
                    ok = a_p[ip] == ub
                    bad += int((~ok).sum())
                    queries[d][p] = ip[ok].astype(np.int32)
                    qstate[d][p] = ub[ok]
                    qnorm[d][p] = n_p[ip[ok]]
                    pend[d][p] = []
                del peer
            del pend
            obs_memory.sample_watermark("plan/resolve")

        # -- query lists: the sector check agreed, the split chosen, what
        #    each peer reads from each shard --------------------------------
        with obs_trace.span("plan/queries", kind="phase"):
            if multi:
                # agree on the sector check globally so a violation fails
                # loudly on every rank instead of hanging the collectives
                bad = int(np.sum(mhu.process_allgather(np.int64(bad))))
            if bad:
                raise RuntimeError(
                    f"{bad} generated matrix elements map outside the basis — "
                    "operator does not preserve the chosen sector"
                )

            hist = np.zeros(T + 1, np.int64)
            for d in my_shards:
                hist += np.bincount(nnz[d], minlength=T + 1)
            cap = max((queries[d][p].size for d in my_shards for p in range(D)
                       if queries[d][p] is not None), default=0)
            if multi:
                hist = np.sum(mhu.process_allgather(hist), axis=0)
                cap = int(np.max(mhu.process_allgather(np.int64(cap))))
            T0, S, Tmax = choose_ell_split(hist, D * M, T,
                                           real_rows=self.n_states)
            self._ell_T0 = T0
            Tw = Tmax - T0 if S else 0
            C = _round_up(cap, 8)
            self.query_capacity = C
            remote_unique = sum(queries[d][p].size for d in my_shards
                                for p in range(D) if queries[d][p] is not None)
            self._plan_remote_unique = remote_unique
            gathered = not compact and exchange_gathers(D, M, C)
            self.exchange_form = "gathered" if gathered else "packed"
            log_debug(f"routing plan: D={D} M={M} T={T} T0={T0} tail={S} "
                      f"capacity={C} remote_unique(local)={remote_unique} "
                      f"exchange={self.exchange_form}")

            # qin[d][q] = the local indices peer q reads from this shard
            # (0-padded); sorted-unique order fixed by pass 1b.  queries[q][d]
            # lives on shard q's owner, so in a multi-controller run each
            # source shard's query lists cross processes in ONE bounded
            # [D, C] allgather round.  The gathered form packs nothing.
            self._qin = None
            if not gathered:
                qin_rows = {d: np.zeros((D, C), np.int32) for d in my_shards}
                if not multi:
                    for d in my_shards:
                        for q in range(D):
                            if q != d:
                                ql = queries[q][d]
                                qin_rows[d][q, : ql.size] = ql
                else:
                    for q in range(D):
                        buf = np.zeros((D, C), np.int32)
                        if q in queries:
                            for dd in range(D):
                                if dd != q:
                                    ql = queries[q][dd]
                                    buf[dd, : ql.size] = ql
                        buf = np.sum(mhu.process_allgather(buf), axis=0,
                                     dtype=np.int32)
                        for d in my_shards:
                            if d != q:
                                qin_rows[d][q] = buf[d]
                qin_shards = [qin_rows.get(d) for d in range(D)]
                self._qin = self._assemble_sharded(qin_shards)
            obs_memory.sample_watermark("plan/queries")

        with obs_trace.span("plan/pack", kind="phase"):
            W = self._c_W if compact else 0.0
            cdtype = np.float64 if self.real else np.complex128
            S_max = 0
            if S:
                S_max = max((int((nnz[d] > T0).sum()) for d in my_shards),
                            default=0)
                if multi:
                    # tail buffers assemble to a uniform [D, S_max]
                    S_max = int(np.max(mhu.process_allgather(np.int64(S_max))))

            # -- pass 2: pack per-shard tables, one shard resident at a time
            idx_shards, cf_shards = [], []
            trow_shards, tidx_shards, tcf_shards = [], [], []
            n_all_shards = []
            badw = 0
            for d in range(D):
                if not self._shard_addressable(d):
                    # another process packs this shard; keep list positions
                    for lst in (idx_shards, cf_shards, trow_shards,
                                tidx_shards, tcf_shards, n_all_shards):
                        lst.append(None)
                    continue
                a_d, n_d = row_provider(d)
                g_main = None if compact else np.zeros((T0, M), np.int32)
                v_main = (np.zeros((T0, M), np.int32) if compact
                          else np.zeros((T0, M), cdtype))
                rows_t = np.zeros(S_max, np.int32)
                v_tail = (np.zeros((Tw, S_max), np.int32) if compact
                          else np.zeros((Tw, S_max), cdtype))
                i_tail = None if compact else np.zeros((Tw, S_max), np.int32)
                t_cursor = 0
                for s, e, n_c, betas, cf, nz in chunks(d):
                    # per-entry destination: local index, or M + p·C + slot
                    # where slot = position in the pass-1b unique-state list
                    # (binary search — the lists are sorted by construction);
                    # gathered, the row of the all-gathered shards: d·M + the
                    # local index, or p·M + the peer's index pass 1b resolved
                    flat_b = betas[nz]
                    owner = shard_index_host(flat_b, D)
                    gflat = np.zeros(flat_b.size, np.int64)
                    nflat = np.ones(flat_b.size)
                    loc = owner == d
                    if loc.any():
                        ip = np.searchsorted(a_d, flat_b[loc])
                        np.clip(ip, 0, M - 1, out=ip)
                        gflat[loc] = d * M + ip if gathered else ip
                        if compact:
                            nflat[loc] = n_d[ip]
                    for p in range(D):
                        if p == d:
                            continue
                        sel = owner == p
                        if not sel.any():
                            continue
                        pos = np.searchsorted(qstate[d][p], flat_b[sel])
                        np.clip(pos, 0, max(qstate[d][p].size - 1, 0), out=pos)
                        gflat[sel] = (p * M + queries[d][p][pos] if gathered
                                      else M + p * C + pos)
                        if compact:
                            nflat[sel] = qnorm[d][p][pos]
                    g = np.zeros(betas.shape, np.int64)
                    g[nz] = gflat
                    if compact:
                        n_b = np.ones(betas.shape)
                        n_b[nz] = nflat
                    cfz = np.where(nz, cf, 0)
                    if compact:
                        ratio = np.abs(cfz) * n_c[:, None] / n_b
                        badw += int((nz & (np.abs(ratio - W) > 1e-9 * W)).sum())
                    order = np.argsort(~nz, axis=1, kind="stable")
                    g_p = np.take_along_axis(np.where(nz, g, 0), order, axis=1)
                    c_p = np.take_along_axis(cfz, order, axis=1)
                    r = e - s

                    def pack(gg, cc):
                        if compact:
                            return np.where(
                                cc != 0,
                                np.sign(cc.real).astype(np.int32)
                                * (gg.astype(np.int32) + 1), 0)
                        return cc

                    if not compact:
                        g_main[:, s:e] = g_p[:r, :T0].T
                    v_main[:, s:e] = pack(g_p[:r, :T0], c_p[:r, :T0]).T
                    if S:
                        rd = np.nonzero(nnz[d][s:e] > T0)[0]
                        if rd.size:
                            tsl = slice(t_cursor, t_cursor + rd.size)
                            rows_t[tsl] = (s + rd).astype(np.int32)
                            if not compact:
                                i_tail[:, tsl] = g_p[rd, T0:Tmax].T
                            v_tail[:, tsl] = pack(g_p[rd, T0:Tmax],
                                                  c_p[rd, T0:Tmax]).T
                            t_cursor += rd.size
                    log_debug(f"plan pass2 shard {d}: rows {e}/{M}")
                # ship this shard's tables to its device NOW so the host
                # staging above is freed before the next shard packs
                if compact:
                    idx_shards.append(self._put_shard(v_main, d))  # sign tags
                else:
                    idx_shards.append(self._put_shard(g_main, d))
                    cf_shards.append(self._put_shard(
                        K.pair_from_complex(v_main) if self.pair else v_main, d))
                if S:
                    trow_shards.append(self._put_shard(rows_t, d))
                    if compact:
                        tidx_shards.append(self._put_shard(v_tail, d))
                    else:
                        tidx_shards.append(self._put_shard(i_tail, d))
                        tcf_shards.append(self._put_shard(
                            K.pair_from_complex(v_tail) if self.pair else v_tail,
                            d))
                if compact:
                    n_all_d = np.ones(M + D * C if D > 1 else M)
                    n_all_d[:M] = n_d
                    for p in range(D):
                        if p != d and qnorm[d][p].size:
                            n_all_d[M + p * C: M + p * C + qnorm[d][p].size] = \
                                qnorm[d][p]
                    n_all_shards.append(n_all_d)
            if compact and self._multi:
                # badw is accumulated over THIS process's addressable shards
                # only; agree on the total before raising so a non-qualifying
                # operator fails loudly on every rank instead of hanging the
                # others in the next collective
                from jax.experimental import multihost_utils
                badw = int(np.sum(multihost_utils.process_allgather(
                    np.int64(badw))))
            if badw:
                raise RuntimeError(
                    f"{badw} matrix elements violate the ±W·n(j)/n(i) form "
                    f"(W={W}); the operator does not qualify for compact mode "
                    "— use mode='ell'"
                )

            if compact:
                self._c_idx = self._assemble_sharded(idx_shards)   # [D, T0, M]
                self._c_tail = None
                if S:
                    self._c_tail = (self._assemble_sharded(trow_shards),
                                    self._assemble_sharded(tidx_shards))
                self._finish_compact_aux(self._assemble_sharded(n_all_shards))
                # per-shard host copies kept only until _save_structure runs
                self._c_n_all_shards = n_all_shards
            else:
                self._ell_idx = self._assemble_sharded(idx_shards)
                self._ell_coeff = self._assemble_sharded(cf_shards)
                self._ell_tail = None
                if S:
                    self._ell_tail = (self._assemble_sharded(trow_shards),
                                      self._assemble_sharded(tidx_shards),
                                      self._assemble_sharded(tcf_shards))
                live = max((int(nnz[d].sum()) for d in my_shards), default=0)
                if multi:
                    live = int(np.max(mhu.process_allgather(np.int64(live))))
                self._plan_counts = self._ell_plan_counts(live)
            # the shards' uploads are not waited for
            obs_memory.sample_watermark("plan/pack")
        _mem_h.release()           # stream staging gone; tables resident
        # the build's close (this runs under ``engine_init/build_plan``):
        # the tables resident and waited for
        obs_memory.sample_watermark("plan_upload/distributed",
                                    wait_for=self.structure_arrays())

    def _ell_plan_counts(self, live_entries: int) -> dict:
        """What one apply of the ELL plan moves on each chip, for the build
        span and the ``engine_init`` event (every shard has the same padded
        shapes, so each count is the fullest chip's): ``shard_rows`` (M),
        ``row_gather_slots`` (the row gathers from ``x`` and the receive
        buffer: M rows x the main table's width, plus the tail's rows x its
        width), ``live_entries`` (the fullest shard's stored entries),
        ``send_values`` and ``pack_values`` (:meth:`_exchange_counts`),
        ``row_bytes`` (one gathered row in the chip's layout: 16, or 32 for
        a pair-form vector) and ``value_bytes`` (8, or 16 for a complex
        value)."""
        M = self.shard_size
        slots = M * self._ell_T0
        if self._ell_tail is not None:
            width, rows = self._ell_tail[1].shape[1:]
            slots += width * rows
        return {"shard_rows": int(M), "row_gather_slots": int(slots),
                "live_entries": int(live_entries),
                **self._exchange_counts(),
                "row_bytes": gather_row_bytes(3 if self.real else 6),
                "value_bytes": 8 if self.real else 16}

    def _exchange_counts(self) -> dict:
        """A chip's exchange in one ELL / compact apply: ``send_values``,
        the rows the exchange puts in the row-gather table beside the
        chip's own (D x C packed, (D - 1) x M gathered), so that
        ``shard_rows + send_values`` is that table's length; and
        ``pack_values``, the values the pack gathers into the send buffer
        (D x C packed, 0 gathered).  Both 0 on one device."""
        D, M, C = self.n_devices, self.shard_size, self.query_capacity
        if D == 1:
            return {"send_values": 0, "pack_values": 0}
        if self.exchange_form == "gathered":
            return {"send_values": int((D - 1) * M), "pack_values": 0}
        return {"send_values": int(D * C), "pack_values": int(D * C)}

    def _finish_compact_aux(self, n_all_dev) -> None:
        """Derived compact-mode device arrays (recomputed on cache restore).

        ``n_all_dev`` is the assembled ``[D, M + D·C]`` device array;
        ``inv_n`` comes from the engine's own sharded norms (pads are 1.0),
        so no global host norm array is ever needed."""
        D = self.n_devices
        self._c_inv_n = jax.jit(jnp.reciprocal)(self._norms)   # [D, M]
        from ..ops.split_gather import split_parts
        self._c_use_sg = split_gather_enabled()
        if self._c_use_sg:
            self._c_n_parts = jax.device_put(
                jax.jit(split_parts)(n_all_dev),
                shard_spec(self.mesh, 3))                    # [D, M+DC, 3]
            self._c_norms = jax.device_put(jnp.zeros((D, 0)),
                                           shard_spec(self.mesh, 2))
        else:
            self._c_n_parts = jax.device_put(
                jnp.zeros((D, 0, 3), jnp.float32), shard_spec(self.mesh, 3))
            self._c_norms = jax.device_put(n_all_dev,
                                           shard_spec(self.mesh, 2))

    # -- plan checkpoint (ell/compact) ----------------------------------

    def _resolve_structure_cache(self, path: Optional[str]) -> Optional[str]:
        """Explicit caller path wins; otherwise the content-addressed
        artifact-cache default (None when the layer is off).  The
        fingerprint is identical on every rank, so the default path is
        consistent across a multi-controller run."""
        if path is not None:
            return path
        from ..utils.artifacts import default_structure_cache
        return default_structure_cache(self._structure_fingerprint())

    def _structure_sidecar(self, path: str) -> str:
        """Distinct from LocalEngine's sidecar (and per mesh size) so local
        and distributed checkpoints for the same basis don't thrash."""
        return path + _sidecar_name(self.n_devices, "structure")

    def _emit_plan_reshard(self, cache_path: Optional[str],
                           rebuild_s: float) -> None:
        """Make the topology-driven plan-cache miss OBSERVABLE.

        Plan sidecars are per-D by fingerprint AND filename
        (``.dist{D}.…``) — bit-correct on a D→D′ resume by construction
        (the engine rebuilds from structure rather than misreading a
        stale ``*.dist{D}.stream.h5``), but previously indistinguishable
        from a cold start.  When this build's cache MISSED and a sidecar
        for the same base path at a DIFFERENT device count sits on disk,
        the miss was a topology change: emit one ``plan_reshard`` event
        carrying the old topologies and the rebuild wall, the
        ``resume_rebuild_plan_s`` the elastic gate trend-tracks.  Only
        explicit cache paths are inspectable (the default artifact cache
        is content-addressed per fingerprint — no sibling to find)."""
        if not cache_path:
            return
        import glob
        import os

        seen = set()
        for cand in glob.glob(glob.escape(cache_path) + ".dist*"):
            m = _SIDECAR_RE.search(os.path.basename(cand))
            if m:
                seen.add(int(m.group(1)))
        seen.discard(self.n_devices)
        if not seen:
            return
        emit("plan_reshard", engine="distributed", mode=self.mode,
             d_from=sorted(int(d) for d in seen),
             d_to=int(self.n_devices),
             rebuild_s=round(float(rebuild_s), 6))

    def _structure_fingerprint(self) -> str:
        if getattr(self, "_fp_cache", None) is not None:
            return self._fp_cache
        import hashlib

        from .engine import hash_basis_operator

        h = hashlib.sha256()
        if self._shards_path is not None:
            # shard-native: the global representative array never exists;
            # the shard manifest's own fingerprint identifies the
            # enumerated content exactly (sector + group + shard count)
            from ..enumeration.sharded import shard_manifest
            man = shard_manifest(self._shards_path)
            hash_basis_operator(h, self.operator, include_arrays=False)
            h.update(str(man["fingerprint"]).encode())
        else:
            hash_basis_operator(h, self.operator)
        h.update(f"dist|{self.mode}|{self.pair}|{self.real}"
                 f"|{self.n_devices}|{self.shard_size}|v2".encode())
        if self.mode == "ell":
            # the columns index the table of the exchange form the plan
            # chose, which the sidecar records: one written before it did
            # misses and is rebuilt under the rule
            h.update(b"|exchange-form")
        if self.mode in ("streamed", "hybrid"):
            # the plan's dest/exchange layout bakes in the row-chunk size
            # and the per-peer capacity; a knob change must miss, not
            # restore a plan whose scatter targets no longer fit
            # v2: sidecars carry per-(chunk, shard) CRCs
            # v3: chunks are codec-encoded (ops/plan_codec.py) — the tier
            # AND the codec format version are part of the identity, so a
            # knob change or a format bump misses and rebuilds (older v2
            # files simply miss — no mixed-format reads)
            from ..ops.plan_codec import PLAN_CODEC_VERSION
            h.update(f"|B{self.batch_size}|cap{self._capacity}"
                     f"|p{self._lk_probes}|c{self._compress}"
                     f"|codec{PLAN_CODEC_VERSION}|v3".encode())
        if self.mode == "hybrid":
            # v4: the TERM MASK enters the content hash (DESIGN.md §28) —
            # a changed hybrid_split must MISS cleanly, never misread a
            # partial-term plan encoded for a different split.  Pinned
            # splits hash their explicit policy string; the "auto" split
            # is a deterministic function of (structure, calibration
            # rates), so the rates themselves stand in for the mask —
            # re-calibrating re-keys the plan.  The effective codec tier
            # rides along (hybrid maps compress "off" to the compacted
            # lossless encoding).  v3-era streamed sidecars carry a
            # different mode string entirely, so they miss-and-rebuild.
            h.update(self._hybrid_token().encode())
            h.update(f"|tier{self._codec_tier}|v4".encode())
        self._fp_cache = h.hexdigest()
        return self._fp_cache

    def _shard_keys(self, d: int):
        """Per-shard dataset names in a v3 (per-shard) structure sidecar."""
        if self.mode == "ell":
            return ("qin", "idx", "coeff", "tail_rows", "tail_idx",
                    "tail_coeff"), f"_{d}"
        return ("qin", "idx", "n_all", "tail_rows", "tail_idx"), f"_{d}"

    def _try_load_structure(self, path: Optional[str]) -> bool:
        """Restore the routing plan from a structure sidecar.

        v3 (current) sidecars hold PER-SHARD datasets (``qin_3``, …): each
        rank of a multi-controller run reads only its addressable shards —
        from its own ``.r<rank>`` sidecar or from any rank's file found
        next to it — and shard-native engines restore without a global
        basis.  v2 sidecars (one global array per table) remain readable
        single-process so plans staged by earlier rounds stay warm.
        """
        if not path:
            return False
        import glob
        import os

        import h5py

        sidecar = self._structure_sidecar(path)
        candidates = [c for c in [sidecar] + sorted(glob.glob(sidecar + ".r*"))
                      if os.path.exists(c)]
        if not candidates:
            return False
        fp = self._structure_fingerprint()
        D = self.n_devices
        my_shards = [d for d in range(D) if self._shard_addressable(d)]

        def put_rows(rows):                   # [D, ...] from per-shard rows
            return self._assemble_sharded(rows)

        # -- v3: collect each of my shards' datasets from the candidates --
        names, _ = self._shard_keys(0)
        rows = {k: [None] * D for k in names}
        scalars = {}
        found_shards = set()
        for cand in candidates:
            try:
                with h5py.File(cand, "r") as f:
                    if "engine_structure" not in f:
                        continue
                    g = f["engine_structure"]
                    if str(g.attrs.get("fingerprint", "")) != fp:
                        continue
                    if "qin" in g:            # legacy whole-array layout
                        if jax.process_count() == 1:
                            return self._load_structure_v2(cand)
                        continue   # keep scanning per-rank v3 candidates
                    for k in ("T0", "C", "W", "form"):
                        if k in g.attrs:
                            scalars[k] = g.attrs[k]
                    for d in my_shards:
                        if f"idx_{d}" not in g:
                            continue
                        found_shards.add(d)
                        for k in names:
                            if f"{k}_{d}" in g:
                                rows[k][d] = g[f"{k}_{d}"][...]
            except OSError as e:
                from ..utils.artifacts import note_artifact_corrupt
                note_artifact_corrupt(cand, "structure", e)
                continue
        need = {"T0", "C"} | ({"W"} if self.mode == "compact" else {"form"})
        if set(my_shards) - found_shards or need - set(scalars):
            return False
        # compact mode always packs
        self.exchange_form = str(scalars.get("form", "packed"))
        self._ell_T0 = int(scalars["T0"])
        self.query_capacity = int(scalars["C"])
        self._qin = (put_rows(rows["qin"])
                     if self.exchange_form == "packed" else None)
        has_tail = any(r is not None for r in rows["tail_rows"])
        if self.mode == "ell":
            self._ell_idx = put_rows(rows["idx"])
            self._ell_coeff = put_rows(rows["coeff"])
            self._ell_tail = None
            if has_tail:
                self._ell_tail = (put_rows(rows["tail_rows"]),
                                  put_rows(rows["tail_idx"]),
                                  put_rows(rows["tail_coeff"]))
        else:
            self._c_W = float(scalars["W"])
            self._c_idx = put_rows(rows["idx"])
            self._c_tail = None
            if has_tail:
                self._c_tail = (put_rows(rows["tail_rows"]),
                                put_rows(rows["tail_idx"]))
            self._finish_compact_aux(put_rows(rows["n_all"]))
        log_debug(f"distributed plan restored from {sidecar} (per-shard)")
        return True

    def _load_structure_v2(self, sidecar: str) -> bool:
        """Restore a legacy whole-array sidecar (single-process only)."""
        if jax.process_count() > 1:
            return False
        from ..io.hdf5 import load_engine_structure

        data = load_engine_structure(sidecar, self._structure_fingerprint())
        if data is None:
            return False
        sh3 = shard_spec(self.mesh, 3)
        self.exchange_form = "packed"
        self._ell_T0 = int(data["T0"])
        self.query_capacity = int(data["C"])
        self._qin = jax.device_put(jnp.asarray(data["qin"]), sh3)

        def put(a):
            return jax.device_put(jnp.asarray(a),
                                  shard_spec(self.mesh, np.ndim(a)))

        if self.mode == "ell":
            self._ell_idx = put(data["idx"])
            self._ell_coeff = put(data["coeff"])
            self._ell_tail = None
            if "tail_rows" in data:
                self._ell_tail = (put(data["tail_rows"]),
                                  put(data["tail_idx"]),
                                  put(data["tail_coeff"]))
        else:
            self._c_W = float(data["W"])
            self._c_idx = put(data["idx"])
            self._c_tail = None
            if "tail_rows" in data:
                self._c_tail = (put(data["tail_rows"]),
                                put(data["tail_idx"]))
            self._finish_compact_aux(put(data["n_all"]))
        log_debug(f"distributed plan restored from {sidecar} (v2)")
        return True

    def _shard_piece(self, arr, d: int) -> Optional[np.ndarray]:
        """Host copy of shard ``d``'s row of an assembled [D, ...] array
        (None when another process holds it)."""
        if not isinstance(arr, jax.Array):
            return np.asarray(arr)[d]
        for piece in arr.addressable_shards:
            # a 1-device mesh yields index slice(None) — start None means 0
            if (piece.index[0].start or 0) == d:
                return np.asarray(piece.data)[0]
        return None

    def _save_structure(self, path: Optional[str], soft: bool = False) -> None:
        """Write the per-shard (v3) structure sidecar.

        Each rank writes its OWN file (``.r<rank>`` suffix in
        multi-controller runs) holding only its addressable shards'
        datasets — no rank ever materializes a global table, so the cache
        works for multi-process and shard-native engines alike.  ``soft``
        marks DEFAULT-path (artifact cache) saves: size-capped by
        ``artifact_max_gb`` and degrading to a debug log on I/O errors.
        """
        if not path:
            return
        from ..io.hdf5 import save_engine_structure

        D = self.n_devices
        payload = {"T0": self._ell_T0, "C": self.query_capacity,
                   "form": self.exchange_form}
        if self.mode == "compact":
            payload["W"] = self._c_W
        for d in range(D):
            if not self._shard_addressable(d):
                continue
            if self._qin is not None:
                payload[f"qin_{d}"] = self._shard_piece(self._qin, d)
            if self.mode == "ell":
                payload[f"idx_{d}"] = self._shard_piece(self._ell_idx, d)
                payload[f"coeff_{d}"] = self._shard_piece(self._ell_coeff, d)
                if self._ell_tail is not None:
                    rows, idx_t, cf_t = self._ell_tail
                    payload[f"tail_rows_{d}"] = self._shard_piece(rows, d)
                    payload[f"tail_idx_{d}"] = self._shard_piece(idx_t, d)
                    payload[f"tail_coeff_{d}"] = self._shard_piece(cf_t, d)
            else:
                payload[f"idx_{d}"] = self._shard_piece(self._c_idx, d)
                # set by the fresh build _save_structure always follows
                payload[f"n_all_{d}"] = self._c_n_all_shards[d]
                if self._c_tail is not None:
                    rows, tag_t = self._c_tail
                    payload[f"tail_rows_{d}"] = self._shard_piece(rows, d)
                    payload[f"tail_idx_{d}"] = self._shard_piece(tag_t, d)
        sidecar = self._structure_sidecar(path)
        if jax.process_count() > 1:
            sidecar = f"{sidecar}.r{jax.process_index()}"
        if soft:
            from ..utils.artifacts import soft_save_structure
            if not soft_save_structure(sidecar,
                                       self._structure_fingerprint(),
                                       self.mode, payload):
                return
        else:
            save_engine_structure(sidecar, self._structure_fingerprint(),
                                  self.mode, payload)
        log_debug(f"distributed plan checkpointed to {sidecar}")

    # ------------------------------------------------------------------
    # Streamed mode: fused-class structure resolved ONCE into a host-RAM
    # plan (optional artifact-cache disk tier), streamed H2D per apply
    # ------------------------------------------------------------------
    #
    # The fused apply pays N·T·(c_scan·|G| + c_route) EVERY time: the
    # coset-walk orbit scan (ops/kernels.state_info) plus the hash/bucket
    # routing are recomputed for every generated amplitude on every apply,
    # although both are pure functions of the (operator, basis, chunking)
    # — chain_36_symm could not finish ONE fused apply in 69 minutes, and
    # a Lanczos solve repeats that identical computation 300–1000×.  The
    # streamed plan stores, per (row chunk, shard):
    #
    #   dest  [B·T] i32       exchange slot (key·Cap + in-bucket rank;
    #                         D·Cap = dropped), from the SAME
    #                         _bucket_positions math as the fused apply
    #   coeff [B, T](,2)      conj-rescaled row coefficient (zero = dead)
    #   ridx  [D·Cap] i32     receive-side basis index (pre-masked)
    #   rok   [D·Cap] bool    receive-side validity mask
    #
    # so a steady-state apply is: gather the chunk's x rows, multiply by
    # coeff, scatter to dest, ONE all_to_all of amplitudes only (the betas
    # no longer travel — the receive side already knows its layout), and a
    # segment_sum — a bandwidth-bound stream of precomputed structure, in
    # the spirit of GSPMD's static-program reuse (PAPERS.md).  The plan
    # spills to host RAM (memory-ledger tracked, device="host") and, when
    # the artifact layer is on, to a content-addressed sidecar that both
    # warm-restores later constructions and serves as the disk tier for
    # plans beyond ``stream_plan_ram_gb``.

    _STREAM_ARRAYS = ("dest", "coeff", "ridx", "rok")

    def _stream_sidecar(self, path: str) -> str:
        return path + _sidecar_name(self.n_devices, "stream")

    def _stream_nchunks(self) -> int:
        B = self.batch_size
        return (self.shard_size + B - 1) // B

    def _make_stream_build(self):
        """One fixed-shape program resolving a row chunk's full structure:
        kernels + orbit scan, bucket routing (shared `_bucket_positions` —
        bit-identical to the fused apply), one betas-only all_to_all, and
        the receive-side lookup.  Outputs the plan arrays plus the psum'd
        structural overflow/invalid counters."""
        D, M = self.n_devices, self.shard_size
        Cap = self._capacity
        lk_shift, lk_probes = self._lk_shift, self._lk_probes
        is_pair = self.pair
        mesh = self.mesh

        def shard_body(a_c, n_c, tables, lk_pair, lk_dir):
            a, nn = a_c[0], n_c[0]
            lkp, lkd = lk_pair[0], lk_dir[0]
            betas, gcoeff = K.gather_coefficients(tables, a, nn)
            valid_row = (a != SENTINEL_STATE)[:, None]
            if is_pair:
                nz = (gcoeff != 0).any(axis=-1) & valid_row
                cf = jnp.where(nz[..., None], K.conj_pair(gcoeff), 0)
            else:
                nz = (gcoeff != 0) & valid_row
                cf = jnp.where(nz, jnp.conj(gcoeff), 0)
            flat_b = betas.reshape(-1)
            live = nz.reshape(-1)
            owner = (hash64(flat_b) % jnp.uint64(D)).astype(jnp.int32) \
                if D > 1 else jnp.zeros(flat_b.shape, jnp.int32)
            key = jnp.where(live, owner, D)
            pos = _bucket_positions(key, D)
            in_cap = (pos < Cap) & (key < D)
            overflow = jnp.sum((pos >= Cap) & (key < D))
            dest = jnp.where(in_cap, key * Cap + pos,
                             D * Cap).astype(jnp.int32)
            send_b = jnp.full(D * Cap, SENTINEL_STATE).at[dest].set(
                flat_b, mode="drop")
            if D > 1:
                recv_b = jax.lax.all_to_all(
                    send_b.reshape(D, Cap), SHARD_AXIS, 0, 0, tiled=True
                ).reshape(-1)
            else:
                recv_b = send_b
            idx, found = state_index_bucketed(
                lkp, lkd, recv_b, shift=lk_shift, probes=lk_probes)
            live_r = recv_b != SENTINEL_STATE
            okc = found & live_r
            invalid = jnp.sum(live_r & ~found)
            ridx = jnp.where(okc, idx, 0).astype(jnp.int32)
            overflow = jax.lax.psum(overflow, SHARD_AXIS)
            invalid = jax.lax.psum(invalid, SHARD_AXIS)
            return (dest[None], cf[None], ridx[None], okc[None],
                    overflow[None], invalid[None])

        cf_ndim = 4 if is_pair else 3

        def build_fn(a_c, n_c, tables, lk_pair, lk_dir):
            f = jax.shard_map(
                shard_body, mesh=mesh,
                in_specs=(_pspec(2), _pspec(2), P(), _pspec(3), _pspec(2)),
                out_specs=(_pspec(2), _pspec(cf_ndim), _pspec(2), _pspec(2),
                           _pspec(1), _pspec(1)),
            )
            return f(a_c, n_c, tables, lk_pair, lk_dir)

        return jax.jit(build_fn)

    def _build_stream_plan(self, row_provider) -> None:
        """Resolve every row chunk's structure once (the cost of roughly
        ONE fused apply plus the plan D2H) into host-RAM per-chunk arrays.
        Double-buffered like the ell/compact plan stream: chunk ci+1's
        upload + device pass is in flight while chunk ci's plan is fetched
        and packed host-side."""
        D, M = self.n_devices, self.shard_size
        B = self.batch_size
        nchunks = self._stream_nchunks()
        my_shards = [d for d in range(D) if self._shard_addressable(d)]

        _mem_h = obs_memory.NULL_HANDLE
        if obs_enabled():
            cfb = 16 if (self.pair or not self.real) else 8
            stage = 2 * (B * self.num_terms * (4 + cfb)
                         + D * self._capacity * 5)
            _mem_h = obs_memory.track(
                f"plan/{obs_memory.next_instance('stream_build')}/staging",
                stage, kind="staging", chunks=int(nchunks))
        self._plan_stage_h = _mem_h

        build = self._stream_build_prog
        if build is None:
            build = self._stream_build_prog = self._make_stream_build()

        def launch(ci):
            a_rows = [None] * D
            n_rows = [None] * D
            for d in my_shards:
                a_rows[d], n_rows[d] = self._stream_chunk_rows(
                    row_provider, d, ci)
            a_dev = self._assemble_sharded(a_rows)
            n_dev = self._assemble_sharded(n_rows)
            return build(a_dev, n_dev, self.tables, self._lk_pair,
                         self._lk_dir)

        chunks = []
        overflow = invalid = 0
        plan_bytes = 0
        pending = launch(0) if nchunks else None
        for ci in range(nchunks):
            nxt = launch(ci + 1) if ci + 1 < nchunks else None
            dest, cf, ridx, rok, ov, iv = pending
            _t_fetch = time.perf_counter()
            per = {}
            for d in my_shards:
                pc = {"dest": self._shard_piece(dest, d),
                      "coeff": self._shard_piece(cf, d),
                      "ridx": self._shard_piece(ridx, d),
                      "rok": self._shard_piece(rok, d)}
                plan_bytes += sum(a.nbytes for a in pc.values())
                per[d] = pc
            histogram("double_buffer_stall_ms").observe(
                (time.perf_counter() - _t_fetch) * 1e3)
            counter("bytes_d2h", path="stream_plan_build").inc(sum(
                a.nbytes for pc in per.values() for a in pc.values()))
            # overflow/invalid are psum'd — identical on every shard
            if my_shards:
                overflow += int(self._shard_piece(ov, my_shards[0]))
                invalid += int(self._shard_piece(iv, my_shards[0]))
            chunks.append(per)
            log_debug(f"stream plan chunk {ci + 1}/{nchunks}")
            pending = nxt
        self._plan_chunks = chunks
        self._plan_disk = None
        # keep the SAME dict object across rebuilds — the __init__
        # weakref.finalize holds a reference to it for close-on-GC
        files = getattr(self, "_plan_files", None)
        if files is None:
            self._plan_files: dict = {}
        else:
            _close_plan_files(files)
        self._plan_nchunks_v = nchunks
        self.plan_bytes = plan_bytes
        self._stream_overflow = overflow
        self._stream_invalid = invalid
        _mem_h.release()
        # the loud structural halt, at BUILD time (fused defers it to the
        # first apply): every rank saw the same psum'd totals, so a raise
        # cannot strand peers in a collective
        self._validate_counters(overflow, invalid, "streamed")
        obs_memory.sample_watermark("plan_build/streamed")

    def _codec_ckind(self) -> str:
        return "real" if self.real else ("pair" if self.pair else "complex")

    def _codec_cshape(self) -> tuple:
        return (self.batch_size, self.num_terms) \
            + ((2,) if self.pair else ())

    def _codec_agree(self, use_dict: bool, nd: int, fill: int,
                     n_live: int):
        """Job-wide codec decisions for a multi-controller encode: the
        per-shard dictionaries, the trimmed exchange capacity, and the
        compacted entry count all enter a collective chunk program as
        uniformly-shaped operands, so every rank must agree.  Backends
        without multiprocess host computations degrade to raw
        uncompacted coefficients everywhere — the same deterministic
        answer on every rank.  The broad except deliberately mirrors
        ``agree_restored``'s (PR 5): allgather failures observed in
        practice are structural (the backend cannot run multiprocess
        host computations at all) and therefore identical on every
        rank; a genuinely one-sided transient would already have
        desynchronized the peers inside the collective itself."""
        try:
            from jax.experimental import multihost_utils as mhu
            g = np.atleast_2d(mhu.process_allgather(np.asarray(
                [int(bool(use_dict)), int(nd), int(fill), int(n_live)],
                np.int64)))
            return (bool(g[:, 0].min()), int(g[:, 1].max()),
                    int(g[:, 2].max()), int(g[:, 3].max()))
        except Exception as e:
            log_debug(f"codec agreement unavailable ({e!r}); raw "
                      "uncompacted coefficient encoding on all ranks")
            return (False, 0, int(self._capacity),
                    self.batch_size * self.num_terms)

    def _encode_stream_plan(self) -> None:
        """Encode the freshly built raw plan chunks in place
        (``ops/plan_codec.py``): dead-entry compaction + exchange-
        capacity trim + bitpacked dest/row/ridx/rok + dictionary or
        quantized coefficients per the ``stream_compress`` tier (tier
        "off" still bitpacks ``rok`` — the free lossless win).  From here
        on the host-RAM copy, the sidecar, and the per-apply H2D stream
        all carry the encoded bytes; ``plan_bytes_raw`` keeps the
        uncompressed total for ``compress_ratio``."""
        from ..ops import plan_codec as PC

        D = self.n_devices
        self._codec = PC.PlanCodec.build(
            self._codec_tier, self._plan_chunks,
            n_dest=self.batch_size * self.num_terms,
            cap_build=self._capacity, n_devices=D,
            shard_size=self.shard_size,
            cshape=self._codec_cshape(), ckind=self._codec_ckind(),
            agree=self._codec_agree if self._multi else None,
            term_mask=self._hybrid_mask)
        enc_bytes = 0
        nrec = 0
        spec = self._codec.spec
        keep_drift_ref = (spec["tier"] in ("f32", "bf16")
                          and spec["coeff"] != "dict"
                          and spec["ckind"] == "real")
        for ci, per in enumerate(self._plan_chunks):
            for d in list(per):
                if keep_drift_ref and ci == self._DRIFT_CHUNK:
                    # raw-fallback quantized tier: the exact f64
                    # coefficients are about to be quantized away — keep
                    # the probe chunk's compact form so the drift probe
                    # (obs/health.py compress_rel_err) still has its
                    # lossless reference (dict-coded plans keep the
                    # originals in the dictionary instead)
                    cp = self._codec.compact_raw(per[d])
                    ref = getattr(self, "_drift_raw_ref", None)
                    if ref is None:
                        ref = self._drift_raw_ref = {}
                    ref[d] = (cp["row"], cp["coeff"].real.astype(
                        np.float64), cp["dest"])
                per[d] = self._codec.encode_chunk(per[d], d)
                enc_bytes += PC.PlanCodec.encoded_bytes(per[d])
                nrec += 1
        self.plan_bytes_raw = self._codec.raw_chunk_bytes() * nrec
        self.plan_bytes = enc_bytes
        log_debug(
            f"stream plan encoded: tier={self._codec_tier} "
            f"coeff={self._codec.spec['coeff']} "
            f"{self.plan_bytes_raw / 1e6:.1f} -> {enc_bytes / 1e6:.1f} MB "
            f"({self.plan_bytes_raw / max(enc_bytes, 1):.2f}x)")

    # -- hybrid mode: per-term recompute-vs-stream split (DESIGN.md §28) ---

    def _init_hybrid_policy(self, hybrid_split) -> None:
        """Resolve and validate the split POLICY before the fingerprint is
        taken (constructor argument > ``config.hybrid`` / ``DMT_HYBRID``).
        The "auto" policy additionally pins the calibration it will price
        with — the rates enter the fingerprint, so a re-calibrated rig
        re-keys (and re-splits) the plan instead of restoring a plan built
        for different economics."""
        cfg = get_config()
        s = str(hybrid_split if hybrid_split is not None
                else cfg.hybrid).strip().lower() or "auto"
        if s not in ("auto", "all-stream", "all-recompute") \
                and not s.startswith("stream:"):
            raise ValueError(
                f"bad hybrid split {s!r}: set tune=static "
                "(DMT_TUNE=static) to let the autotuner pick the split, "
                "or pick auto | all-stream | all-recompute | "
                "stream:<term,term,...> (DMT_HYBRID / config.hybrid)")
        self._hybrid_split = s
        self._static_hybrid_mask()      # explicit lists validate eagerly
        self._hybrid_cal = None
        if s == "auto":
            # the autotuner's rates win when tuning is on: under
            # tune=live that is the refined posterior, so a drift-driven
            # re-tune RE-KEYS the split through the same rate-bearing
            # fingerprint token a re-calibration would (DESIGN.md §28/§30)
            cal = getattr(self, "_tune_cal", None)
            if cal is None:
                from ..obs import roofline as _roofline
                cal = _roofline.resolve_calibration()
            self._hybrid_cal = cal

    def _hybrid_token(self) -> str:
        """The fingerprint's split token: the policy string, plus — for
        "auto" — the calibration rates the split was priced with (the
        mask is a deterministic function of both, so together with the
        structure hash they pin it exactly)."""
        tok = self._hybrid_split
        if self._hybrid_split == "auto" and self._hybrid_cal is not None:
            from ..obs import roofline as _roofline
            tok += "|" + ",".join(
                f"{k}={float(self._hybrid_cal.get(k) or 0):.6g}"
                for k in _roofline.RATE_FIELDS)
        return f"|hyb[{tok}]"

    def _static_hybrid_mask(self) -> Optional[np.ndarray]:
        """The [T] stream mask of a policy that needs no census
        (all-stream / all-recompute / an explicit ``stream:`` list);
        None for "auto" (resolved from the build census instead)."""
        T = self.num_terms
        s = self._hybrid_split
        if s == "all-stream":
            return np.ones(T, bool)
        if s == "all-recompute":
            return np.zeros(T, bool)
        if s.startswith("stream:"):
            mask = np.zeros(T, bool)
            idx = [int(t) for t in s[len("stream:"):].split(",")
                   if t.strip()]
            bad = [t for t in idx if not 0 <= t < T]
            if bad:
                raise ValueError(
                    f"hybrid stream terms {bad} outside [0, {T})")
            mask[idx] = True
            return mask
        return None

    def _hybrid_group_order(self) -> int:
        """|G| for the recompute pricing: the per-entry orbit-scan cost
        scales with the symmetry group order (1 when the basis needs no
        projection — the cheap-orbit regime where recompute shines)."""
        if self.tables.group is None:
            return 1
        grp = getattr(self.operator.basis, "group", None)
        return max(len(grp), 1) if grp is not None else 1

    def _hybrid_entry_bytes(self) -> float:
        """Modeled encoded bytes ONE live streamed entry puts on the
        per-apply H2D stream: the bitpacked (dest, row) index pair plus
        the tier's coefficient bytes (u16 dictionary code expected for
        the lossless/off tiers on repeating-coefficient sectors — the
        optimistic end, which biases auto toward streaming, the
        conservative direction for wall-clock).  The shared-per-chunk
        ridx/rok layout is excluded: it streams regardless of the
        split."""
        from ..ops import plan_codec as PC

        w = PC.bits_for(self.n_devices * self._capacity) \
            + PC.bits_for(max(self.batch_size - 1, 1))
        ncomp = 2 if (self.pair or not self.real) else 1
        coeff_b = {"lossless": 2.0, "f32": 4.0 * ncomp,
                   "bf16": 2.0 * ncomp}.get(self._codec_tier, 2.0)
        return w / 8.0 + coeff_b

    def _hybrid_census(self):
        """Global per-term live-entry counts of the freshly built raw
        plan (the auto split's input): ``(counts [T], rows)`` summed over
        chunks, shards, and ranks.  Multi-controller runs allgather the
        census so every rank prices — and therefore splits — identically;
        backends without multiprocess host computations degrade to the
        deterministic all-stream split everywhere (same contract as
        ``_codec_agree``)."""
        from ..ops.plan_codec import _canonical

        T = self.num_terms
        ckind = self._codec_ckind()
        lim = self.n_devices * self._capacity
        counts = np.zeros(T, np.int64)
        rows = 0
        for per in self._plan_chunks:
            for pc in per.values():
                flat = _canonical(pc["coeff"], ckind)
                dest = np.asarray(pc["dest"], np.int64).reshape(-1)
                live = (flat != 0) & (dest < lim)
                counts += live.reshape(-1, T).sum(axis=0)
                rows += self.batch_size
        if not self._multi:
            return counts, rows
        try:
            from jax.experimental import multihost_utils as mhu
            payload = np.concatenate([counts, [rows]]).astype(np.int64)
            tot = np.sum(np.atleast_2d(mhu.process_allgather(payload)),
                         axis=0)
            return tot[:T], int(tot[T])
        except Exception as e:
            log_debug(f"hybrid census agreement unavailable ({e!r}); "
                      "falling back to the all-stream split on all ranks")
            return None, 0

    def _resolve_hybrid_mask(self) -> np.ndarray:
        """The resolved [T] stream mask for this build: the pinned policy
        mask, or — for "auto" — the per-term priced split
        (:func:`~..obs.roofline.choose_hybrid_split`: recompute flops at
        the calibrated flop rate vs encoded plan bytes + decode gathers
        at the calibrated H2D/gather rates)."""
        mask = self._static_hybrid_mask()
        if mask is None:
            from ..obs import roofline as _roofline
            counts, rows = self._hybrid_census()
            if counts is None:       # no cross-rank census: deterministic
                mask = np.ones(self.num_terms, bool)
            else:
                mask = _roofline.choose_hybrid_split(
                    counts, rows, self._hybrid_group_order(),
                    self._hybrid_cal, self._hybrid_entry_bytes(),
                    cplx=self.pair or not self.real)
        log_debug(f"hybrid split ({self._hybrid_split}): "
                  f"{int(mask.sum())}/{mask.size} terms streamed, "
                  f"{int((~mask).sum())} recomputed on device")
        return np.asarray(mask, bool)

    def _setup_hybrid_recompute(self) -> None:
        """Device operands of the recompute side, built once per engine:
        the recompute-term subset of the operator tables (row-sliced — the
        per-term kernels are independent across terms, so the sliced scan
        reproduces the build's values bit-for-bit) and the engine's
        basis/norm rows padded to the plan's chunk grid (the chunk
        program dynamic-slices both exactly as it slices ``x``)."""
        mask = self._hybrid_mask
        sel = np.nonzero(~mask)[0]
        self._hyb_n_recompute = int(sel.size)
        self.hybrid_stream_fraction = float(mask.mean()) if mask.size \
            else 1.0
        if sel.size:
            sel_d = jnp.asarray(sel, jnp.int32)
            off = self.tables.off
            # trim trailing all-zero inner-kernel columns: the full table
            # pads every term group to the global K_max, but the
            # recompute subset is typically the CHEAP terms (the auto
            # split's whole point), whose groups hold fewer kernels.  A
            # zero-v column contributes exactly 0 to the (v·sign·ok) sum,
            # so the trim is bit-exact while cutting the per-(row, term)
            # kernel work to the subset's true K.
            kv = self.operator.off_diag_table.v[sel]
            knz = np.nonzero((kv != 0).any(axis=0))[0]
            kmax = int(knz.max()) + 1 if knz.size else 1
            sub = K.OffDiagKernelTables(
                x=off.x[sel_d], v=off.v[sel_d, :kmax],
                s=off.s[sel_d, :kmax], m=off.m[sel_d, :kmax],
                r=off.r[sel_d, :kmax])
            self._hyb_tables = K.OperatorTables(
                diag=self.tables.diag, off=sub, group=self.tables.group)
        else:
            self._hyb_tables = self.tables      # unused (all-stream)
        M, Mp = self.shard_size, self._plan_nchunks_v * self.batch_size
        if Mp > M:
            sh2 = shard_spec(self.mesh, 2)
            self._hyb_alphas = jax.jit(
                lambda a: jnp.pad(a, ((0, 0), (0, Mp - M)),
                                  constant_values=SENTINEL_STATE),
                out_shardings=sh2)(self._alphas)
            self._hyb_norms = jax.jit(
                lambda a: jnp.pad(a, ((0, 0), (0, Mp - M)),
                                  constant_values=1.0),
                out_shardings=sh2)(self._norms)
        else:
            self._hyb_alphas, self._hyb_norms = self._alphas, self._norms

    def _upload_codec_tables(self) -> None:
        """Stage the per-shard coefficient dictionaries on the mesh — ONCE
        per engine, device-resident for its life (they are tiny; only the
        coded chunk stream re-travels per apply).  Raw/off codecs get an
        empty [D, 0] placeholder so the chunk program signature is
        uniform."""
        D = self.n_devices
        rows = [None] * D
        n = 0
        for d in range(D):
            if self._shard_addressable(d):
                rows[d] = self._codec.dict_device_row(d)
                n += rows[d].nbytes
        self._cdict_dev = self._assemble_sharded(rows)
        if n:
            counter("bytes_h2d", path="plan_codec_dict").inc(n)

    def _register_stream_plan(self) -> None:
        """Host-RAM plan bytes into the memory ledger (device="host") for
        the engine's lifetime + one ``plan_stream`` event the capacity
        planner and obs reports read."""
        if not obs_enabled():
            return
        import weakref

        tier = "disk" if self._plan_chunks is None else "ram"
        h = obs_memory.track(
            f"plan/{obs_memory.next_instance('stream_plan')}/host",
            int(self.plan_bytes) if tier == "ram" else 0,
            device="host", kind="stream_plan", tier=tier,
            chunks=int(self._plan_nchunks_v))
        weakref.finalize(self, h.release)
        from ..obs import gauge
        gauge("stream_plan_bytes").set(int(self.plan_bytes))
        raw = int(getattr(self, "plan_bytes_raw", 0) or self.plan_bytes)
        hyb_ctx = {}
        if self.mode == "hybrid":
            # the split's identity card, read by tools/capacity.py
            # snapshots: which fraction of the terms travel in the
            # stream, under which policy
            hyb_ctx = {"hybrid_split": str(self._hybrid_split),
                       "stream_terms": int(self._hybrid_mask.sum()),
                       "num_terms": int(self.num_terms),
                       "stream_term_fraction":
                       round(float(self.hybrid_stream_fraction), 4)}
        emit("plan_stream", engine="distributed", tier=tier,
             mode=self.mode,
             plan_bytes=int(self.plan_bytes),
             plan_bytes_raw=raw,
             # the EFFECTIVE codec tier — for hybrid plans compress "off"
             # maps to the compacted lossless encoding, and the reported
             # bytes are that encoding's, so the event must say so
             compress=str(getattr(self, "_codec_tier",
                                  getattr(self, "_compress", "off"))),
             compress_ratio=round(raw / max(int(self.plan_bytes), 1), 4),
             chunks=int(self._plan_nchunks_v),
             capacity=int(self._capacity), batch=int(self.batch_size),
             overflow=int(self._stream_overflow),
             invalid=int(self._stream_invalid),
             host_rss_bytes=obs_memory.host_rss_bytes(), **hyb_ctx)

    def _save_stream_plan(self, path: Optional[str], soft: bool = False
                          ) -> None:
        """Persist the plan to the artifact-cache sidecar (per-rank file in
        multi-controller runs, like the v3 structure sidecars) and — when
        the plan exceeds ``stream_plan_ram_gb`` — demote the RAM copy to
        the disk tier, reading chunks back from the sidecar per apply."""
        cfg = get_config()
        saved = None
        if path:
            payload = {"Cap": int(self._capacity), "B": int(self.batch_size),
                       "nchunks": int(self._plan_nchunks_v),
                       "overflow": int(self._stream_overflow),
                       "invalid": int(self._stream_invalid),
                       "codec_spec": self._codec.spec_json()}
            if self._codec.spec["coeff"] == "dict":
                for d in self._codec.dicts:
                    if self._shard_addressable(d):
                        payload[f"cdict_{d}"] = self._codec.dict_store(d)
            for ci, per in enumerate(self._plan_chunks):
                for d, pc in per.items():
                    # per-(chunk, shard) checksum: the disk tier verifies
                    # it on every read, the RAM restore once — a torn
                    # sidecar chunk degrades instead of corrupting applies
                    payload[f"crc_{d}_{ci}"] = _plan_chunk_crc(pc)
                    for k in self._STREAM_ARRAYS:
                        payload[f"{k}_{d}_{ci}"] = pc[k]
            sidecar = self._stream_sidecar(path)
            if jax.process_count() > 1:
                sidecar = f"{sidecar}.r{jax.process_index()}"
            if soft:
                from ..utils.artifacts import soft_save_structure
                if soft_save_structure(sidecar,
                                       self._structure_fingerprint(),
                                       self.mode, payload):
                    saved = sidecar
            else:
                from ..io.hdf5 import save_engine_structure
                save_engine_structure(sidecar,
                                      self._structure_fingerprint(),
                                      self.mode, payload)
                saved = sidecar
            if saved:
                log_debug(f"stream plan checkpointed to {saved}")
        if (self.plan_bytes > cfg.stream_plan_ram_gb * 1e9
                or self._tune_plan_tier == "disk"):
            if saved:
                D = self.n_devices
                self._plan_disk = {
                    d: saved for d in range(D) if self._shard_addressable(d)}
                self._plan_chunks = None
                log_debug("stream plan beyond stream_plan_ram_gb (or "
                          "tuned to the disk tier): host RAM copy "
                          "dropped, disk tier active")
            else:
                from ..utils.logging import log_warn
                log_warn(
                    f"stream plan ({self.plan_bytes / 1e9:.1f} GB) exceeds "
                    "stream_plan_ram_gb but no artifact-cache sidecar is "
                    "available as a disk tier; keeping it in host RAM "
                    "(set tune=static to let the autotuner pick a "
                    "feasible tier/codec, enable DMT_ARTIFACT_CACHE, or "
                    "raise DMT_STREAM_PLAN_RAM_GB)")

    def _try_load_stream_plan(self, path: Optional[str]) -> bool:
        """Restore the plan from a stream sidecar: each rank reads only its
        addressable shards' chunk datasets — from its own ``.r<rank>`` file
        or any rank's found next to it.  Plans beyond ``stream_plan_ram_gb``
        stay on disk and are read per chunk during applies."""
        if not path:
            return False
        import glob
        import os

        import h5py

        sidecar = self._stream_sidecar(path)
        candidates = [c for c in [sidecar]
                      + sorted(glob.glob(sidecar + ".r*"))
                      if os.path.exists(c)]
        if not candidates:
            return False
        fp = self._structure_fingerprint()
        D = self.n_devices
        my_shards = [d for d in range(D) if self._shard_addressable(d)]
        scalars = {}
        where: dict = {}            # shard -> candidate file holding it
        for cand in candidates:
            try:
                with h5py.File(cand, "r") as f:
                    if "engine_structure" not in f:
                        continue
                    g = f["engine_structure"]
                    if str(g.attrs.get("fingerprint", "")) != fp:
                        continue
                    for k in ("Cap", "B", "nchunks", "overflow", "invalid"):
                        if k in g.attrs:
                            scalars[k] = int(g.attrs[k])
                    if "codec_spec" in g.attrs:
                        scalars["codec_spec"] = str(g.attrs["codec_spec"])
                    for d in my_shards:
                        if d not in where and f"dest_{d}_0" in g:
                            where[d] = cand
            except OSError:
                continue
        need = {"Cap", "B", "nchunks", "overflow", "invalid", "codec_spec"}
        if set(my_shards) - set(where) or need - set(scalars):
            return False
        if scalars["Cap"] != self._capacity \
                or scalars["B"] != self.batch_size:
            return False      # fingerprinted, but belt-and-braces
        nchunks = scalars["nchunks"]
        if nchunks != self._stream_nchunks():
            return False
        from ..ops import plan_codec as PC
        try:
            codec = PC.PlanCodec.from_spec_json(scalars["codec_spec"])
        except (ValueError, KeyError):
            return False          # future codec format: miss and rebuild
        if (codec.spec["tier"] != self._codec_tier
                or codec.spec["n_dest"]
                != self.batch_size * self.num_terms
                or codec.spec["cap_build"] != self._capacity
                or codec.spec["D"] != self.n_devices
                or codec.spec["ckind"] != self._codec_ckind()):
            return False
        # a partial-term (hybrid) plan must NEVER be misread as a full
        # streamed plan (or vice versa): the spec's hybrid flag must match
        # the engine mode, and for the policy-pinned splits the stored
        # stream-term set must equal the policy's (the auto split is
        # pinned by the fingerprint's calibration token instead — the
        # census that produced it is deterministic per structure+rates)
        if bool(codec.spec.get("hybrid")) != (self.mode == "hybrid"):
            return False
        if self.mode == "hybrid":
            want = self._static_hybrid_mask()
            got = codec.term_mask()
            if got is None or got.size != self.num_terms:
                return False
            if want is not None and not np.array_equal(got, want):
                return False
        # group shards per candidate so each sidecar opens ONCE for the
        # sizing pass and once for the RAM load — a chain_32-class plan
        # has hundreds of (chunk, shard) datasets, and per-dataset reopen
        # cycles would dominate the warm restore
        by_file: dict = {}
        for d, cand in where.items():
            by_file.setdefault(cand, []).append(d)
        plan_bytes = 0
        for cand, ds_list in by_file.items():
            try:
                with h5py.File(cand, "r") as f:
                    g = f["engine_structure"]
                    for d in ds_list:
                        if codec.spec["coeff"] == "dict":
                            codec.set_dict(d, g[f"cdict_{d}"][...])
                        for ci in range(nchunks):
                            for k in self._STREAM_ARRAYS:
                                ds = g[f"{k}_{d}_{ci}"]
                                plan_bytes += ds.size * ds.dtype.itemsize
            except (OSError, KeyError) as e:
                # truncated mid-write / bit-rot: a restore-time miss (the
                # fresh build replaces it) that also feeds the
                # corrupt/quarantine tally
                from ..utils.artifacts import note_artifact_corrupt
                note_artifact_corrupt(cand, "stream_plan", e)
                return False
        self._codec = codec
        if self.mode == "hybrid":
            self._hybrid_mask = codec.term_mask()
        self._plan_nchunks_v = nchunks
        self.plan_bytes = plan_bytes
        self.plan_bytes_raw = codec.raw_chunk_bytes() \
            * nchunks * len(my_shards)
        self._stream_overflow = scalars["overflow"]
        self._stream_invalid = scalars["invalid"]
        self._plan_files = {}
        if (plan_bytes > get_config().stream_plan_ram_gb * 1e9
                or self._tune_plan_tier == "disk"):
            self._plan_chunks = None
            self._plan_disk = where
            log_debug(f"stream plan restored on the DISK tier "
                      f"({plan_bytes / 1e9:.1f} GB from {len(where)} "
                      "sidecar(s))")
        else:
            self._plan_disk = None
            chunks = [dict() for _ in range(nchunks)]
            for cand, ds_list in by_file.items():
                try:
                    with h5py.File(cand, "r") as f:
                        g = f["engine_structure"]
                        for d in ds_list:
                            for ci in range(nchunks):
                                pc = {k: g[f"{k}_{d}_{ci}"][...]
                                      for k in self._STREAM_ARRAYS}
                                crc = g.attrs.get(f"crc_{d}_{ci}")
                                if crc is not None \
                                        and _plan_chunk_crc(pc) != int(crc):
                                    raise ValueError(
                                        f"stream plan chunk {ci} shard {d} "
                                        "failed its checksum")
                                chunks[ci][d] = pc
                except (OSError, KeyError, ValueError) as e:
                    from ..utils.artifacts import note_artifact_corrupt
                    note_artifact_corrupt(cand, "stream_plan", e)
                    return False
            self._plan_chunks = chunks
            log_debug(f"stream plan restored from {candidates[0]}")
        self._validate_counters(self._stream_overflow,
                                self._stream_invalid, "streamed")
        return True

    def _stream_chunk_rows(self, row_provider, d: int, ci: int):
        """Row chunk ``ci`` of shard ``d`` padded to the plan's row-chunk
        size (SENTINEL rows / unit norms) — shared by the one-time plan
        build and the per-chunk corrupt-sidecar rebuild so both resolve
        the identical structure."""
        a_d, n_d = row_provider(d)
        B, M = self.batch_size, self.shard_size
        s, e = ci * B, min((ci + 1) * B, M)
        a, nn = a_d[s:e], n_d[s:e]
        if e - s < B:
            a = np.concatenate(
                [a, np.full(B - (e - s), SENTINEL_STATE, np.uint64)])
            nn = np.concatenate([nn, np.ones(B - (e - s))])
        return a, nn

    def _plan_chunk_host(self, ci: int, degrade: bool = True) -> dict:
        """One chunk's host-side plan arrays per addressable shard — from
        the RAM copy, or read back (checksum-verified, retried) from the
        disk-tier sidecar (the OS page cache makes repeated applies
        stream, not re-read cold).  A persistently corrupt chunk degrades
        through :meth:`_degrade_plan_chunk` instead of raising mid-apply —
        unless ``degrade=False`` (the pipelined prefetch workers: the
        repair dispatches collective programs and mutates plan state, so
        it must run on the apply thread; the raw failure propagates to
        the consumer instead)."""
        if self._plan_chunks is not None:
            return self._plan_chunks[ci]
        got = self._plan_repaired.get(ci)
        if got is not None:
            return got
        out = {}
        for d, path in list(self._plan_disk.items()):
            try:
                out[d] = faults.with_retries(
                    "plan_chunk_read",
                    lambda: self._read_plan_chunk(path, d, ci),
                    exc_types=(OSError, KeyError, ValueError))
            except (OSError, KeyError, ValueError) as e:
                if not degrade:
                    raise
                return self._degrade_plan_chunk(ci, path, e)
        return out

    def _read_plan_chunk(self, path: str, d: int, ci: int) -> dict:
        """One (shard, chunk) record from a disk-tier sidecar, with the
        stored CRC verified (``ValueError`` on mismatch).  EVERY failure
        drops the cached file handle so the retry reopens fresh — an
        os.replace-healed sidecar (new inode) is picked up, and a stale
        handle can't replay the same bad bytes through the backoff."""
        faults.check("plan_chunk_read", path=path, chunk=ci)
        import h5py

        f = self._plan_files.get(path)
        if f is None:
            f = self._plan_files[path] = h5py.File(path, "r")
        try:
            g = f["engine_structure"]
            pc = {k: g[f"{k}_{d}_{ci}"][...] for k in self._STREAM_ARRAYS}
            crc = g.attrs.get(f"crc_{d}_{ci}")
            if crc is not None and _plan_chunk_crc(pc) != int(crc):
                raise ValueError(
                    f"stream plan chunk {ci} shard {d} failed its "
                    "checksum")
        except (OSError, KeyError, ValueError):
            self._plan_files.pop(path, None)
            try:
                f.close()
            except Exception:
                pass
            raise
        return pc

    def _degrade_plan_chunk(self, ci: int, path: str, error) -> dict:
        """The documented fallback for a corrupt/truncated disk-tier chunk
        (retries exhausted): count it (``artifact_cache{kind=stream_plan,
        event=corrupt}``), rebuild THIS chunk's plan from structure, and
        on the sidecar's second failure quarantine the file and rebuild
        the whole plan back into host RAM (the disk tier is gone).  Multi-
        controller runs cannot rebuild rank-locally (the build program is
        collective) — they fail loudly so the supervisor relaunches and
        the all-or-nothing restore agreement rebuilds everywhere."""
        from ..utils.artifacts import note_artifact_corrupt
        from ..utils.logging import log_warn

        quarantined = note_artifact_corrupt(path, "stream_plan", error)
        f = self._plan_files.pop(path, None)
        if f is not None:
            try:
                f.close()
            except Exception:
                pass
        if self._multi:
            # OSError, deliberately NOT RuntimeError: the plan_upload
            # retry wrapper retries RuntimeErrors, and this abort must
            # propagate on the first pass (re-running the read/degrade
            # cycle would double-count corruption and quarantine a file
            # the multi-controller policy says to fail loudly on)
            raise OSError(
                f"stream plan sidecar {path} unreadable in a "
                f"multi-controller run ({error!r}); a rank-local rebuild "
                "would desynchronize the build collectives — relaunch to "
                "rebuild the plan on every rank") from error
        if quarantined:
            log_warn("stream plan disk tier lost (sidecar quarantined); "
                     "rebuilding the full plan from structure into host "
                     "RAM")
            self._plan_disk = None
            self._plan_repaired.clear()
            self._build_stream_plan(self._row_provider)
            self._encode_stream_plan()
            self._upload_codec_tables()
            self._register_stream_plan()
            return self._plan_chunks[ci]
        per = self._rebuild_plan_chunk(ci)
        self._plan_repaired[ci] = per
        return per

    def _rebuild_plan_chunk(self, ci: int) -> dict:
        """Re-resolve ONE chunk's plan from structure (tables + per-shard
        lookup are still device-resident in streamed mode) — the same
        program, row padding, AND codec as the original build, so the
        repaired chunk's encoded bytes are bit-identical to what the
        sidecar should have held (the stored CRC would match)."""
        build = self._stream_build_prog
        if build is None:
            build = self._stream_build_prog = self._make_stream_build()
        D = self.n_devices
        my = [d for d in range(D) if self._shard_addressable(d)]
        a_rows = [None] * D
        n_rows = [None] * D
        for d in my:
            a_rows[d], n_rows[d] = self._stream_chunk_rows(
                self._row_provider, d, ci)
        dest, cf, ridx, rok, _ov, _iv = build(
            self._assemble_sharded(a_rows), self._assemble_sharded(n_rows),
            self.tables, self._lk_pair, self._lk_dir)
        per = {d: self._codec.encode_chunk(
            {"dest": self._shard_piece(dest, d),
             "coeff": self._shard_piece(cf, d),
             "ridx": self._shard_piece(ridx, d),
             "rok": self._shard_piece(rok, d)}, d) for d in my}
        emit("plan_chunk_rebuilt", engine="distributed", chunk=int(ci))
        log_debug(f"stream plan chunk {ci} rebuilt from structure")
        return per

    def _fetch_plan_chunk(self, ci: int, degrade: bool = True) -> dict:
        """The latency-bearing HOST half of one plan-chunk upload: the
        ``plan_upload`` fault site plus the RAM/disk fetch
        (:meth:`_plan_chunk_host` — dict walk, or disk read + CRC +
        possible rebuild), retried with backoff.  This is what the
        pipelined prefetch workers run ahead of the apply loop (with
        ``degrade=False`` — see :meth:`_plan_chunk_host`): the work
        releases the GIL (h5py/numpy C code, injected-latency sleeps),
        so it genuinely overlaps the apply thread's dispatches — the
        device staging (:meth:`_stage_plan_chunk`) deliberately stays on
        the apply thread, where it costs the same as in the sequential
        schedule."""
        def _fetch():
            faults.check("plan_upload", exc=RuntimeError, chunk=ci)
            return self._plan_chunk_host(ci, degrade=degrade)

        return faults.with_retries("plan_upload", _fetch,
                                   exc_types=(RuntimeError,))

    def _stage_plan_chunk(self, per: dict):
        """Fetched host arrays → the mesh ([D, ...] assembled arrays).
        The H2D dispatch is async; the byte counter increments here —
        AFTER the retried fetch succeeded — so a transient failure never
        double-counts a chunk."""
        rows = {k: [None] * self.n_devices for k in self._STREAM_ARRAYS}
        n = 0
        for d, pc in per.items():
            for k in self._STREAM_ARRAYS:
                rows[k][d] = pc[k]
                n += pc[k].nbytes
        staged = tuple(self._assemble_sharded(rows[k])
                       for k in self._STREAM_ARRAYS)
        counter("bytes_h2d", path="plan_stream").inc(n)
        return staged

    def _stage_with_retries(self, per: dict):
        """Device staging under the same bounded-retry policy as the
        fetch (the staging is idempotent pure H2D, and the byte counter
        is the closure's LAST step, so a failed attempt never
        double-counts) — a transient dispatch failure degrades to a
        retry instead of killing a solve mid-apply."""
        return faults.with_retries(
            "plan_upload", lambda: self._stage_plan_chunk(per),
            exc_types=(RuntimeError,))

    def _upload_plan_chunk(self, ci: int):
        """Stage one plan chunk onto the mesh ([D, ...] assembled arrays).
        Dispatched one chunk AHEAD of the sequential apply loop so the
        H2D copy overlaps the previous chunk's device pass (the PR-1
        double-buffer pattern, now on the apply path).  The upload is
        idempotent (pure H2D of host-resident arrays), so a transient
        failure is retried with backoff instead of killing a solve
        mid-apply."""
        return self._stage_with_retries(self._fetch_plan_chunk(ci))

    # -- self-tuning runtime (DESIGN.md §30) -------------------------------

    def _tune_stats(self) -> dict:
        """The structure geometry the autotuner prices from — everything
        is an engine fact, nothing is a rate (rates are the search's
        OTHER input, so the same stats re-price correctly under a
        refined posterior)."""
        from ..utils.artifacts import artifacts_enabled
        cfg = get_config()
        return {"shard_size": int(self.shard_size),
                "num_terms": int(self.num_terms),
                "n_my_shards": int(self._n_my_shards),
                "n_devices": int(self.n_devices),
                "pair": bool(self.pair),
                "cplx": bool(self.pair or not self.real),
                "columns": 1,
                "group_order": int(self._hybrid_group_order()),
                "ram_budget_bytes": float(cfg.stream_plan_ram_gb) * 1e9,
                "disk_available": bool(artifacts_enabled())}

    def _init_autotune(self, batch_size_arg, pipeline_arg,
                       hybrid_arg) -> None:
        """``tune=static|live`` engine-build hook: restore or run the
        knob search, agree the answer across ranks, and fold the chosen
        knobs into the build (before any plan exists — the plan is then
        BUILT at the tuned knobs, so the fingerprint/sidecar/bit-identity
        story is exactly a hand-set engine's)."""
        from .. import tune as _tune
        from ..obs import roofline as _roofline
        dev = self.mesh.devices.flat[0]
        plat = dev.platform
        kind = getattr(dev, "device_kind", plat)
        prior = None
        if self._tune_mode == "live":
            prior = _tune.load_posterior(plat, kind, self.mode)
        if prior is None:
            prior = _roofline.resolve_calibration(backend=plat)
        prior = dict(prior)
        prior.setdefault("device_kind", kind)
        stats = self._tune_stats()
        fp = _tune.tuning_fingerprint(stats, prior, self.mode)
        chosen = _tune.load_tuned(fp)
        search_s = 0.0
        if chosen is None:
            chosen, search_s = _tune.timed_choose(stats, prior, self.mode)
            _tune.save_tuned(fp, chosen, stats, prior, search_s)
        chosen = _tune.agree_config(chosen, self._multi)
        self._tuned = chosen
        self._tune_cal = prior
        self._tune_fp = fp
        self._apply_tuned_knobs(chosen, batch_size_arg, pipeline_arg,
                                hybrid_arg)
        if self._tune_mode == "live":
            self._tuner = _tune.LiveTuner(self.mode, stats, prior, chosen)
        obs_phases.emit_tune_config(
            "distributed", self.mode, chosen.knobs(), chosen.token(),
            chosen.priced_ms, chosen.source, search_s, fp)
        log_debug(f"autotune ({self._tune_mode}): {chosen.token()} "
                  f"priced {chosen.priced_ms:.3f} ms/apply "
                  f"[{chosen.source}]")

    def _apply_tuned_knobs(self, t, batch_size_arg, pipeline_arg,
                           hybrid_arg) -> None:
        """Fold a :class:`~..tune.TunedConfig` into the build with the
        documented precedence: an explicit constructor argument always
        wins; a config knob moved off its dataclass default (env var or
        ``update_config``) is a hand pin and wins; the tuned value fills
        everything else."""
        import dataclasses as _dc
        cfg = get_config()
        defaults = {f.name: f.default
                    for f in _dc.fields(type(cfg))}
        M = self.shard_size
        if batch_size_arg is None \
                and cfg.matvec_batch_size == defaults["matvec_batch_size"]:
            self.batch_size = _round_up(min(int(t.batch_size), M), 8)
        if pipeline_arg is None \
                and str(cfg.pipeline) == str(defaults["pipeline"]):
            self._pipeline_req = int(t.pipeline_depth)
        if str(cfg.stream_compress) == str(defaults["stream_compress"]):
            self._tune_compress = t.stream_compress
        if hybrid_arg is None \
                and str(cfg.hybrid) == str(defaults["hybrid"]):
            self._tune_hybrid_split = t.hybrid_split \
                if t.hybrid_split != "-" else None
        self._tune_workers = int(t.prefetch_workers) or None
        self._tune_plan_tier = t.plan_tier

    def _agree_retune(self, prop):
        """One window-boundary collective: every rank reaches this at
        the same apply (windows are deterministic in apply count), so
        the first PROPOSING rank's config is adopted fleet-wide — or the
        re-tune is dropped everywhere.  One rank re-keying alone would
        strand the peers in the next ``_plan_stream`` collective, so on
        any agreement failure the conservative answer is no re-tune on
        every rank."""
        if not self._multi:
            return prop
        try:
            from jax.experimental import multihost_utils as mhu

            from ..tune.space import TunedConfig
            enc = prop.encode() if prop is not None else [0] * 6
            vec = np.asarray([1 if prop is not None else 0] + enc,
                             np.int64)
            rows = np.asarray(
                mhu.process_allgather(vec)).reshape(-1, vec.size)
            have = rows[:, 0] == 1
            if not have.any():
                return None
            r = int(np.argmax(have))
            return TunedConfig.decode(
                rows[r, 1:], self.mode,
                priced_ms=prop.priced_ms if prop is not None else 0.0,
                source="retune")
        except Exception as e:
            log_debug(f"retune agreement unavailable ({e!r}); "
                      "skipping the re-tune on all ranks")
            return None

    def maybe_retune(self) -> bool:
        """Apply a pending drift-triggered re-tune NOW — at a safe
        boundary only (callers: the top of :meth:`matvec` before any
        device work, and the serve pool between jobs).  The plan is
        re-keyed exactly like a fresh build at the new knobs: artifact
        restore first, deterministic rebuild otherwise — never a
        mid-apply mutation.  Returns True when a re-key happened."""
        prop = self._retune_pending
        if prop is None or self.mode not in ("streamed", "hybrid"):
            return False
        self._retune_pending = None
        old = self._tuned
        ratio = (self._tuner.last_ratio
                 if self._tuner is not None else 0.0) or 0.0
        t0 = time.perf_counter()
        self._tuned = prop
        M = self.shard_size
        self.batch_size = _round_up(min(int(prop.batch_size), M), 8)
        self._pipeline_req = int(prop.pipeline_depth)
        self._compress = prop.stream_compress
        self._codec_tier = self._compress
        if self.mode == "hybrid":
            if self._compress == "off":
                self._codec_tier = "lossless"
            self._tune_hybrid_split = prop.hybrid_split \
                if prop.hybrid_split != "-" else None
            if self._tuner is not None:
                # re-key the auto split at the POSTERIOR rates — the §28
                # rate-bearing fingerprint token changes with them
                self._tune_cal = self._tuner.posterior.rates()
            self._init_hybrid_policy(self._tune_hybrid_split)
            self._hybrid_mask = None
        self._tune_workers = int(prop.prefetch_workers) or None
        self._tune_plan_tier = prop.plan_tier
        try:
            self._rebuild_stream_plan()
        except Exception as e:
            oom_reraise(e, engine="distributed", mode=self.mode,
                        phase="retune", n_states=int(self.n_states))
        if self._tuner is not None:
            self._tuner.note_rebuild(prop)
        obs_phases.emit_retune(
            "distributed", self.mode, self._apply_idx,
            old.token() if old is not None else "-", prop.token(),
            ratio, prop.priced_ms, time.perf_counter() - t0)
        log_debug(f"autotune re-key at apply {self._apply_idx}: "
                  f"{old.token() if old is not None else '-'} -> "
                  f"{prop.token()} (ratio {ratio:.2f})")
        return True

    def _rebuild_stream_plan(self) -> None:
        """Tear down the streamed/hybrid plan and rebuild it at the
        CURRENT knobs (row-chunk size, codec tier, hybrid split) — the
        §30 boundary re-key.  Mirrors the constructor's streamed branch:
        the re-keyed fingerprint is consulted against the artifact cache
        first (a re-tune back to previously built knobs restores warm),
        then the kept row provider rebuilds deterministically."""
        self._fp_cache = None
        self._phase_count_cache = {}
        self._stream_build_prog = None
        self._plan_repaired = {}
        self._stream_timeline = []
        self._plan_disk = None
        self._capacity = self._fused_capacity()
        old_files = self._plan_files

        def agree(restored: bool) -> bool:
            if not self._multi:
                return restored
            try:
                from jax.experimental import multihost_utils as mhu
                return bool(int(np.min(
                    mhu.process_allgather(np.int32(restored)))))
            except Exception as e:
                log_debug(f"restore agreement unavailable ({e!r}); "
                          "rebuilding on all ranks")
                return False

        cache = self._resolve_structure_cache(None)
        restored = agree(self._try_load_stream_plan(cache))
        if not restored:
            self._build_stream_plan(self._row_provider)
            if self.mode == "hybrid":
                self._hybrid_mask = self._resolve_hybrid_mask()
            self._encode_stream_plan()
            self._save_stream_plan(cache, soft=True)
        self.structure_restored = restored
        if self._plan_files is not old_files:
            # the restore path swaps in a fresh handle dict; the engine's
            # finalizer tracks the old one — close it and re-register
            import weakref
            _close_plan_files(old_files)
            weakref.finalize(self, _close_plan_files, self._plan_files)
        self._upload_codec_tables()
        if self.mode == "hybrid":
            self._setup_hybrid_recompute()
        self._register_stream_plan()
        self.pipeline_depth = self._resolve_pipeline_depth(
            self._plan_nchunks_v)
        self._matvec = self._make_streamed_matvec()
        self._last_program_key = self.mode
        self._last_capacity = self._capacity
        self._checked.add(self.mode)

    def _resolve_pipeline_depth(self, nchunks: int) -> int:
        """Resolve the ``pipeline_depth`` knob (constructor argument >
        ``config.pipeline`` / ``DMT_PIPELINE``) for an apply of
        ``nchunks`` row chunks: 0 = the sequential compute-then-exchange
        schedule every earlier round shipped (and the default), an
        integer >= 2 = that many chunks in flight, ``auto`` = the
        roofline-calibration policy
        (:func:`~..obs.roofline.choose_pipeline_depth` — on only when the
        priced overlappable time is worth the bookkeeping).  Single-
        program plan modes (ell/compact) have no chunk sequence to
        pipeline and always resolve 0."""
        if self.mode not in ("fused", "streamed", "hybrid"):
            return 0
        val = self._pipeline_req
        if val is None:
            val = get_config().pipeline
        s = str(val).strip().lower()
        if s in ("", "off", "0", "1", "false", "no", "none"):
            return 0
        if s == "auto":
            from ..obs import roofline as _roofline
            depth = _roofline.choose_pipeline_depth(
                self._phase_counts(2 if self.pair else 1),
                _roofline.resolve_calibration(), int(nchunks),
                self.n_devices)
            if depth:
                log_debug(f"pipeline auto: depth {depth} over {nchunks} "
                          f"chunk(s) ({self.mode})")
            return depth
        try:
            depth = int(s)
        except ValueError:
            raise ValueError(
                f"bad pipeline depth {val!r}: pick off | auto | an "
                "integer >= 2 (DMT_PIPELINE / config.pipeline)") from None
        if depth < 0:
            raise ValueError(f"pipeline depth must be >= 0, got {depth}")
        depth = min(depth, max(int(nchunks), 1))
        # a clamp down to one chunk leaves nothing to pipeline — resolve
        # to the sequential schedule, not a degenerate depth-1 pipeline
        return depth if depth >= 2 else 0

    def _make_streamed_matvec(self):
        D, M, T = self.n_devices, self.shard_size, self.num_terms
        B = self.batch_size
        Cap = self._capacity
        nchunks = self._plan_nchunks_v
        Mp = nchunks * B
        dtype = self._dtype
        is_pair = self.pair
        ptail = (2,) if is_pair else ()
        mesh = self.mesh
        from ..ops import plan_codec as PC
        spec = self._codec.spec
        tier_off = spec["tier"] == "off"
        # hybrid mode (DESIGN.md §28): the chunk program carries a second,
        # recompute side — the non-streamed terms' orbit scan + routing —
        # whose amplitudes merge into the SAME send buffer (and therefore
        # the same staged exchange) as the decoded streamed entries
        hyb = self.mode == "hybrid"
        n_rec = self._hyb_n_recompute if hyb else 0
        # the apply runs at the codec's TRIMMED exchange capacity: the
        # build sized buckets for the worst case, the finished plan knows
        # the true max fill (cap_eff == cap_build for the off tier)
        cap_apply = int(spec["cap_eff"])
        n_recv = D * cap_apply

        def make_recompute(tail):
            """HYBRID's recompute side for one chunk: re-derive the
            non-streamed terms' structure on device (the same
            ``gather_coefficients`` + ``_bucket_positions`` math the plan
            build ran, restricted to the recompute term subset — the
            per-term kernels are independent across terms, so the values
            are bit-identical to the build's) and scatter the amplitudes
            into the merged send buffer.

            The merged exchange slot is recovered WITHOUT streaming it:
            in the full plan each bucket's live entries occupy the slot
            prefix [0, fill) in flattened (row, term) order, so the
            recompute entries' slots are exactly the per-bucket
            complement of the streamed entries' stored slots, taken in
            increasing order — the j-th recompute entry of a bucket (by
            the recompute-only in-bucket rank, a preserved subsequence of
            the full order) lands on the bucket's j-th free slot.  That
            makes the hybrid send buffer — and every exchanged and
            accumulated bit after it — identical to the full-streamed
            apply's."""
            nbt = len(tail) - len(ptail)

            def add_recompute(send_a, x_c, a_c, n_c, ht, dest_s):
                betas, gcoeff = K.gather_coefficients(ht, a_c, n_c)
                valid_row = (a_c != SENTINEL_STATE)[:, None]
                if is_pair:
                    nz = (gcoeff != 0).any(axis=-1) & valid_row
                    cf = jnp.where(nz[..., None], K.conj_pair(gcoeff), 0)
                else:
                    nz = (gcoeff != 0) & valid_row
                    cf = jnp.where(nz, jnp.conj(gcoeff), 0)
                flat_b = betas.reshape(-1)
                live = nz.reshape(-1)
                owner = (hash64(flat_b) % jnp.uint64(D)).astype(jnp.int32) \
                    if D > 1 else jnp.zeros(flat_b.shape, jnp.int32)
                key = jnp.where(live, owner, D)
                pos = _bucket_positions(key, D)
                # free-slot table from the streamed entries' occupancy:
                # slot_of[k·cap + j] = the j-th unoccupied slot of bucket
                # k (dest_s pads carry the n_recv sentinel and drop out)
                occ = jnp.zeros(n_recv, jnp.int32).at[dest_s].set(
                    1, mode="drop")
                free = 1 - occ.reshape(D, cap_apply)
                fr = jnp.cumsum(free, axis=1)
                buck = jax.lax.broadcasted_iota(
                    jnp.int32, (D, cap_apply), 0)
                cols = jax.lax.broadcasted_iota(
                    jnp.int32, (D, cap_apply), 1)
                tgt = jnp.where(free > 0, buck * cap_apply + (fr - 1),
                                n_recv)
                slot_of = jnp.zeros(n_recv, jnp.int32).at[
                    tgt.reshape(-1)].set(cols.reshape(-1), mode="drop")
                safe = (jnp.clip(key, 0, D - 1) * cap_apply
                        + jnp.minimum(pos, cap_apply - 1))
                dest_r = jnp.where(live & (pos < cap_apply),
                                   key * cap_apply + slot_of[safe], n_recv)
                x_t = x_c[:, None]                   # [B, 1] + tail
                if is_pair:
                    g_t = cf[:, :, None, :] if nbt else cf
                    amps = K.cmul_pair(g_t, x_t)
                else:
                    g_t = cf[:, :, None] if nbt else cf
                    amps = g_t * x_t
                return send_a.at[dest_r].set(
                    amps.reshape((-1,) + tail), mode="drop")

            return add_recompute

        def make_decode_send(tail):
            """One chunk's SEND side as a pure function of (x slice,
            plan arrays): decode + gather + multiply + scatter into the
            bucketed send buffer, plus the decoded receive layout.
            Shared by the sequential chunk program (which consumes all
            three outputs) and the pipelined produce program (which keeps
            only the send buffer — XLA dead-code-eliminates the receive
            decode there; the exchange program re-derives it via
            ``decode_recv``), so the two schedules compute identical
            amplitudes by construction."""
            nbt = len(tail) - len(ptail)   # number of batch axes (0 or 1)
            add_recompute = make_recompute(tail) if (hyb and n_rec) \
                else None

            def decode_send(x_c, dest, coeff, ridx, rok, cdict,
                            a_c=None, n_c=None, ht=None):
                if tier_off:
                    # raw plan layout: identical arithmetic to the fused
                    # chunk — amplitudes are conj-coefficient × x,
                    # dead/overflowed entries dropped by dest == D·Cap
                    # (coeff is pre-zeroed for dead entries)
                    dest_, cf_, ridx_, rok_ = PC.decode_plan_shard(
                        spec, dest, coeff, ridx, rok, cdict)
                    x_t = x_c[:, None]
                    g_t = cf_
                    if nbt:
                        g_t = g_t[:, :, None, :] if is_pair \
                            else g_t[:, :, None]
                    amps = K.cmul_pair(g_t, x_t) if is_pair else g_t * x_t
                    flat_a = amps.reshape((-1,) + tail)
                    send_a = jnp.zeros((n_recv,) + tail,
                                       dtype).at[dest_].set(
                        flat_a, mode="drop")
                else:
                    # compacted stream, decoded in-program: XLA fuses the
                    # unpack/dict gathers with the explicit row gather,
                    # multiply and scatter below — the "fused decode"
                    # default.  Only LIVE entries exist (dead ones never
                    # left the host), the explicit x[row] gather replaces
                    # the implicit i // T, and padding entries scatter to
                    # the drop sentinel.  Values and accumulation order
                    # match the raw layout exactly (DESIGN.md §23).
                    dest_, row_, cf_, ridx_, rok_ = PC.decode_plan_shard(
                        spec, dest, coeff, ridx, rok, cdict)
                    xg = x_c[row_]                     # [n_live] + tail
                    if is_pair:
                        g = cf_[:, None, :] if nbt else cf_
                        amps = K.cmul_pair(g, xg)
                    else:
                        g = cf_[:, None] if nbt else cf_
                        amps = g * xg
                    send_a = jnp.zeros((n_recv,) + tail,
                                       dtype).at[dest_].set(
                        amps, mode="drop")
                    if add_recompute is not None:
                        # hybrid: the recompute terms' amplitudes land in
                        # the same buffer at their merged (full-plan)
                        # slots — disjoint from the streamed entries', so
                        # the scatter order between the two sides cannot
                        # change a bit
                        send_a = add_recompute(send_a, x_c, a_c, n_c, ht,
                                               dest_)
                return send_a, ridx_, rok_

            return decode_send

        def decode_recv(ridx, rok):
            """The receive layout alone (the pipelined exchange program's
            half of the decode) — same unpack ops as the send side's."""
            rok_ = PC.unpack_bits(rok, n_recv, 1).astype(bool)
            if tier_off:
                return ridx, rok_
            return PC.unpack_bits(
                ridx, n_recv, spec["w_ridx"]).astype(jnp.int32), rok_

        def accumulate(y_, recv_a, ridx_, rok_, tail):
            """Receive-side accumulation — ONE definition for both
            schedules, so the pipelined apply cannot drift from the
            sequential one by construction (same mask, same
            ``segment_sum``, same order)."""
            return y_ + jax.ops.segment_sum(
                jnp.where(rok_.reshape(rok_.shape + (1,) * len(tail)),
                          recv_a, 0),
                ridx_, num_segments=M)

        def make_io_progs(tail):
            nd = 2 + len(tail)
            pad_prog = jax.jit(lambda x: jnp.pad(
                x.astype(dtype),
                ((0, 0), (0, Mp - M)) + ((0, 0),) * len(tail)))
            zeros_prog = jax.jit(
                lambda: jnp.zeros((D, M) + tail, dtype),
                out_shardings=shard_spec(mesh, nd))
            epi_prog = jax.jit(
                lambda y, x, diag: y + diag.astype(dtype).reshape(
                    diag.shape + (1,) * len(tail)) * x.astype(dtype))
            return pad_prog, zeros_prog, epi_prog

        # hybrid: every chunk program takes three extra operands — the
        # padded basis/norm rows (sharded, dynamic-sliced per chunk like
        # x) and the recompute-term table subset (replicated, like the
        # fused program's tables).  Non-hybrid programs keep their exact
        # historical signature.
        hyb_specs = (_pspec(2), _pspec(2), P()) if hyb else ()

        def slice_hyb(start, hargs):
            if not hyb:
                return (None, None, None)
            ap, nn, ht = hargs
            return (jax.lax.dynamic_slice(ap[0], (start,), (B,)),
                    jax.lax.dynamic_slice(nn[0], (start,), (B,)), ht)

        def make_programs(tail):
            decode_send = make_decode_send(tail)

            def shard_body(xp, y, start, dest, coeff, ridx, rok, cdict,
                           *hargs):
                xp_, y_ = xp[0], y[0]
                zeros = tuple(jnp.zeros((), start.dtype) for _ in tail)
                x_c = jax.lax.dynamic_slice(
                    xp_, (start,) + zeros, (B,) + tail)
                send_a, ridx_, rok_ = decode_send(
                    x_c, dest[0], coeff[0], ridx[0], rok[0], cdict[0],
                    *slice_hyb(start, hargs))
                if D > 1:
                    recv_a = jax.lax.all_to_all(
                        send_a.reshape((D, cap_apply) + tail), SHARD_AXIS,
                        0, 0, tiled=True
                    ).reshape((-1,) + tail)
                else:
                    recv_a = send_a
                return accumulate(y_, recv_a, ridx_, rok_, tail)[None]

            nd = 2 + len(tail)

            def chunk_fn(xp, y, start, dest, coeff, ridx, rok, cdict,
                         *hargs):
                f = jax.shard_map(
                    shard_body, mesh=mesh,
                    in_specs=(_pspec(nd), _pspec(nd), P(),
                              _pspec(dest.ndim), _pspec(coeff.ndim),
                              _pspec(ridx.ndim), _pspec(rok.ndim),
                              _pspec(cdict.ndim)) + hyb_specs,
                    out_specs=_pspec(nd),
                )
                return f(xp, y, start, dest, coeff, ridx, rok, cdict,
                         *hargs)

            chunk_prog = jax.jit(chunk_fn, donate_argnums=(1,))
            return (chunk_prog,) + make_io_progs(tail)

        def make_pipe_programs(tail):
            """The pipelined schedule's split programs (DESIGN.md §25):
            ``send_prog`` produces one chunk's bucketed send buffer (the
            local gather/multiply — dispatched up to ``depth`` chunks
            ahead), ``exch_prog`` runs the STAGED exchange (D−1
            ``ppermute`` rounds, element-identical to the monolithic
            ``all_to_all``) and accumulates into the donated ``y``.
            Exchanges retire strictly in chunk order through the ``y``
            chain, so the accumulation order — and therefore every bit of
            the result — matches the sequential schedule."""
            decode_send = make_decode_send(tail)
            nd = 2 + len(tail)

            def send_body(xp, start, dest, coeff, ridx, rok, cdict,
                          *hargs):
                zeros = tuple(jnp.zeros((), start.dtype) for _ in tail)
                x_c = jax.lax.dynamic_slice(
                    xp[0], (start,) + zeros, (B,) + tail)
                send_a, _, _ = decode_send(
                    x_c, dest[0], coeff[0], ridx[0], rok[0], cdict[0],
                    *slice_hyb(start, hargs))
                return send_a[None]

            def send_fn(xp, start, dest, coeff, ridx, rok, cdict, *hargs):
                f = jax.shard_map(
                    send_body, mesh=mesh,
                    in_specs=(_pspec(nd), P(),
                              _pspec(dest.ndim), _pspec(coeff.ndim),
                              _pspec(ridx.ndim), _pspec(rok.ndim),
                              _pspec(cdict.ndim)) + hyb_specs,
                    out_specs=_pspec(2 + len(tail)),
                )
                return f(xp, start, dest, coeff, ridx, rok, cdict, *hargs)

            def exch_body(y, send, ridx, rok):
                y_, s_ = y[0], send[0]
                ridx_, rok_ = decode_recv(ridx[0], rok[0])
                recv_a = _staged_all_to_all(
                    s_.reshape((D, cap_apply) + tail),
                    SHARD_AXIS).reshape((-1,) + tail)
                return accumulate(y_, recv_a, ridx_, rok_, tail)[None]

            def exch_fn(y, send, ridx, rok):
                f = jax.shard_map(
                    exch_body, mesh=mesh,
                    in_specs=(_pspec(nd), _pspec(2 + len(tail)),
                              _pspec(ridx.ndim), _pspec(rok.ndim)),
                    out_specs=_pspec(nd),
                )
                return f(y, send, ridx, rok)

            # y chains through the exchanges (donated, as in the
            # sequential program); the send buffer is donated into its
            # exchange so slot memory really is bounded at `depth` buffers
            send_prog = jax.jit(send_fn)
            exch_prog = jax.jit(exch_fn, donate_argnums=(0, 1))
            return (send_prog, exch_prog) + make_io_progs(tail)

        programs: dict = {}
        pipe_programs: dict = {}
        hyb_ops = (self._hyb_alphas, self._hyb_norms, self._hyb_tables) \
            if hyb else ()

        def run_cols(x):
            tail = tuple(x.shape[2:])
            progs = programs.get(tail)
            if progs is None:
                progs = programs[tail] = make_programs(tail)
            chunk_prog, pad_prog, zeros_prog, epi_prog = progs
            xp = pad_prog(x)
            y = zeros_prog()
            record_stall = obs_enabled()
            # per-chunk timeline for phase attribution: the measured H2D
            # wait (the stall above) plus the host dispatch wall of each
            # chunk program — host perf_counter readings only, no syncs
            # beyond the stall measurement obs already takes
            timeline = [] if obs_phases.phases_enabled() else None
            pending = self._upload_plan_chunk(0) if nchunks else None
            for ci in range(nchunks):
                entry = {"chunk": ci}
                # chunk span: H2D wait + dispatch of one streamed plan
                # chunk.  A rank wedged here (stuck disk read, dead H2D)
                # leaves this span open, so the heartbeat's stall_report
                # names the exact chunk the rank died on
                with obs_trace.span("chunk", kind="chunk", chunk=ci):
                    if record_stall:
                        # the wait below is the stream's whole performance
                        # story: ~0 when the upload finished while the
                        # device ran the previous chunk, the H2D lag
                        # otherwise.  It exists ONLY to feed the metric —
                        # dispatch tracks the transfer dependency itself —
                        # so DMT_OBS=off skips the host sync entirely
                        _t0 = time.perf_counter()
                        jax.block_until_ready(pending)
                        stall_ms = (time.perf_counter() - _t0) * 1e3
                        histogram("plan_stream_stall_ms").observe(stall_ms)
                        entry["stall_ms"] = round(stall_ms, 4)
                    _td = time.perf_counter()
                    y = chunk_prog(xp, y, jnp.int32(ci * B), *pending,
                                   self._cdict_dev, *hyb_ops)
                    if timeline is not None:
                        entry["dispatch_ms"] = round(
                            (time.perf_counter() - _td) * 1e3, 4)
                        timeline.append(entry)
                    if ci + 1 < nchunks:
                        pending = self._upload_plan_chunk(ci + 1)
            if timeline is not None:
                self._stream_timeline.extend(timeline)
            return epi_prog(y, x, self._diag)

        depth = self.pipeline_depth

        def run_cols_pipe(x):
            """The pipelined schedule (DESIGN.md §25): plan staging runs
            up to ``depth`` chunks ahead in the prefetch workers, produce
            programs are dispatched as their chunks stage, and each
            chunk's staged exchange retires strictly in chunk order once
            ``depth`` produces are queued ahead of it — so the device
            sees P_j..P_{j+depth-1} before X_j and can drain compute
            while an exchange is in flight.  The consume-side waits
            (``stall_ms``) are the apply's measured time-at-barrier; the
            worker-side staging walls (``stage_ms``) are the work the
            pipeline hid."""
            tail = tuple(x.shape[2:])
            progs = pipe_programs.get(tail)
            if progs is None:
                progs = pipe_programs[tail] = make_pipe_programs(tail)
            send_prog, exch_prog, pad_prog, zeros_prog, epi_prog = progs
            xp = pad_prog(x)
            y = zeros_prog()
            record_stall = obs_enabled()
            timeline = [] if obs_phases.phases_enabled() else None
            d = max(min(depth, nchunks), 1)
            sends: dict = {}
            entries: dict = {}            # chunk -> its timeline entry

            def retire(j, y):
                # send-slot discipline: slot j's exchange is dispatched as
                # soon as `depth` produces are in the queue ahead of it —
                # the produce→exchange dependency rides the dataflow (the
                # exchange consumes and DONATES the send buffer), so no
                # host sync is needed and the dispatch wall stays
                # comparable to the sequential schedule's.  At most
                # `depth` send buffers sit between a produce and its
                # exchange in the dispatch stream.  The dispatch wall
                # lands on CHUNK J's timeline entry (the exchange retired
                # here belongs to chunk j, not to the loop iteration
                # dispatching it).
                snd, ridx_j, rok_j = sends.pop(j)
                _t1 = time.perf_counter()
                y = exch_prog(y, snd, ridx_j, rok_j)
                ent = entries.pop(j, None)
                if ent is not None:
                    ent["exch_ms"] = round(
                        (time.perf_counter() - _t1) * 1e3, 4)
                return y

            pfh = {"pf": _PlanPrefetcher(self, nchunks, d)}

            def consume(ci):
                # prefetch-get (the measured barrier wait when the fetch
                # was NOT hidden) + the H2D dispatch — called one chunk
                # AHEAD of use, so the transfer overlaps the previous
                # chunk's dispatches exactly as in the sequential
                # schedule's double buffer
                kind, val, stage_ms, wait_ms = pfh["pf"].get(ci)
                if kind == "degrade":
                    # corrupt-chunk repair runs HERE, on the apply thread
                    # (it can dispatch collective build programs and
                    # mutate plan state): stop the workers, degrade or
                    # rebuild exactly as the sequential schedule would,
                    # then resume prefetching the chunks still ahead
                    pfh["pf"].close(join=True)
                    _t0 = time.perf_counter()
                    val = self._fetch_plan_chunk(ci)
                    wait_ms += (time.perf_counter() - _t0) * 1e3
                    pfh["pf"] = _PlanPrefetcher(self, nchunks, d,
                                                start=ci + 1)
                return self._stage_with_retries(val), stage_ms, wait_ms

            try:
                nxt = consume(0) if nchunks else None
                for ci in range(nchunks):
                    entry = None
                    if timeline is not None:
                        entry = entries[ci] = {"chunk": ci}
                        timeline.append(entry)   # mutated through retire
                    # chunk span: staging consume + produce dispatch (+ the
                    # in-order retire of the chunk leaving the pipeline) —
                    # a rank wedged here leaves the span open, so the
                    # heartbeat's stall_report names the stuck chunk
                    with obs_trace.span("chunk", kind="chunk", chunk=ci):
                        staged, stage_ms, wait_ms = nxt
                        if record_stall:
                            # consume-side exposure: the prefetch wait +
                            # the residual wait on a transfer dispatched
                            # one chunk ago — ~0 when the pipeline hid the
                            # fetch behind compute, the time-at-barrier
                            # otherwise.  The sync exists only to feed the
                            # metric (dispatch tracks the transfer
                            # dependency itself), same contract as the
                            # sequential stall probe.
                            _t0 = time.perf_counter()
                            jax.block_until_ready(staged)
                            stall_ms = wait_ms \
                                + (time.perf_counter() - _t0) * 1e3
                            histogram("plan_stream_stall_ms").observe(
                                stall_ms)
                            if entry is not None:
                                entry["stall_ms"] = round(stall_ms, 4)
                                entry["stage_ms"] = round(stage_ms, 4)
                        # only the exchange's operands stay referenced
                        # until retire: dropping the dest/coeff arrays
                        # here keeps the live plan footprint at the
                        # documented `depth` send buffers, not `depth`
                        # full plan chunks
                        sends[ci] = (send_prog(xp, jnp.int32(ci * B),
                                               *staged, self._cdict_dev,
                                               *hyb_ops),
                                     staged[2], staged[3])
                        if ci >= d - 1:
                            y = retire(ci - (d - 1), y)
                        if ci + 1 < nchunks:
                            nxt = consume(ci + 1)
                # drain: the last d−1 chunks' exchanges, still in order
                for j in range(max(nchunks - d + 1, 0), nchunks):
                    with obs_trace.span("chunk", kind="chunk", chunk=j,
                                        drain=True):
                        y = retire(j, y)
            finally:
                # join even on the exception path: a retried apply must
                # not spawn fresh workers while an old one is still
                # inside the thread-unsafe h5py handles
                pfh["pf"].close(join=True)
            if timeline is not None:
                self._stream_timeline.extend(timeline)
            return epi_prog(y, x, self._diag)

        run_group = run_cols_pipe if depth >= 2 else run_cols

        def run(x):
            # WIDE batches are applied in column groups of 4: per-chunk
            # scratch (amps [B, T, k] + exchange [D·Cap·k]) grows linearly
            # in k, and streamed mode exists precisely for bases that
            # crowd HBM — the same ~4×-a-single-apply bound fused enforces
            # by shrinking its row chunk.  Each group re-streams the plan;
            # k ≤ 4 keeps the one-stream-per-apply (and bit-identity-to-
            # fused) fast path.
            tl = 1 if is_pair else 0
            k = x.shape[2] if x.ndim == 3 + tl else 1
            if k > 4:
                y = jnp.concatenate(
                    [run_group(x[:, :, s:s + 4])
                     for s in range(0, k, 4)], axis=2)
            else:
                y = run_group(x)
            self._last_program_key = self.mode
            self._last_capacity = Cap
            return (y, jnp.asarray(self._stream_overflow, jnp.int64),
                    jnp.asarray(self._stream_invalid, jnp.int64))

        return run

    def _make_compact_matvec(self):
        D, C = self.n_devices, self.query_capacity
        T0 = self._ell_T0
        W = self._c_W
        has_tail = self._c_tail is not None
        use_sg = self._c_use_sg

        from ..ops.split_gather import join_parts, split_parts

        def shard_body(x, qin, tags, diag, inv_n, n_parts, norms_all, tail):
            x, qin, tags, diag, inv_n = (
                a[0] for a in (x, qin, tags, diag, inv_n))
            n_parts, norms_all = n_parts[0], norms_all[0]
            batched = x.ndim == 2
            if D > 1:
                S = x[qin]
                R = jax.lax.all_to_all(S, SHARD_AXIS, 0, 0, tiled=True)
                xx = jnp.concatenate(
                    [x, R.reshape((D * C,) + x.shape[1:])], axis=0)
            else:
                xx = x

            if use_sg:
                xs = split_parts(xx).reshape(xx.shape[0], -1)
                kx = xs.shape[1]
                src = jnp.concatenate([xs, n_parts], axis=1)

                def gather_nx(i):
                    g = src[i]
                    xg = join_parts(
                        g[..., :kx].reshape(i.shape + x.shape[1:] + (3,)),
                        jnp.float64)
                    ng = join_parts(g[..., kx:], jnp.float64)
                    return xg, ng
            else:
                def gather_nx(i):
                    return xx[i], norms_all[i]

            def terms(acc, tags, width):
                def body(acc, v):
                    i = jnp.maximum(jnp.abs(v) - 1, 0)
                    s = jnp.sign(v).astype(jnp.float64)
                    xg, ng = gather_nx(i)
                    w = s * ng
                    return acc + (w[:, None] if batched else w) * xg

                if unroll_terms_ok(width, tags.shape[1], x.shape):
                    for t in range(width):
                        acc = body(acc, tags[t])
                else:
                    acc, _ = jax.lax.scan(
                        lambda a, v: (body(a, v), None), acc, tags[:width])
                return acc

            # zero carries must be marked varying-per-shard before they
            # enter a lax.scan under shard_map (the body gathers from the
            # shard-varying xx, so the carry comes back varying; an
            # unvarying init then fails the scan's type check — only the
            # scan branch of `terms` hits this, i.e. the LARGE-T0 regime
            # small-config tests never reach)
            def zvar(a):
                return jax.lax.pcast(a, SHARD_AXIS, to="varying")
            acc = terms(zvar(jnp.zeros(x.shape, jnp.float64)), tags, T0)
            d = diag.reshape(diag.shape + (1,) * (x.ndim - 1))
            sc = (W * inv_n).reshape(inv_n.shape + (1,) * (x.ndim - 1))
            y = d * x + sc * acc
            if has_tail:
                rows, tag_t = (a[0] for a in tail)
                acc_t = terms(zvar(jnp.zeros(rows.shape + x.shape[1:])),
                              tag_t, tag_t.shape[0])
                sct = W * inv_n[rows]
                y = y.at[rows].add(
                    (sct[:, None] if batched else sct) * acc_t, mode="drop")
            return y[None]

        mesh = self.mesh

        def apply_fn(x, operands):
            qin, tags, diag, inv_n, n_parts, norms_all, tail = operands
            tail_specs = tuple(_pspec(a.ndim) for a in tail) if has_tail \
                else P()
            f = jax.shard_map(
                shard_body, mesh=mesh,
                in_specs=(_pspec(x.ndim), _pspec(qin.ndim),
                          _pspec(tags.ndim), _pspec(diag.ndim),
                          _pspec(inv_n.ndim), _pspec(n_parts.ndim),
                          _pspec(norms_all.ndim), tail_specs),
                out_specs=_pspec(x.ndim),
            )
            y = f(x.astype(jnp.float64), qin, tags, diag, inv_n, n_parts,
                  norms_all, tail)
            return y, jnp.zeros((), jnp.int64), jnp.zeros((), jnp.int64)

        self._apply_fn = apply_fn
        self._operands = (self._qin, self._c_idx, self._diag, self._c_inv_n,
                          self._c_n_parts, self._c_norms, self._c_tail)
        _mv = jax.jit(apply_fn)
        return lambda x: _mv(x, self._operands)

    def _make_ell_matvec(self):
        D, C = self.n_devices, self.query_capacity
        T0 = self._ell_T0
        dtype = self._dtype
        has_tail = self._ell_tail is not None
        use_sg = split_gather_enabled()
        is_pair = self.pair
        nd_base = 2 if is_pair else 1   # ndim of one unbatched local vector
        gathered = self.exchange_form == "gathered"

        def shard_body(x, qin, gidx, coeff, diag, tail):
            x, gidx, coeff, diag = (a[0] for a in (x, gidx, coeff, diag))
            batched = x.ndim == nd_base + 1
            # the named scopes are metadata on the operations (their
            # ``op_name``; a device trace carries it per event): no
            # operation is added, moved or split for them
            if D > 1 and gathered:
                with jax.named_scope("apply/exchange"):
                    # every peer receives the whole shard: [D·M] +
                    # x.shape[1:], shard p's rows at p·M.  An all_to_all of
                    # the shard broadcast to the D peers moves what an
                    # all_gather would, (D - 1)·M values; the all_gather is
                    # asynchronous, and the compiler then slices every
                    # column's coefficients while it runs, 1.6 GB more in a
                    # pair-form block program
                    B = jnp.broadcast_to(x[None], (D,) + x.shape)
                    R = jax.lax.all_to_all(B, SHARD_AXIS, 0, 0, tiled=True)
                    xx = R.reshape((D * x.shape[0],) + x.shape[1:])
            elif D > 1:
                with jax.named_scope("apply/pack"):
                    S = x[qin[0]]               # [D, C] + x.shape[1:]
                with jax.named_scope("apply/exchange"):
                    R = jax.lax.all_to_all(S, SHARD_AXIS, 0, 0, tiled=True)
                    xx = jnp.concatenate(
                        [x, R.reshape((D * C,) + x.shape[1:])], axis=0)
            else:
                xx = x
            with jax.named_scope("apply/split"):
                gx = prep_gather(xx, dtype, use_sg)

            def contrib(c, g):
                if is_pair:
                    return K.cmul_pair(c[:, None, :] if batched else c, g)
                return (c[:, None] if batched else c) * g

            def terms(y, gidx, coeff, width):
                if unroll_terms_ok(width, gidx.shape[1], x.shape):
                    for t in range(width):
                        y = y + contrib(coeff[t], gx(gidx[t]))
                else:
                    def step(y, args):
                        i, c = args
                        return y + contrib(c, gx(i)), None
                    y, _ = jax.lax.scan(step, y,
                                        (gidx[:width], coeff[:width]))
                return y

            with jax.named_scope("apply/diag"):
                d = diag.reshape(
                    diag.shape + (1,) * (x.ndim - 1)).astype(dtype)
                y = d * x
            with jax.named_scope("apply/terms"):
                y = terms(y, gidx, coeff, T0)
            if has_tail:
                with jax.named_scope("apply/tail"):
                    rows, idx_t, cf_t = (a[0] for a in tail)
                    zshape = rows.shape + x.shape[1:]
                    acc = terms(jax.lax.pcast(jnp.zeros(zshape, dtype),
                                              SHARD_AXIS, to="varying"),
                                idx_t, cf_t, idx_t.shape[0])
                    y = y.at[rows].add(acc, mode="drop")
            return y[None]

        mesh = self.mesh

        def apply_fn(x, operands):
            qin, gidx, coeff, diag, tail = operands
            tail_specs = tuple(_pspec(a.ndim) for a in tail) if has_tail \
                else P()
            f = jax.shard_map(
                shard_body, mesh=mesh,
                in_specs=(_pspec(x.ndim),
                          P() if qin is None else _pspec(qin.ndim),
                          _pspec(gidx.ndim),
                          _pspec(coeff.ndim), _pspec(diag.ndim), tail_specs),
                out_specs=_pspec(x.ndim),
            )
            y = f(x.astype(dtype), qin, gidx, coeff, diag, tail)
            return y, jnp.zeros((), jnp.int64), jnp.zeros((), jnp.int64)

        self._apply_fn = apply_fn
        self._operands = (self._qin, self._ell_idx, self._ell_coeff,
                          self._diag, self._ell_tail)
        _mv = jax.jit(apply_fn)
        return lambda x: _mv(x, self._operands)

    # ------------------------------------------------------------------
    # Fused mode: dynamic bucketing + all_to_all + segment_sum
    # ------------------------------------------------------------------

    def _fused_capacity(self, batch_rows: Optional[int] = None) -> int:
        cfg = get_config()
        D, T = self.n_devices, self.num_terms
        B = batch_rows or self.batch_size
        total = B * max(T, 1)
        if D == 1:
            return _round_up(total, 8)
        mean = total / D
        cap = int(math.ceil(mean * max(cfg.all_to_all_capacity_factor, 1.0)))
        cap = min(max(cap, 64), total, cfg.remote_buffer_size)
        if cap < mean:
            # a cap below the per-chunk MEAN bucket size makes first-apply
            # overflow near-certain for any balanced hash — fail fast at
            # build time with the knob name instead of after a full apply
            # (measured: chain_32_symm at the 150k default needs ~165k).
            # Kept a warning, not an error: deliberately tiny caps are how
            # the overflow-detection path itself is exercised.
            import warnings
            warnings.warn(
                f"fused-mode exchange capacity {cap} is below the mean "
                f"per-peer bucket size {mean:.0f} (batch {B} × {T} terms "
                f"on {D} shards) — the first apply will almost surely "
                "overflow; raise remote_buffer_size "
                "(DMT_REMOTE_BUFFER_SIZE) or lower matvec_batch_size",
                RuntimeWarning, stacklevel=3)
        return _round_up(cap, 8)

    def _make_fused_matvec(self):
        D, M, T = self.n_devices, self.shard_size, self.num_terms
        dtype = self._dtype
        lk_shift, lk_probes = self._lk_shift, self._lk_probes
        is_pair = self.pair
        ptail = (2,) if is_pair else ()   # trailing (re, im) axis in pair mode
        mesh = self.mesh
        # the fused pipeline is the IN-PROGRAM software pipeline: one
        # chunk's staged exchange in flight under the next chunk's
        # compute, i.e. depth 2 regardless of the requested number (extra
        # depth only means extra live send buffers inside one program —
        # report the honest value)
        self.pipeline_depth = min(self.pipeline_depth, 2)
        pipe = self.pipeline_depth >= 2

        def make_program(B, Cap):
            nchunks = M // B if M % B == 0 else M // B + 1
            Mp = nchunks * B

            def shard_body(x, alphas, norms, tables, lk_pair, lk_dir):
                x, alphas, norms = x[0], alphas[0], norms[0]
                lk_pair, lk_dir = lk_pair[0], lk_dir[0]
                # an optional trailing batch axis rides the SAME routing: betas,
                # owners, sort order, and the state-index lookup are per (row,
                # term) — independent of the column — so a [M, k] batch pays one
                # hash/argsort/all_to_all for all k columns instead of k full
                # applies (the batch economics ELL mode already had)
                tail = x.shape[1:]           # (k,)? + (2,)? — batch then pair
                # pad local arrays to a whole number of chunks
                xp = jnp.pad(x, ((0, Mp - M),) + ((0, 0),) * (x.ndim - 1))
                ap = jnp.pad(alphas, (0, Mp - M),
                             constant_values=SENTINEL_STATE)
                np_ = jnp.pad(norms, (0, Mp - M), constant_values=1.0)
                nbt = len(tail) - len(ptail)  # number of batch axes (0 or 1)

                def produce(a_c, n_c, x_c):
                    """Chunk SEND side: orbit scan + amplitudes + bucket
                    routing into the fixed-capacity send buffers (plus the
                    overflow delta) — shared by the sequential and
                    pipelined scan bodies, so both route identically."""
                    betas, gcoeff = K.gather_coefficients(tables, a_c, n_c)
                    # scatter-form amplitude: conj(row form) · x[α].  Liveness is
                    # *structural* (coeff ≠ 0, row not padding) — independent of
                    # x's zero pattern, so the overflow/invalid counters checked
                    # on the first call hold for every later x.
                    valid_row = (a_c != SENTINEL_STATE)[:, None]
                    x_t = x_c[:, None]                      # [B, 1] + tail
                    if is_pair:
                        nz = (gcoeff != 0).any(axis=-1) & valid_row
                        g_t = K.conj_pair(gcoeff)           # [B, T, 2]
                        if nbt:
                            g_t = g_t[:, :, None, :]        # [B, T, 1, 2]
                        amps = jnp.where(
                            nz.reshape(nz.shape + (1,) * len(tail)),
                            K.cmul_pair(g_t, x_t), 0)
                    else:
                        nz = (gcoeff != 0) & valid_row
                        g_t = jnp.conj(gcoeff)
                        if nbt:
                            g_t = g_t[:, :, None]
                        amps = jnp.where(
                            nz.reshape(nz.shape + (1,) * nbt), g_t * x_t, 0)
                    flat_b = betas.reshape(-1)
                    flat_a = amps.reshape((-1,) + tail)
                    live = nz.reshape(-1)
                    owner = (hash64(flat_b) % jnp.uint64(D)).astype(jnp.int32) \
                        if D > 1 else jnp.zeros(flat_b.shape, jnp.int32)
                    key = jnp.where(live, owner, D)
                    # Bucket positions: rank within the owner bucket (the
                    # scatter target makes within-bucket order irrelevant —
                    # segment_sum on the receive side is order-insensitive,
                    # and send_b/send_a share one dest).  The helper is
                    # SHARED with the streamed plan build, which replays
                    # this exact routing once and stores the result.
                    pos = _bucket_positions(key, D)
                    in_cap = (pos < Cap) & (key < D)
                    ov = jnp.sum((pos >= Cap) & (key < D))
                    dest = jnp.where(in_cap, key * Cap + pos, D * Cap)
                    send_b = jnp.full(D * Cap, SENTINEL_STATE).at[dest].set(
                        flat_b, mode="drop")
                    send_a = jnp.zeros((D * Cap,) + tail, dtype).at[dest].set(
                        flat_a, mode="drop")
                    return send_b, send_a, ov

                def consume(y, invalid, recv_b, recv_a):
                    """Chunk RECEIVE side: owner lookup + masked
                    ``segment_sum`` — one definition for both schedules
                    (the pipelined body feeds it the same values one scan
                    step later, so accumulation order is unchanged)."""
                    idx, found = state_index_bucketed(
                        lk_pair, lk_dir, recv_b,
                        shift=lk_shift, probes=lk_probes)
                    # structural liveness on the receive side: real entries carry
                    # a non-SENTINEL state (padding slots are SENTINEL, amp 0)
                    live_r = recv_b != SENTINEL_STATE
                    okc = found & live_r
                    invalid = invalid + jnp.sum(live_r & ~found)
                    y = y + jax.ops.segment_sum(
                        jnp.where(okc.reshape(okc.shape + (1,) * len(tail)),
                                  recv_a, 0),
                        jnp.where(okc, idx, 0),
                        num_segments=M)
                    return y, invalid

                def chunk(carry, args):
                    y, overflow, invalid = carry
                    a_c, n_c, x_c = args
                    send_b, send_a, ov = produce(a_c, n_c, x_c)
                    overflow = overflow + ov
                    if D > 1:
                        recv_b = jax.lax.all_to_all(
                            send_b.reshape(D, Cap), SHARD_AXIS, 0, 0, tiled=True
                        ).reshape(-1)
                        recv_a = jax.lax.all_to_all(
                            send_a.reshape((D, Cap) + tail), SHARD_AXIS, 0, 0,
                            tiled=True
                        ).reshape((-1,) + tail)
                    else:
                        recv_b, recv_a = send_b, send_a
                    y, invalid = consume(y, invalid, recv_b, recv_a)
                    return (y, overflow, invalid), None

                def exchange_staged(send_b, send_a):
                    recv_b = _staged_all_to_all(
                        send_b.reshape(D, Cap), SHARD_AXIS).reshape(-1)
                    recv_a = _staged_all_to_all(
                        send_a.reshape((D, Cap) + tail),
                        SHARD_AXIS).reshape((-1,) + tail)
                    return recv_b, recv_a

                def chunk_pipe(carry, args):
                    # the in-program software pipeline (DESIGN.md §25):
                    # the PREVIOUS chunk's staged exchange + accumulate
                    # and THIS chunk's orbit scan/routing are independent
                    # dataflow inside one scan step, so the scheduler may
                    # run the ppermute rounds while the gather/multiply
                    # computes — chunk i's exchange in flight under chunk
                    # i+1's compute, exactly the overlap the roofline's
                    # pipelined estimate prices.  y still accumulates in
                    # chunk order (one step late), so the result is
                    # bit-identical to the sequential schedule.  The
                    # carry grows by the 2·D·Cap in-flight send buffers —
                    # small next to the B·T orbit-scan working set
                    # (measured ~1% on the CPU rig, whose runtime copies
                    # scan carries per iteration; pipeline-check bounds
                    # the ratio), and the price of keeping this ONE
                    # static program.
                    y, overflow, invalid, prev_b, prev_a = carry
                    a_c, n_c, x_c = args
                    recv_b, recv_a = exchange_staged(prev_b, prev_a)
                    y, invalid = consume(y, invalid, recv_b, recv_a)
                    send_b, send_a, ov = produce(a_c, n_c, x_c)
                    return (y, overflow + ov, invalid, send_b, send_a), None

                xs = (ap.reshape(nchunks, B), np_.reshape(nchunks, B),
                      xp.reshape((nchunks, B) + tail).astype(dtype))
                if not pipe:
                    init = jax.lax.pcast(
                        (jnp.zeros((M,) + tail, dtype),
                         jnp.zeros((), jnp.int64),
                         jnp.zeros((), jnp.int64)),
                        SHARD_AXIS, to="varying")
                    (y, overflow, invalid), _ = jax.lax.scan(chunk, init, xs)
                else:
                    # prologue slot: an all-SENTINEL/zero in-flight chunk —
                    # its receive side is fully masked, so consuming it
                    # adds exact zeros to the all-+0.0 initial y (no bit
                    # can change) and counts nothing
                    init = jax.lax.pcast(
                        (jnp.zeros((M,) + tail, dtype),
                         jnp.zeros((), jnp.int64),
                         jnp.zeros((), jnp.int64),
                         jnp.full(D * Cap, SENTINEL_STATE),
                         jnp.zeros((D * Cap,) + tail, dtype)),
                        SHARD_AXIS, to="varying")
                    (y, overflow, invalid, last_b, last_a), _ = \
                        jax.lax.scan(chunk_pipe, init, xs)
                    # epilogue: the last chunk's exchange drains here
                    recv_b, recv_a = exchange_staged(last_b, last_a)
                    y, invalid = consume(y, invalid, recv_b, recv_a)
                # cross-shard totals so every shard reports the same counters
                overflow = jax.lax.psum(overflow, SHARD_AXIS)
                invalid = jax.lax.psum(invalid, SHARD_AXIS)
                return y[None], overflow[None], invalid[None]

            def apply_fn(x, operands):
                alphas, norms, diag, tables, lk_pair, lk_dir = operands
                f = jax.shard_map(
                    shard_body, mesh=mesh,
                    in_specs=(_pspec(x.ndim), _pspec(2), _pspec(2), P(),
                              _pspec(3), _pspec(2)),
                    out_specs=(_pspec(x.ndim), _pspec(1), _pspec(1)),
                )
                y, overflow, invalid = f(x.astype(dtype), alphas, norms,
                                         tables, lk_pair, lk_dir)
                d = diag.astype(dtype)
                y = y + d.reshape(d.shape + (1,) * (x.ndim - 2)) \
                    * x.astype(dtype)
                return y, overflow[0], invalid[0]

            return apply_fn

        base_B = self.batch_size
        apply_fn = make_program(base_B, self._capacity)
        self._apply_fn = apply_fn
        self._operands = (self._alphas, self._norms, self._diag, self.tables,
                          self._lk_pair, self._lk_dir)
        programs = {base_B: jax.jit(apply_fn)}
        capacities = {base_B: self._capacity}

        def run(x):
            # Batches ride the same program: the routing (hash/argsort/
            # all_to_all index side) is shared across columns, so a
            # k-column apply costs one exchange with k× payload instead of
            # k full applies.  WIDE batches shrink the row chunk so the
            # per-chunk working set (amps [B, T, k] + exchange buffers
            # [2·D·Cap·k]) stays within ~4× a single apply's footprint —
            # fused mode exists precisely for bases that crowd HBM.
            tl = 1 if is_pair else 0
            k = x.shape[2] if x.ndim == 3 + tl else 1
            B = base_B if k <= 4 else min(
                base_B, _round_up(max(8, (4 * base_B) // k), 8))
            if B not in programs:
                capacities[B] = self._fused_capacity(B)
                programs[B] = jax.jit(make_program(B, capacities[B]))
            # matvec() validates counters once per program key, with THIS
            # program's capacity in any overflow report
            self._last_program_key = B
            self._last_capacity = capacities[B]
            return programs[B](x, self._operands)

        return run

    # ------------------------------------------------------------------
    # Public API
    # ------------------------------------------------------------------

    def to_hashed(self, x) -> jax.Array:
        """Block (global sorted) → hashed layout, device-sharded.

        For a pair-mode engine, complex input is converted to (re, im)-f64
        pair form on the host (trailing axis 2) before sharding.
        """
        x = np.asarray(x)
        if self.pair and np.iscomplexobj(x):
            x = K.pair_from_complex(x)
        xh = self._require_layout().to_hashed(x, fill=0)
        return jax.device_put(jnp.asarray(xh), shard_spec(self.mesh, xh.ndim))

    def from_hashed(self, xh) -> np.ndarray:
        if (isinstance(xh, jax.Array) and jax.process_count() > 1
                and not xh.is_fully_addressable):
            # multi-controller: the hashed array spans other processes'
            # devices — allgather the global value (DCN) before the host
            # unshuffle, the H2B role of arrFromHashedToBlock
            # (HashedToBlock.chpl:67-153)
            from jax.experimental import multihost_utils
            xh = multihost_utils.process_allgather(xh, tiled=True)
        return self._require_layout().from_hashed(np.asarray(xh))

    def random_hashed(self, seed: int = 0, cols: Optional[int] = None):
        """A normalized random vector — or, with ``cols``, a [D, M, cols]
        block of per-column-normalized vectors — directly in hashed layout
        (pads zero).  Generated per shard (deterministic in
        (seed, shard)), so a shard-native engine never touches a global
        array; norms are device reductions over the sharded axes.  This is
        the ONE home of the per-shard seeding/pad-zero invariants — block
        consumers (LOBPCG start blocks) use ``cols`` rather than
        re-deriving them."""
        D, M = self.n_devices, self.shard_size
        tail = ((cols,) if cols else ()) + ((2,) if self.pair else ())
        rows = [None] * D
        for d in range(D):
            if not self._shard_addressable(d):
                continue
            rng = np.random.default_rng(
                np.random.SeedSequence((seed, d)))
            c = int(self.counts[d])
            x = np.zeros((M,) + tail)
            x[:c] = rng.standard_normal((c,) + tail)
            rows[d] = x
        xh = self._assemble_sharded(rows)
        if cols is None:
            nrm = jax.jit(lambda a: jnp.sqrt(jnp.sum(a * a)))(xh)
            return jax.jit(jnp.divide)(xh, nrm)
        ax = (0, 1, 3) if self.pair else (0, 1)

        def col_norm(a):
            return jnp.sqrt(jnp.sum(a * a, axis=ax, keepdims=True))

        nrm = jax.jit(col_norm)(xh)
        return jax.jit(jnp.divide)(xh, nrm)

    def state_keyed_hashed(self, salt: int = 0):
        """Deterministic probe vector keyed by STATE VALUE, not shard slot.

        ``x[state] = hash64(state XOR salt)/2⁶⁴ − ½`` — a pure function of
        the basis state, so two engines over the same sector on DIFFERENT
        mesh sizes (or shard partitions, e.g. an 8-shard file vs its
        :func:`~..enumeration.sharded.reshard_shards` 4-shard copy) hold
        the identical global vector.  That makes cross-mesh invariants
        (⟨x, Hx⟩, ‖Hx‖) directly comparable — the verification probe for
        scale runs where no global array can exist.  Pads are zero; pair
        engines get an independent imaginary part (salt+1)."""
        from ..enumeration.host import hash64, shard_index

        D, M = self.n_devices, self.shard_size
        tail = (2,) if self.pair else ()

        def keyed(reps, s):
            with np.errstate(over="ignore"):     # u64 wrap is the point
                mix = np.uint64(0x9E3779B97F4A7C15) * np.uint64(s + 1)
            h = hash64(reps ^ mix)
            return h.astype(np.float64) / 2.0 ** 64 - 0.5

        if self._shards_path is None:
            reps_global = self.operator.basis.representatives
            owners = shard_index(reps_global, D)
        rows = [None] * D
        for d in range(D):
            if not self._shard_addressable(d):
                continue
            if self._shards_path is not None:
                from ..enumeration.sharded import load_shard
                reps = load_shard(self._shards_path, d)[0]
            else:
                reps = reps_global[owners == d]
            x = np.zeros((M,) + tail)
            if self.pair:
                x[: reps.size, 0] = keyed(reps, salt)
                x[: reps.size, 1] = keyed(reps, salt + 1)
            else:
                x[: reps.size] = keyed(reps, salt)
            rows[d] = x
        return self._assemble_sharded(rows)

    def matvec(self, xh, check: Optional[bool] = None) -> jax.Array:
        """y = H·x in hashed layout ([D, M] or [D, M, k]).

        First call (or ``check=True``) validates the overflow and
        invalid-state counters — the loud-failure analogs of the reference's
        blocking buffers and halt (DistributedMatrixVector.chpl:113-118).

        A device out-of-memory failure surfaces as a typed
        :class:`~..obs.memory.OomError` with the memory-forensics report
        attached; with the obs layer off the original error propagates
        untouched.
        """
        try:
            return self._matvec_impl(xh, check)
        except Exception as e:
            oom_reraise(e, engine="distributed", mode=self.mode,
                        phase="apply", n_states=int(self.n_states))

    def _matvec_impl(self, xh, check: Optional[bool] = None) -> jax.Array:
        # apply span: every event this apply emits (matvec_apply,
        # apply_phases, chunk spans, health probes) attributes to it —
        # pure host bookkeeping, the apply program is byte-identical with
        # tracing on or off (guard-tested by `make trace-check`)
        with obs_trace.span("apply", kind="apply", engine="distributed",
                            mode=self.mode, apply=self._apply_idx):
            return self._matvec_body(xh, check)

    def _matvec_body(self, xh, check: Optional[bool] = None) -> jax.Array:
        # §30 safe boundary: a drift-scheduled re-tune lands HERE, before
        # any of this apply's device work — the plan is never mutated
        # mid-apply, and the re-key wall never pollutes the apply wall
        if self._retune_pending is not None:
            self.maybe_retune()
        # sampled continuous profiling: every profile_every-th apply runs
        # inside a bounded jax.profiler trace window (obs/profile.py);
        # off-mode is a single branch and the apply program is untouched
        # either way — the profiler observes, it never rewrites
        with obs_profile.sample_window("distributed", self._apply_idx):
            return self._matvec_inner(xh, check)

    def _matvec_inner(self, xh, check: Optional[bool] = None) -> jax.Array:
        # telemetry measures eager *dispatch* wall time only (async queue —
        # NO block_until_ready here: recording must never add a sync)
        _t0 = time.perf_counter()
        with self.timer.scope("matvec"):
            xh = jnp.asarray(xh)
            if self.pair and (xh.ndim not in (3, 4) or xh.shape[-1] != 2):
                raise ValueError(
                    f"pair-mode engine expects hashed [D, M, 2] or "
                    f"[D, M, k, 2] (re, im) f64 vectors, got {xh.shape}"
                )
            raise_deferred_failure(self)
            # chaos site for the exchange dispatch: fires BEFORE any device
            # work, so an injected "failed collective" leaves the engine
            # state intact — the next apply (a supervisor relaunch, or a
            # caller's retry) runs clean
            faults.check("exchange", exc=RuntimeError, engine="distributed")
            y, overflow, invalid = self._matvec(xh)
            key = self._last_program_key
            if isinstance(overflow, jax.core.Tracer):
                # called under an outer trace (e.g. lobpcg_standard's
                # while_loop): the counters are abstract.  Validation still
                # happens — at RUN time on the concrete counters, see
                # ``attach_traced_counter_check``.  The shipped solvers run
                # an eager probe first (key already in ``_checked``),
                # paying zero overhead; only never-probed program keys get
                # the per-call callback.
                if check is not False and key not in self._checked:
                    attach_traced_counter_check(
                        self,
                        "DistributedEngine.matvec traced before any eager "
                        "call with this program key: overflow/invalid "
                        "counter validation runs via jax.debug.callback "
                        "at execution time instead of raising inline; run "
                        "one eager matvec first to validate up front",
                        lambda o, i: self._validate_counters(o, i, key),
                        lambda: self._checked.add(key),
                        (overflow, invalid))
                return y
            if check or (check is None and key not in self._checked):
                self._validate_counters(int(overflow), int(invalid), key)
                self._checked.add(key)
            # health: drain scalars parked by PREVIOUS applies (their device
            # work has been consumed — a ready-buffer copy, not a sync),
            # queue this apply's on-device overflow/invalid counters (fused
            # mode computes them anyway; they ride the result transfer), and
            # every health_every-th apply piggyback one fused NaN/Inf + norm
            # reduction on y (a separate tiny program — the apply program is
            # byte-identical with probes on or off)
            obs_health.drain()
            idx = self._apply_idx
            self._apply_idx += 1
            if self.mode in ("fused", "streamed", "hybrid"):
                # streamed counters are the build-time structural totals —
                # constant per plan, but the obs series must stay visible
                # (zero being the healthy reading) exactly as in fused mode
                obs_health.defer_exchange_counters("distributed", idx,
                                                   overflow, invalid)
            if obs_health.probe_due(idx):
                obs_health.probe_apply("distributed", y, idx)
                if self.mode in ("streamed", "hybrid") \
                        and self._compress in ("f32", "bf16"):
                    # lossy-tier drift sample rides the same cadence: a
                    # solve-long compress_rel_err series catches the
                    # accumulation the one-shot compress-check gate can't
                    self._probe_compress_drift(xh, idx)
            if obs_memory.watermark_due(idx):
                obs_memory.sample_watermark("apply/distributed", apply=idx)
        dt_ms = (time.perf_counter() - _t0) * 1e3
        if obs_enabled():
            # one rank-tagged event per eager apply: the raw material of
            # the cross-rank straggler report (merge aligns these across
            # ranks by `apply`; time-at-barrier = max − this rank's ts)
            nbytes = self._exchange_nbytes(xh)
            counter("exchange_bytes", engine="distributed").inc(nbytes)
            emit("matvec_apply", engine="distributed", apply=idx,
                 wall_ms=round(dt_ms, 4), bytes=nbytes)
            if obs_phases.phases_enabled():
                tail_elems = 1
                for s in xh.shape[2:]:
                    tail_elems *= int(s)
                k = tail_elems // 2 if self.pair else tail_elems
                timeline = measured = pipe = None
                if self.mode in ("streamed", "hybrid"):
                    timeline = self._stream_timeline or None
                    self._stream_timeline = []
                    if timeline:
                        measured = {"plan_h2d": sum(
                            c.get("stall_ms", 0.0) for c in timeline)}
                if self.pipeline_depth:
                    # the measured overlap/time-at-barrier split of a
                    # pipelined apply (DESIGN.md §25): barrier_ms = host
                    # wall EXPOSED waiting on plan staging (the consume
                    # waits), hidden_ms = staging work the prefetch
                    # workers ran behind chunk compute, overlap_fraction =
                    # the hidden share.  The exchange programs' dispatch
                    # walls ride as the measured `exchange` phase — an
                    # exchange beating its bound renders `hidden` in the
                    # roofline report, i.e. overlap working (§22).
                    pipe = {"depth": int(self.pipeline_depth)}
                    if timeline:
                        barrier = sum(c.get("stall_ms", 0.0)
                                      for c in timeline)
                        # a chunk's hidden work is the part of its fetch
                        # wall the consumer did NOT wait out — a fully
                        # exposed fetch (stall ≈ stage) hid nothing, and
                        # must not report overlap_fraction ≈ 0.5
                        hidden = sum(max(c.get("stage_ms", 0.0)
                                         - c.get("stall_ms", 0.0), 0.0)
                                     for c in timeline)
                        measured["exchange"] = sum(
                            c.get("exch_ms", 0.0) for c in timeline)
                        pipe.update(
                            barrier_ms=barrier, hidden_ms=hidden,
                            overlap_fraction=(
                                max(0.0, min(1.0,
                                             hidden / (hidden + barrier)))
                                if hidden + barrier > 0 else None))
                obs_phases.emit_apply_phases(
                    "distributed", self.mode, idx, dt_ms,
                    self._phase_counts(tail_elems), chunks=self._nchunks(),
                    columns=max(k, 1), measured_ms=measured,
                    chunk_timeline=timeline, pipeline=pipe)
                if self._tuner is not None:
                    # tune=live: the same walls the phases event records
                    # feed the rate posterior; a drift past DRIFT_BAND
                    # comes back as a proposal that waits for the next
                    # safe boundary.  Window boundaries are deterministic
                    # in apply count, so every rank joins the agreement
                    # round at the same apply.
                    prop = self._tuner.observe(
                        self._phase_counts(tail_elems), dt_ms, measured)
                    if self._tuner.window_closed and self._multi:
                        prop = self._agree_retune(prop)
                    if prop is not None:
                        self._retune_pending = prop
        histogram("matvec_apply_ms", engine="distributed").observe(dt_ms)
        return y

    def _nchunks(self) -> int:
        """Row chunks one apply streams through (1 for the single-program
        ell/compact plans)."""
        if self.mode in ("streamed", "hybrid"):
            return int(self._plan_nchunks_v)
        if self.mode == "fused":
            B = self._last_program_key or self.batch_size
            return -(-self.shard_size // max(int(B), 1))
        return 1

    def _phase_counts(self, tail_elems: int) -> dict:
        """Structural per-apply counts per phase (``obs/phases.py``
        taxonomy), this rank's addressable shards only — pure functions of
        the plan geometry the engine already knows, cached per
        (mode, program, tail), exact by construction (pinned in
        ``tests/test_phases.py``):

        * ``plan_h2d``   streamed mode's per-apply plan bytes (one full
          stream per ≤4-column group — the k>4 re-stream policy);
        * ``compute``    x gathers per structure entry (+ the send-side
          ``x[qin]`` gather in ell/compact where the exchange is packed;
          the orbit scan in fused);
        * ``exchange``   exactly :meth:`_exchange_nbytes`'s send volume;
        * ``accumulate`` receive-side ``segment_sum`` slots (fused and
          streamed) or the two-level tail scatter rows (ell/compact).
        """
        key = (self.mode, self._last_program_key, int(tail_elems))
        cache = getattr(self, "_phase_count_cache", None)
        if cache is None:
            cache = self._phase_count_cache = {}
        got = cache.get(key)
        if got is not None:
            return got
        D, M, T = self.n_devices, self.shard_size, self.num_terms
        nmy = self._n_my_shards
        cplx = self.pair or not self.real
        k = max(tail_elems // 2 if self.pair else tail_elems, 1)
        vb = 16 if cplx else 8            # one vector value
        fmul = 8 if cplx else 2           # multiply-add flops per column
        xbytes = self._exchange_nbytes_tail(int(tail_elems))
        c = obs_phases.zero_counts()
        c["exchange"]["bytes"] = xbytes
        if self.mode in ("ell", "compact"):
            pack = self._exchange_counts()["pack_values"]
            tail = self._ell_tail if self.mode == "ell" else self._c_tail
            cfb = (16 if cplx else 8) if self.mode == "ell" else 4 + 8
            g_tail = int(tail[1].shape[1] * tail[1].shape[2]) if tail else 0
            rows_t = int(tail[0].shape[1]) if tail else 0
            g = nmy * (self._ell_T0 * M + g_tail + pack)
            c["compute"] = {"bytes": g * (vb * k + cfb), "gathers": g,
                            "flops": g * k * fmul}
            c["accumulate"] = {"bytes": nmy * rows_t * vb * k,
                               "gathers": nmy * rows_t,
                               "flops": nmy * rows_t * k * (2 if cplx else 1)}
        else:
            nch = self._nchunks()
            Cap = self._last_capacity or self._capacity
            B = self.batch_size if self.mode in ("streamed", "hybrid") \
                else int(self._last_program_key or self.batch_size)
            if self.mode in ("streamed", "hybrid"):
                # the codec sets the apply's real geometry: trimmed
                # exchange capacity, and (compressed tiers) live entries
                # only — the structural counts must match the work the
                # chunk program actually dispatches
                spec = self._codec.spec
                seg = nmy * nch * int(spec["n_recv"])
            else:
                seg = nmy * nch * D * Cap
            c["accumulate"] = {"bytes": seg * vb * k, "gathers": seg,
                               "flops": seg * k * (2 if cplx else 1)}
            ent = nmy * nch * B * T
            if self.mode in ("streamed", "hybrid"):
                if spec["tier"] != "off":
                    ent = nmy * nch * int(spec["n_live"])
                ngroups = -(-k // 4) if k > 4 else 1
                c["plan_h2d"]["bytes"] = int(self.plan_bytes) * ngroups
                if self.mode == "hybrid":
                    # the split's two compute sides, priced separately
                    # (DESIGN.md §28): the decode side is live streamed
                    # entries (each an explicit x[row] gather + multiply),
                    # the recompute side runs the orbit scan on every
                    # (row, recompute-term) pair — the same per-term cost
                    # model the auto split priced, so `obs_report
                    # roofline` shows where the chosen split lands versus
                    # its bound
                    ent_r = nmy * nch * B * self._hyb_n_recompute
                    G = self._hybrid_group_order()
                    c["compute_decode"] = {"bytes": ent * vb * k,
                                           "gathers": ent,
                                           "flops": ent * k * fmul}
                    c["compute_recompute"] = {
                        "bytes": ent_r * vb * k, "gathers": 0,
                        "flops": ent_r * (k * fmul
                                          + G * obs_phases.ORBIT_OPS)}
                else:
                    c["compute"] = {"bytes": ent * vb * k, "gathers": 0,
                                    "flops": ent * k * fmul}
            else:
                grp = getattr(self.operator.basis, "group", None)
                G = max(len(grp), 1) if grp is not None else 1
                c["compute"] = {"bytes": ent * vb * k, "gathers": ent,
                                "flops": ent * (k * fmul
                                                + G * obs_phases.ORBIT_OPS)}
        cache[key] = c
        return c

    def _exchange_nbytes(self, xh) -> int:
        """Estimated per-rank ``all_to_all`` send volume for ONE apply of
        ``xh`` (this rank's addressable shards only).  ELL/compact send
        exactly the padded [D, C] query payload per shard, or the other
        D - 1 shards where the exchange is gathered; fused mode sends
        the fixed-capacity state+amplitude buckets per row chunk."""
        tail_elems = 1
        for s in xh.shape[2:]:
            tail_elems *= int(s)
        return self._exchange_nbytes_tail(tail_elems)

    def _exchange_nbytes_tail(self, tail_elems: int) -> int:
        """:meth:`_exchange_nbytes` from the trailing element count alone
        (shared with the phase accounting, which has no ``xh`` in hand)."""
        D = self.n_devices
        if D <= 1:
            return 0
        nmy = self._n_my_shards
        if self.mode in ("ell", "compact"):
            return (nmy * self._exchange_counts()["send_values"]
                    * tail_elems * 8)
        if self.mode in ("streamed", "hybrid"):
            # amplitudes only: the receive side already holds its layout,
            # so the betas no longer travel (half the fused exchange for
            # real sectors) — at the codec's TRIMMED capacity (== the
            # build capacity for the off tier)
            item = int(jnp.dtype(self._dtype).itemsize)
            cap = int(self._codec.spec["cap_eff"])
            return (nmy * self._plan_nchunks_v * D * cap
                    * tail_elems * item)
        cap = (self._last_capacity if self._last_capacity is not None
               else getattr(self, "_capacity", 0))
        B = self._last_program_key or self.batch_size
        nchunks = -(-self.shard_size // max(B, 1))
        return nmy * nchunks * D * cap * (8 + tail_elems * 8)

    # -- lossy-tier numerical-drift probe ----------------------------------

    #: the probe chunk: the drift sample is a 1-in-N subsample by
    #: construction (one chunk's live plan entries, probe-cadence applies)
    _DRIFT_CHUNK = 0

    def _drift_probe_state(self):
        """Lazy state for the compressed-drift probe: the probe chunk's
        x-row indices, EXACT (lossless-path) coefficients and quantization
        deltas as device-resident arrays for the first addressable shard.
        None when the probe does not apply — non-quantized tier, complex /
        pair sector (the quantized tiers are real-valued), or a
        sidecar-restored raw-fallback plan whose exact f64 coefficients
        are no longer recoverable (dict-coded plans keep the originals as
        the searchsorted key space, so restore still probes)."""
        st = getattr(self, "_drift_state", None)
        if st is not None:
            return st or None       # False sentinel: checked, unavailable
        self._drift_state = False
        codec = getattr(self, "_codec", None)
        if codec is None or codec.spec["tier"] not in ("f32", "bf16") \
                or codec.spec["ckind"] != "real" or self.pair:
            return None
        from ..ops.plan_codec import _quantize
        try:
            per = self._plan_chunk_host(self._DRIFT_CHUNK)
            d = min(per)
            if codec.spec["coeff"] == "dict":
                dec = codec.decode_chunk_host(per[d], d)
                codes = np.asarray(per[d]["coeff"], np.int64)
                exact = codec.dicts[d][codes].real.astype(np.float64)
                rows, dest = dec["row"], dec["dest"]
            else:
                stash = getattr(self, "_drift_raw_ref", None)
                if not stash or d not in stash:
                    log_debug("compress-drift probe unavailable: "
                              "raw-fallback coefficients restored from "
                              "sidecar (exact values not kept)")
                    return None
                rows, exact, dest = stash[d]
            live = np.asarray(dest) < int(codec.spec["n_recv"])
            exact = np.where(live, exact, 0.0)
            delta = _quantize(exact, codec.spec["tier"]) - exact
            self._drift_state = {"d": int(d),
                                 "rows": np.asarray(rows, np.int32),
                                 "exact": exact, "delta": delta,
                                 "dev": {}, "progs": {}}
        except Exception as e:      # a failed probe must not cost the run
            from ..utils.logging import log_warn
            log_warn(f"compress-drift probe disabled: {e!r}")
            return None
        return self._drift_state

    def _probe_compress_drift(self, xh, idx: int) -> None:
        """Dispatch one input-weighted drift sample for a quantized-tier
        streamed apply (probe-cadence only, piggybacking ``health_every``):
        ‖Δc·x[rows]‖ / ‖c·x[rows]‖ over the probe chunk's live entries,
        where Δc is the lossless-vs-quantized coefficient difference.  A
        separate tiny program — the apply HLO is untouched — with the
        scalars parked on the health layer's deferred-fetch queue (no sync
        lands on the hot path)."""
        st = self._drift_probe_state()
        if st is None:
            return
        d = st["d"]
        D = self.n_devices
        xs = None
        for s in xh.addressable_shards:
            i0 = s.index[0]
            start = i0.start or 0
            stop = i0.stop if i0.stop is not None else D
            if start <= d < stop:
                xs = s.data[d - start]
                break
        if xs is None:          # shard moved out of this process's reach
            return
        dev = next(iter(xs.devices()), None)
        ref = st["dev"].get(dev)
        if ref is None:
            # pin the reference arrays next to the shard they probe — a
            # one-time H2D per device, not a per-probe transfer
            ref = st["dev"][dev] = tuple(
                jax.device_put(a, dev) for a in
                (st["rows"], st["exact"], st["delta"]))
        prog = st["progs"].get(xs.shape)
        if prog is None:
            def _drift(xv, rows, exact, delta):
                g = xv[rows]                         # [n] or [n, k]
                if g.ndim == 2:
                    exact, delta = exact[:, None], delta[:, None]
                num = jnp.sqrt(jnp.sum((delta * g) ** 2))
                den = jnp.sqrt(jnp.sum((exact * g) ** 2))
                return num, den
            prog = st["progs"][xs.shape] = jax.jit(_drift)
        num, den = prog(xs, *ref)
        obs_health.defer_compress_drift(
            "distributed", idx, self._compress, self._DRIFT_CHUNK,
            num, den)

    def _validate_counters(self, overflow: int, invalid: int, key) -> None:
        """Raise loudly when the drain counters report lost amplitudes —
        the analog of the reference's blocking-buffer halt
        (DistributedMatrixVector.chpl:113-118)."""
        if overflow:
            cap = (self._last_capacity if self._last_capacity
                   is not None else getattr(self, "_capacity", None))
            raise RuntimeError(
                f"{overflow} amplitudes overflowed the all_to_all "
                f"capacity {cap} (program chunk {key}); raise "
                "remote_buffer_size or all_to_all_capacity_factor"
            )
        if invalid:
            raise RuntimeError(
                f"{invalid} generated amplitudes map outside the "
                "basis — operator does not preserve the chosen sector"
            )


    def matvec_global(self, x) -> np.ndarray:
        """Convenience: block-layout in/out (shuffle → matvec → unshuffle).

        Complex input to a pair-mode engine is converted in and back out, so
        callers see complex128 regardless of the device representation.
        """
        was_complex = self.pair and np.iscomplexobj(x)
        y = self.from_hashed(self.matvec(self.to_hashed(x)))
        return K.complex_from_pair(y) if was_complex else y

    def dot(self, ah, bh):
        """Global ⟨a, b⟩ over hashed vectors (pad slots are zero by invariant).
        The engine-side analog of PRIMME's ``globalSumReal``
        (PRIMME.chpl:267-311) — XLA turns the sum over the sharded axis into
        a psum over ICI.

        For a pair-mode engine the full *complex* inner product is returned
        (as a Python complex): Re = Σ(a_re·b_re + a_im·b_im),
        Im = Σ(a_re·b_im − a_im·b_re) — both pure-f64 device reductions.
        """
        ah, bh = jnp.asarray(ah), jnp.asarray(bh)
        if self.pair:
            re = jnp.vdot(ah, bh)
            im = jnp.vdot(ah[..., 0], bh[..., 1]) \
                - jnp.vdot(ah[..., 1], bh[..., 0])
            return complex(float(re), float(im))
        return jnp.vdot(ah, bh)

    def __call__(self, xh):
        return self.matvec(xh)

    def bound_matvec(self):
        """(apply_fn, operands) — the matvec as a pure function of
        ``(x, operands)``; see :meth:`LocalEngine.bound_matvec` for the
        jit-composition contract (no large closure constants).

        A streamed engine has no single traceable apply program — its
        matvec is a host-driven stream of per-chunk programs over the
        host-resident plan — so tracing it into an outer jit (the
        single-vector Lanczos block runner, LOBPCG) is refused: use
        :func:`~..solve.lanczos.lanczos_block`, whose eager block applies
        stream each plan chunk once per k-column block."""
        if self.mode in ("streamed", "hybrid"):
            raise NotImplementedError(
                f"{self.mode} engines cannot be traced into an outer jitted "
                "program (the plan lives in host RAM and streams per "
                "apply); drive them with the EAGER solver family instead — "
                "solve.lanczos_block (eigenpairs, one multi-RHS block "
                "apply at a time, thick-restartable via max_basis_size), "
                "solve.kpm (Chebyshev/KPM spectral densities), "
                "solve.evolve (Krylov exp(-iHt) time evolution) — each "
                "streams the plan once per eager apply")
        return self._apply_fn, self._operands

    def structure_arrays(self) -> dict:
        """The live precomputed plan/structure arrays by name (empty in
        fused mode) — the single enumeration the memory ledger registers
        and :attr:`ell_nbytes` sums, parity-tested per mode.  Includes the
        static routing plan (``qin``) the apply's pack gathers from, where
        the exchange is packed; compact mode's derived norm tables count
        too (they were silently missing from the hand-maintained total
        before)."""
        if self.mode == "ell":
            out = {"idx": self._ell_idx, "coeff": self._ell_coeff}
            if self._qin is not None:
                out["qin"] = self._qin
            if self._ell_tail is not None:
                rows, t_idx, t_cf = self._ell_tail
                out.update(tail_rows=rows, tail_idx=t_idx, tail_coeff=t_cf)
            return out
        if self.mode == "compact":
            out = {"idx": self._c_idx, "qin": self._qin,
                   "inv_n": self._c_inv_n, "n_parts": self._c_n_parts,
                   "norms_all": self._c_norms}
            if self._c_tail is not None:
                rows, t_idx = self._c_tail
                out.update(tail_rows=rows, tail_idx=t_idx)
            return out
        return {}

    def memory_arrays(self) -> dict:
        """Every resident device-array group by ledger name (fused mode
        carries the per-shard lookup instead of structure tables)."""
        out = {"operator_tables": self.tables,
               "basis_rows": (self._alphas, self._norms),
               "diag": self._diag}
        if self.mode in ("fused", "streamed", "hybrid"):
            out["lookup"] = (self._lk_pair, self._lk_dir)
        for name, arrs in self.structure_arrays().items():
            out[f"structure/{name}"] = arrs
        return out

    def apply_memory_analysis(self, xh=None) -> Optional[dict]:
        """Compile-time memory analysis of the apply program for ``xh``'s
        shapes (a zero hashed vector by default) — see
        :meth:`LocalEngine.apply_memory_analysis`.  None for streamed
        engines: the apply is a host-driven program sequence, not one
        compiled executable."""
        if self.mode in ("streamed", "hybrid"):
            return None
        if xh is None:
            shape = (self.n_devices, self.shard_size) \
                + ((2,) if self.pair else ())
            xh = jnp.zeros(shape, self._dtype)  # f64, or c128 native-complex
        return analyze_bound_apply(self, "distributed", xh)

    @property
    def ell_nbytes(self) -> int:
        """Device memory held by the precomputed plan structure (0 in
        fused mode) — the summed ``nbytes`` of the live
        :meth:`structure_arrays` leaves."""
        return sum(int(a.nbytes) for a in jax.tree_util.tree_leaves(
            self.structure_arrays()))
