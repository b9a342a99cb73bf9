"""Device-memory observability: ledger, watermarks, executable analysis,
OOM forensics.

Fourth pillar of the telemetry subsystem (see ``obs/__init__``).  The whole
engine design pivots on HBM headroom — mode selection (ell vs fused), batch
widths, and "will this basis fit?" all come down to bytes — yet before this
module the only signals were hand-estimated comments and trial-and-OOM.
Four producers report through here:

* **Ledger** (:func:`track` / :func:`ledger_tree` / :func:`emit_ledger`):
  a process-wide registry of named allocations.  Engines, the distributed
  plan stream, solvers, and the artifact loader register what they hold
  (ELL/fused tables, double-buffer slots, Krylov workspace, staged exchange
  buffers) under ``/``-separated attribution paths; the tree rolls totals
  up per component and is emitted as ``memory_ledger`` events.  Entries are
  *live*: :meth:`Handle.release` (or the owner being garbage-collected,
  via ``weakref.finalize``) removes them.  An entry made from arrays
  (:func:`track_tree`) also records the bytes EACH device holds
  (``per_device``, from the arrays' shardings), beside the global total: a
  quarter each for a buffer sharded over four chips.
* **Watermark sampler** (:func:`sample_watermark` / :func:`watermark_due`):
  polls ``device.memory_stats()`` around engine init, at the end of every
  build pass, at the solver's block boundaries, and every
  ``memory_every``-th apply, publishing ``hbm_bytes_in_use`` /
  ``hbm_peak_bytes`` gauges and ``memory_watermark`` events.  A sample
  names the fullest device's own row (``fullest``), what the ledger holds
  on that device (``ledger_bytes``, ``ledger``) and whether the device
  had been waited for (``synced``), so that one event says how much is
  resident and owned by nobody; its envelope's ``span_id`` names the span
  it was taken in.  Backends without stats (the CPU client returns
  ``None``) soft-fail once and stay silent — the ledger and executable
  analysis remain the advisory sources there.
* **Compiled-executable analysis** (:func:`record_executable_analysis`):
  captures ``compiled.memory_analysis()`` (argument / output / temp /
  generated-code bytes) for every AOT-cached executable at compile time,
  emits it as ``memory_analysis`` events, and stores a JSON sidecar next
  to the XLA artifact cache so predicted-vs-measured peak is diffable
  across runs.
* **OOM forensics** (:func:`attach_oom` / :class:`OomError`): engine
  build/apply errors carrying ``RESOURCE_EXHAUSTED`` gain a structured
  :class:`MemoryReport` (ledger tree + last watermark + executable
  analyses + remediation suggestions), emitted as a critical
  ``memory_report`` event and re-raised as the typed :class:`OomError`.

Disabled-path contract (the PR-2 guard, extended): with ``DMT_OBS=off``
every producer is a no-op — :func:`track` returns the shared
:data:`NULL_HANDLE`, :func:`watermark_due` is False, analyses record
nothing, and :func:`attach_oom` returns ``None`` so the original error
propagates untouched.  All the hot-path hooks live on the error path or
behind a cadence check; the apply program itself never changes.
"""

from __future__ import annotations

import json
import os
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from ..utils.config import get_config
from ..utils.logging import log_debug, log_warn
from .events import emit, obs_enabled
from .metrics import counter, gauge

__all__ = [
    "Handle",
    "NULL_HANDLE",
    "track",
    "track_tree",
    "ledger_entries",
    "ledger_tree",
    "ledger_total",
    "ledger_on",
    "host_rss_bytes",
    "emit_ledger",
    "next_instance",
    "sample_watermark",
    "watermark_due",
    "last_watermark",
    "record_executable_analysis",
    "executable_analyses",
    "MemoryReport",
    "OomError",
    "is_resource_exhausted",
    "build_memory_report",
    "remediation",
    "attach_oom",
    "reset_memory",
]


# ---------------------------------------------------------------------------
# ledger

_lock = threading.Lock()
_ledger: Dict[str, dict] = {}           # path -> entry dict (insertion order)
_instances: Dict[str, int] = {}         # per-kind engine/solver counters

#: Finalizer-safe deferred releases.  Handle.release is the target of the
#: engines' ``weakref.finalize``, which the garbage collector may run in
#: the MIDDLE of a ledger operation on the same thread (any allocation
#: inside a ``with _lock:`` block can trigger a collection) — taking the
#: non-reentrant ``_lock`` there deadlocks the process (observed: an
#: engine finalizer firing inside ``ledger_entries``'s snapshot
#: comprehension froze the whole test suite).  So release never locks: it
#: queues its paths on this list (``list.append`` is atomic under the
#: GIL, and the GC never starts a nested collection from a finalizer),
#: and every locked ledger operation drains the queue first.
_released: List[List[str]] = []


def _drain_released_locked() -> None:
    """Apply queued finalizer releases; the caller holds ``_lock``."""
    while _released:
        for p in _released.pop():
            _ledger.pop(p, None)


@dataclass
class Handle:
    """A live ledger registration; :meth:`release` removes every path this
    handle owns (idempotent).  :meth:`set` re-points one path's byte count
    — growing workspaces (block-Lanczos bases) update in place instead of
    re-registering."""

    paths: List[str] = field(default_factory=list)

    def set(self, path: str, nbytes: int) -> None:
        with _lock:
            _drain_released_locked()
            ent = _ledger.get(path)
            if ent is not None:
                ent["bytes"] = int(nbytes)
                # the split by device was of the old size
                ent.pop("per_device", None)

    def release(self) -> None:
        # GC-safe by construction: NO lock here (see ``_released``)
        paths, self.paths = self.paths, []
        if paths:
            _released.append(paths)


class _NullHandle(Handle):
    """Shared no-op handle returned when the layer is disabled."""

    __slots__ = ()

    def __init__(self):
        super().__init__(paths=[])

    def set(self, path, nbytes):
        pass

    def release(self):
        pass


NULL_HANDLE = _NullHandle()


def next_instance(kind: str) -> str:
    """A readable unique attribution id for one engine/solver instance
    (``local:0``, ``distributed:1``, ...) — ledger paths must not collide
    when a process holds several engines of the same kind."""
    with _lock:
        i = _instances.get(kind, 0)
        _instances[kind] = i + 1
    return f"{kind}:{i}"


def track(path: str, nbytes: int, device: str = "",
          handle: Optional[Handle] = None,
          per_device: Optional[Dict[str, int]] = None, **meta) -> Handle:
    """Register one named allocation under a ``/``-separated attribution
    path (``engine/local:0/structure/idx``).  Re-tracking an existing path
    replaces it (a rebuilt table supersedes the old entry).  Returns the
    handle owning the registration (pass ``handle=`` to accumulate several
    paths under one owner).  ``per_device`` is the split of ``nbytes`` by
    device (``{"tpu:0": ...}``, the names of a watermark's ``devices``
    rows); an entry without one counts in full on every device
    (:func:`ledger_on`)."""
    if not obs_enabled():
        return NULL_HANDLE
    h = handle if handle is not None else Handle()
    ent = {"bytes": int(nbytes), "device": str(device)}
    if per_device is not None:
        ent["per_device"] = {str(d): int(b) for d, b in per_device.items()}
    for k, v in meta.items():
        ent[k] = v
    with _lock:
        _drain_released_locked()
        _ledger[path] = ent
        if path not in h.paths:
            h.paths.append(path)
    return h


def _device_name(dev) -> str:
    """A device as the ledger's ``per_device`` and a watermark's
    ``devices`` rows name it: the two are joined on it."""
    return f"{dev.platform}:{dev.id}"


def _tree_bytes(tree):
    """``(total, per_device)`` of a pytree of arrays: the summed global
    ``nbytes`` and what each local device holds of them, by the arrays'
    own shardings: a shard's shape on every device the sharding names (a
    replicated array counts in full on each).  Read off the sharding and
    not off the shards' buffers, which would materialise an array object a
    shard.  ``per_device`` is None where a leaf is no device array: the
    split is then not known."""
    import math

    import jax

    total, per, known = 0, {}, True
    for leaf in jax.tree_util.tree_leaves(tree):
        total += int(getattr(leaf, "nbytes", 0))
        sharding = getattr(leaf, "sharding", None)
        if sharding is None:
            known = False
            continue
        held = math.prod(sharding.shard_shape(leaf.shape)) \
            * leaf.dtype.itemsize
        for dev in sharding.addressable_devices:
            name = _device_name(dev)
            per[name] = per.get(name, 0) + int(held)
    return total, (per if known else None)


def track_tree(path: str, tree, device: str = "",
               handle: Optional[Handle] = None, **meta) -> Handle:
    """Register a pytree of arrays under one path (the engines' table
    bundles are pytrees): the summed ``nbytes``, and the bytes each device
    holds of it (``per_device``; all of them on the one device of a
    one-chip run)."""
    if not obs_enabled():
        return NULL_HANDLE
    try:
        total, per = _tree_bytes(tree)
    except Exception:
        total, per = int(getattr(tree, "nbytes", 0)), None
    return track(path, total, device=device, handle=handle, per_device=per,
                 **meta)


def ledger_entries() -> Dict[str, dict]:
    """Snapshot of the live ledger: {path: {bytes, device, ...meta}}."""
    with _lock:
        _drain_released_locked()
        return {p: dict(e) for p, e in _ledger.items()}


def ledger_tree() -> dict:
    """The live ledger as a nested attribution tree: each node carries the
    rolled-up ``bytes`` of its subtree plus ``children``; leaf nodes keep
    their entry metadata."""
    root = {"bytes": 0, "children": {}}
    for path, ent in ledger_entries().items():
        node = root
        node["bytes"] += ent["bytes"]
        for part in path.split("/"):
            node = node["children"].setdefault(
                part, {"bytes": 0, "children": {}})
            node["bytes"] += ent["bytes"]
        for k, v in ent.items():
            if k != "bytes":
                node[k] = v
    return root


def ledger_total(prefix: Optional[str] = None,
                 device: Optional[str] = None) -> int:
    """Total live bytes, optionally restricted to paths under ``prefix``
    and/or to one ``device`` class (``"host"`` for host-RAM entries like
    the streamed engine's plan; ``"device"`` for HBM-resident arrays)."""
    total = 0
    for path, ent in ledger_entries().items():
        if prefix is not None and path != prefix \
                and not path.startswith(prefix + "/"):
            continue
        if device is not None and ent.get("device") != device:
            continue
        total += ent["bytes"]
    return total


def ledger_on(device_name: str) -> Dict[str, int]:
    """What the live ledger holds on one device (a name as in a
    watermark's ``devices`` rows), by the first part of the path
    (``engine``, ``solver``, ``plan``, ...).  Host-RAM entries
    (``device="host"``) are left out; an entry that recorded no split by
    device counts in full, which is what it holds on the one device of a
    one-chip run."""
    out: Dict[str, int] = {}
    for path, ent in ledger_entries().items():
        if ent.get("device") == "host":
            continue
        per = ent.get("per_device")
        held = ent["bytes"] if per is None else per.get(device_name, 0)
        root = path.split("/", 1)[0]
        out[root] = out.get(root, 0) + int(held)
    return out


def host_rss_bytes() -> int:
    """This process's current resident-set size in bytes (0 when the
    platform exposes none) — the host-RAM watermark companion to the
    device ``memory_stats()`` sampler, read by the streamed engine's plan
    accounting and the OOM forensics report.  Proc-based (no psutil
    dependency); soft-fails to 0 anywhere /proc is absent."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        return 0


def emit_ledger(context: str, **fields) -> Optional[dict]:
    """One ``memory_ledger`` event: the current attribution tree + total
    plus caller context (engines pass mode / sizes / T0 so the capacity
    planner can work from the snapshot alone)."""
    if not obs_enabled():
        return None
    return emit("memory_ledger", context=str(context),
                total_bytes=int(ledger_total()),
                entries=ledger_entries(), **fields)


# ---------------------------------------------------------------------------
# watermark sampler

_wm_lock = threading.Lock()
_wm_unsupported = False       # first None/failing memory_stats() latches
_last_watermark: Optional[dict] = None


def _device_stats() -> Optional[List[dict]]:
    """Per-local-device ``memory_stats()`` rows, or None when the backend
    exposes none (latched after the first miss so the per-apply cadence
    never re-pays a failing query)."""
    global _wm_unsupported
    if _wm_unsupported:
        return None
    try:
        import jax

        rows = []
        for d in jax.local_devices():
            st = d.memory_stats()
            if not st:
                continue
            rows.append({
                "device": _device_name(d),
                "bytes_in_use": int(st.get("bytes_in_use", 0)),
                "peak_bytes_in_use": int(st.get("peak_bytes_in_use", 0)),
                "bytes_limit": int(st.get("bytes_limit", 0)),
            })
    except Exception as e:
        with _wm_lock:
            _wm_unsupported = True
        log_debug(f"device memory_stats unavailable: {e!r}")
        return None
    if not rows:
        with _wm_lock:
            _wm_unsupported = True
        log_debug("device memory_stats unavailable on this backend "
                  "(advisory mode: ledger + executable analysis only)")
        return None
    return rows


def sample_watermark(tag: str, synced: bool = False, wait_for=None,
                     **fields) -> Optional[dict]:
    """Poll device memory and publish one ``memory_watermark`` event plus
    the ``hbm_bytes_in_use`` / ``hbm_peak_bytes`` gauges.  Returns the
    sample dict, or None when the layer is off or the backend has no
    stats (soft-fail: never raises).

    ``bytes_in_use`` is the SUM over the local devices and ``peak_bytes``
    the MAXIMUM, so the two cannot be subtracted on a mesh: ``fullest`` is
    the one row of the device with the largest ``peak_bytes_in_use`` (the
    device a harness's peak is read on), ``ledger`` what the ledger holds
    on that device by first path part (:func:`ledger_on`) and
    ``ledger_bytes`` its sum.  ``synced`` says that the caller has just
    waited for the device in the span the sample is taken in, so that
    ``bytes_in_use`` is what is resident with nothing in flight; with
    ``wait_for`` (arrays) the sampler waits for them itself, after it has
    seen that the backend has stats, and the sample is synced."""
    global _last_watermark
    if not obs_enabled():
        return None
    rows = _device_stats()
    if rows is None:
        return None
    if wait_for is not None:
        import jax

        jax.block_until_ready(wait_for)
        rows, synced = _device_stats() or rows, True
    in_use = sum(r["bytes_in_use"] for r in rows)
    peak = max(r["peak_bytes_in_use"] for r in rows)
    limit = sum(r["bytes_limit"] for r in rows)
    fullest = max(rows, key=lambda r: r["peak_bytes_in_use"])
    held = ledger_on(fullest["device"])
    sample = {"tag": str(tag), "bytes_in_use": in_use,
              "peak_bytes": peak, "bytes_limit": limit, "devices": rows,
              "fullest": dict(fullest), "ledger_bytes": sum(held.values()),
              "ledger": held, "synced": bool(synced)}
    gauge("hbm_bytes_in_use").set(in_use)
    gauge("hbm_peak_bytes").set(peak)
    with _wm_lock:
        _last_watermark = sample
    emit("memory_watermark", **sample, **fields)
    return sample


def watermark_due(apply_index: int) -> bool:
    """Whether eager apply ``apply_index`` should sample a watermark: the
    first and every ``memory_every``-th apply.  Always False when the
    layer is off or the backend already proved statless, so the hot path
    never branches further."""
    if not obs_enabled() or _wm_unsupported:
        return False
    every = max(int(get_config().memory_every), 1)
    return apply_index % every == 0


def last_watermark() -> Optional[dict]:
    """The most recent watermark sample (OOM forensics context), or None."""
    with _wm_lock:
        return dict(_last_watermark) if _last_watermark else None


# ---------------------------------------------------------------------------
# compiled-executable memory analysis

_exec_analyses: Dict[str, dict] = {}

_ANALYSIS_FIELDS = (
    ("argument_bytes", "argument_size_in_bytes"),
    ("output_bytes", "output_size_in_bytes"),
    ("temp_bytes", "temp_size_in_bytes"),
    ("alias_bytes", "alias_size_in_bytes"),
    ("generated_code_bytes", "generated_code_size_in_bytes"),
)


def record_executable_analysis(key: str, compiled,
                               program: Optional[str] = None,
                               **fields) -> Optional[dict]:
    """Capture ``compiled.memory_analysis()`` for one AOT executable
    (``key`` identifies the compiled specialization; ``program`` the
    human-readable program name): stores it in the process registry, emits
    a ``memory_analysis`` event, sets the
    ``executable_temp_bytes{program=...}`` gauge, and writes a JSON
    sidecar next to the XLA artifact cache (all soft-fail).  Returns the
    analysis dict, or None when disabled/unavailable."""
    if not obs_enabled():
        return None
    try:
        ma = compiled.memory_analysis()
    except Exception as e:
        log_debug(f"memory_analysis unavailable for {key}: {e!r}")
        return None
    if ma is None:
        return None
    ana = {"key": str(key), "program": str(program or key)}
    for out_key, attr in _ANALYSIS_FIELDS:
        ana[out_key] = int(getattr(ma, attr, 0) or 0)
    ana["peak_estimate_bytes"] = (ana["argument_bytes"]
                                  + ana["output_bytes"]
                                  + ana["temp_bytes"])
    with _lock:
        _exec_analyses[str(key)] = dict(ana)
    gauge("executable_temp_bytes",
          program=ana["program"]).set(ana["temp_bytes"])
    emit("memory_analysis", **ana, **fields)
    _save_analysis_sidecar(str(key), ana)
    return ana


def _save_analysis_sidecar(name: str, ana: dict) -> None:
    """Persist one analysis next to the XLA artifact cache tree so the
    capacity planner and run-diff can read compile-time memory facts
    without re-running; soft-fail like every other cache write."""
    from ..utils.artifacts import artifact_path, artifacts_enabled

    if not artifacts_enabled():
        return
    try:
        import hashlib

        fp = hashlib.sha256(name.encode()).hexdigest()
        path = artifact_path("xla-analysis", fp, ".json")
        with open(path, "w") as f:
            json.dump(ana, f, sort_keys=True)
    except OSError as e:
        log_debug(f"memory-analysis sidecar save skipped: {e!r}")


def executable_analyses() -> Dict[str, dict]:
    """Snapshot of every captured executable analysis, keyed by program."""
    with _lock:
        return {k: dict(v) for k, v in _exec_analyses.items()}


# ---------------------------------------------------------------------------
# OOM forensics

class OomError(RuntimeError):
    """A device ``RESOURCE_EXHAUSTED`` failure with forensics attached:
    ``.report`` carries the :class:`MemoryReport` dict (ledger tree, last
    watermark, executable analyses, remediation)."""

    def __init__(self, message: str, report: dict):
        super().__init__(message)
        self.report = report


@dataclass
class MemoryReport:
    """Structured OOM forensics: what was resident (ledger), what the
    device said (watermark), what the compiler predicted (analyses), and
    what to try next (remediation)."""

    context: dict
    ledger: dict
    ledger_total_bytes: int
    watermark: Optional[dict]
    executables: Dict[str, dict]
    remediation: List[str]
    host_rss_bytes: int = 0

    def to_dict(self) -> dict:
        return {"context": self.context, "ledger": self.ledger,
                "ledger_total_bytes": self.ledger_total_bytes,
                "watermark": self.watermark,
                "executables": self.executables,
                "remediation": self.remediation,
                "host_rss_bytes": self.host_rss_bytes}


_OOM_MARKERS = ("resource_exhausted", "out of memory", "out-of-memory")


def is_resource_exhausted(exc: BaseException) -> bool:
    """Whether an exception is a device out-of-memory failure.  Matched on
    the message — jaxlib's ``XlaRuntimeError`` carries the gRPC-style
    ``RESOURCE_EXHAUSTED:`` prefix and the allocator says ``Out of
    memory``; matching text keeps this independent of which jaxlib
    exception class this version raises."""
    msg = f"{type(exc).__name__}: {exc}".lower()
    return any(m in msg for m in _OOM_MARKERS)


def remediation(context: dict) -> List[str]:
    """Suggested ways out of the OOM the context describes, most effective
    first.  These are the levers the engines actually expose — the point
    is that the error message names them instead of leaving the operator
    to rediscover the design doc."""
    mode = str(context.get("mode", ""))
    engine = str(context.get("engine", ""))
    phase = str(context.get("phase", ""))
    out = []
    if mode in ("streamed", "hybrid"):
        out.append(
            "set tune=static (DMT_TUNE=static): the autotuner prices the "
            "row-chunk / compress / pipeline / plan-tier cross-product "
            "against the calibrated roofline and picks the cheapest "
            "feasible config — usually the right knobs without hand-tuning")
    if mode in ("ell", "compact"):
        out.append(
            "switch to mode='streamed' (DistributedEngine): the routing "
            "plan lives in host RAM and streams per apply — fused-level "
            "device memory at near-plan-bandwidth apply speed")
        out.append(
            "switch to mode='fused' (recomputes structure per apply: "
            "O(B*T) scratch instead of resident O(N*T0) tables)")
        if mode == "ell":
            out.append(
                "mode='compact' fits isotropic real sectors in 4 B/entry "
                "(~1/3 of the standard ELL tables)")
    if phase == "init":
        out.append(
            "lower ell_build_budget_gb (DMT_ELL_BUILD_BUDGET_GB) to force "
            "the two-pass low-memory build bounded by the packed table "
            "size")
    out.append(
        "lower matvec_batch_size (DMT_MATVEC_BATCH_SIZE): per-chunk "
        "scratch and fused exchange buffers scale with the row chunk")
    out.append(
        "narrow the apply batch (fewer RHS columns per matvec): gather "
        "scratch scales with vec_width")
    if engine == "distributed":
        out.append(
            "add shards (more devices / a larger mesh): per-device table "
            "and vector bytes scale ~1/D")
    else:
        out.append(
            "shard over a mesh with DistributedEngine: per-device bytes "
            "scale ~1/D")
    out.append(
        "run tools/capacity.py against this run's obs stream for "
        "per-mode bytes/row and the max basis size this device fits")
    return out


def build_memory_report(**context) -> MemoryReport:
    """Assemble the forensics snapshot for an OOM (or for inspection)."""
    return MemoryReport(
        context=dict(context),
        ledger=ledger_tree(),
        ledger_total_bytes=ledger_total(),
        watermark=last_watermark(),
        executables=executable_analyses(),
        remediation=remediation(context),
        host_rss_bytes=host_rss_bytes(),
    )


def attach_oom(exc: BaseException, **context) -> Optional[OomError]:
    """OOM forensics entry point for the engines' error paths: when the
    layer is on and ``exc`` is a ``RESOURCE_EXHAUSTED`` failure, emit the
    critical ``memory_report`` event and return a typed :class:`OomError`
    for the caller to ``raise ... from exc``.  Returns None otherwise —
    the caller re-raises the original, so with ``DMT_OBS=off`` this is a
    provable no-op and non-OOM errors are never rewritten."""
    if not obs_enabled() or not is_resource_exhausted(exc):
        return None
    report = build_memory_report(**context)
    rd = report.to_dict()
    counter("oom_events").inc()
    emit("memory_report", level="critical", error=f"{exc}"[:500], **rd)
    try:
        # the flight recorder's OOM trigger: the bundle carries this
        # report plus the open-span stack and the last ring events —
        # lazily imported (flight imports this module the same way)
        from .flight import flight_dump

        flight_dump("oom", error=f"{exc}"[:500], report=rd)
    except Exception:
        pass
    detail = " ".join(f"{k}={v}" for k, v in context.items())
    lines = "\n  - ".join(report.remediation)
    msg = (f"device memory exhausted ({detail}): {exc}\n"
           f"resident per the memory ledger: "
           f"{report.ledger_total_bytes / 1e9:.3f} GB"
           + (f"; last watermark peak "
              f"{report.watermark['peak_bytes'] / 1e9:.3f} GB"
              if report.watermark else "")
           + f"\nremediation:\n  - {lines}")
    log_warn(f"OOM forensics: {detail} "
             f"(ledger {report.ledger_total_bytes / 1e9:.3f} GB resident)")
    return OomError(msg, rd)


# ---------------------------------------------------------------------------


def reset_memory() -> None:
    """Drop ledger, analyses, watermark state and the unsupported latch
    (tests).  The per-kind instance counters are deliberately NOT reset:
    handles (and engine GC finalizers) from before the reset stay live,
    and reusing an instance id would let a stale finalizer release a
    NEW owner's identically-named paths."""
    global _wm_unsupported, _last_watermark
    with _lock:
        _drain_released_locked()
        _ledger.clear()
        _exec_analyses.clear()
    with _wm_lock:
        _wm_unsupported = False
        _last_watermark = None
