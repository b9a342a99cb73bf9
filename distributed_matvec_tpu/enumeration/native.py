"""ctypes loader + driver for the C++ enumeration kernel (``_native.cpp``).

The reference's enumeration is native (Haskell/C kernels called in 10240-state
batches, StatesEnumeration.chpl:158-200) and parallel (dynamic chunking over
tasks, :321-334).  This wrapper:

  * compiles ``_native.cpp`` on first use with g++ for one fixed, named
    target (``_build_command``) and keeps the .so next to the source under
    a name keyed on the source and the command, so a binary that something
    else produced is never loaded; a failed build raises
    :class:`NativeBuildError` with the compiler's stderr,
  * splits the search range into equal-*index*-work chunks via the
    fixed-hamming rank/unrank (``determineEnumerationRanges``,
    StatesEnumeration.chpl:94-113),
  * orders group elements cheap-first (ascending network width) so the
    early-exit orbit scan rejects most candidates after a couple of cheap
    translations before ever touching expensive elements,
  * streams: memory is bounded by the per-chunk survivor buffers, never by
    the candidate count.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import platform
import shutil
import subprocess
import threading
from typing import List, Optional, Tuple

import numpy as np

from . import host as _host

__all__ = ["NativeBuildError", "native_available", "build_info",
           "enumerate_representatives_native",
           "lookup_owners", "full_state_range", "rank_state_ranges"]

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "_native.cpp")
_CXX = "g++"
# One named target per architecture instead of -march=native: the binary is
# built on whatever host first needs it (a sandbox, a TPU VM) and must mean
# the same thing on each.  x86-64-v3 (AVX2/BMI2/POPCNT, Haswell 2013 and
# later) covers every host a TPU is attached to; /proc/cpuinfo is checked
# against _TARGET_CPU_FLAGS before the build so a lesser CPU is an error
# here, not a SIGILL in the scan.
_TARGETS = {"x86_64": "x86-64-v3"}
_TARGET_CPU_FLAGS = ("avx2", "bmi1", "bmi2", "fma", "movbe", "popcnt", "abm")
_lock = threading.Lock()
_lib = None
_lib_error: Optional["NativeBuildError"] = None


class NativeBuildError(RuntimeError):
    """The C++ enumeration kernel could not be built or loaded."""


class _Group(ctypes.Structure):
    _fields_ = [
        ("mask", ctypes.POINTER(ctypes.c_uint64)),
        ("lshift", ctypes.POINTER(ctypes.c_uint64)),
        ("rshift", ctypes.POINTER(ctypes.c_uint64)),
        ("xor_mask", ctypes.POINTER(ctypes.c_uint64)),
        ("char_real", ctypes.POINTER(ctypes.c_double)),
        ("g", ctypes.c_int64),
        ("s", ctypes.c_int64),
    ]


def _build_flags() -> List[str]:
    flags = ["-O3", "-std=c++17", "-shared", "-fPIC"]
    march = _TARGETS.get(platform.machine())
    if march:
        flags.append(f"-march={march}")
    return flags


def _build_command(out: str) -> List[str]:
    return [_CXX, *_build_flags(), "-o", out, _SRC, "-lpthread"]


def _so_path() -> str:
    """The one file name the current source + compiler flags may produce
    (paths stay out of the key: every checkout of one commit agrees)."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join([_CXX, *_build_flags()]).encode())
    return os.path.join(_HERE, f"_native_{h.hexdigest()[:16]}.so")


def _check_cpu() -> None:
    if platform.machine() not in _TARGETS:
        return
    try:
        with open("/proc/cpuinfo") as f:
            line = next((ln for ln in f if ln.startswith("flags")), "")
    except OSError:
        return
    have = set(line.split(":", 1)[-1].split())
    missing = [fl for fl in _TARGET_CPU_FLAGS if fl not in have]
    if have and missing:
        raise NativeBuildError(
            f"this CPU lacks {missing}, which the native enumerator's "
            f"build target -march={_TARGETS[platform.machine()]} assumes")


def _build() -> str:
    so = _so_path()
    if os.path.exists(so):
        return so
    _check_cpu()
    # compile to a temp name and rename: writing the .so in place would
    # clobber the text mapping of any process that already dlopened it
    # (a long-running enumeration would SIGBUS mid-flight)
    tmp = so + f".build{os.getpid()}"
    cmd = _build_command(tmp)
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True,
                       timeout=300)
        os.replace(tmp, so)
    except (OSError, subprocess.SubprocessError) as e:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        stderr = (getattr(e, "stderr", None) or "").strip()
        raise NativeBuildError(
            f"building the native enumerator failed: {e!r}\n"
            f"command: {' '.join(cmd)}\n"
            f"compiler stderr:\n{stderr or '(none)'}\n"
            "The NumPy enumerator is not substituted for it; pick it "
            "explicitly with DMT_ENUMERATION_BACKEND=numpy (small sectors "
            "only).") from e
    # binaries of earlier sources or flags are never loaded again; unlinking
    # one that a running process has mapped is harmless
    for old in glob.glob(os.path.join(_HERE, "_native_*.so")):
        if old != so:
            try:
                os.unlink(old)
            except OSError:
                pass
    return so


def _load():
    """The loaded kernel library; raises :class:`NativeBuildError` (every
    time, without recompiling) once a build or load has failed."""
    global _lib, _lib_error
    with _lock:
        if _lib is not None:
            return _lib
        if _lib_error is not None:
            raise _lib_error
        try:
            lib = ctypes.CDLL(_build())
        except NativeBuildError as e:
            _lib_error = e
            raise
        except OSError as e:
            _lib_error = NativeBuildError(
                f"loading the native enumerator failed: {e!r}")
            raise _lib_error from e
        lib.dmt_enumerate_ranges.restype = ctypes.c_int64
        lib.dmt_enumerate_ranges.argtypes = [
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_int64, ctypes.c_int,
            ctypes.POINTER(_Group), ctypes.c_double,
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
        ]
        lib.dmt_fill_fixed_hamming.restype = ctypes.c_int64
        lib.dmt_fill_fixed_hamming.argtypes = [
            ctypes.c_uint64, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_int64,
        ]
        lib.dmt_lookup_owners.restype = ctypes.c_int64
        lib.dmt_lookup_owners.argtypes = [
            ctypes.POINTER(ctypes.c_uint64), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int,
        ]
        _lib = lib
        return _lib


def native_available() -> bool:
    """Whether this host has the compiler the kernel is built with.  A
    compiler that is present and fails is an error (:func:`_load`), not
    'unavailable'."""
    return shutil.which(_CXX) is not None


def build_info() -> dict:
    """What the environment line of ``chip_smoke.py`` prints: compiler
    path, build command, and whether the kernel for the current source is
    built (building it if need be)."""
    so = _so_path()
    prebuilt = os.path.exists(so)
    _load()
    return {"compiler": shutil.which(_CXX),
            "command": " ".join(_build_command(os.path.basename(so))),
            "result": "loaded existing" if prebuilt else "built"}


def _group_tables_cheap_first(group):
    """Shift/mask tables with elements sorted by network width (identity
    first) — the early-exit scan meets cheap translations before expensive
    reflections."""
    ls, rs, ms, xor = group.shift_mask_tables()
    widths = np.array([(m != 0).sum() for m in ms])
    widths[0] = -1  # identity stays first
    order = np.argsort(widths, kind="stable")
    return (ls[order], rs[order], ms[order], xor[order],
            group.characters.real[order].copy())


def _ranges(lo: int, hi: int, hamming: Optional[int], n_chunks: int):
    """Equal-index-work split of [lo, hi] (determineEnumerationRanges)."""
    if hamming is None or hamming == 0:
        edges = np.linspace(lo, hi + 1, n_chunks + 1, dtype=np.uint64)
        starts = edges[:-1].copy()
        ends = np.maximum(edges[1:], 1) - 1
        keep = starts <= ends
        return starts[keep], ends[keep]
    r_lo = int(_host.fixed_hamming_rank(np.uint64(lo))[0])
    r_hi = int(_host.fixed_hamming_rank(np.uint64(hi))[0])
    total = r_hi - r_lo + 1
    n_chunks = max(1, min(n_chunks, total))
    idx = np.linspace(r_lo, r_hi + 1, n_chunks + 1).astype(np.int64)
    starts, ends = [], []
    for i in range(n_chunks):
        if idx[i] >= idx[i + 1]:
            continue
        starts.append(_host.fixed_hamming_unrank(idx[i], hamming))
        ends.append(_host.fixed_hamming_unrank(idx[i + 1] - 1, hamming))
    return (np.array(starts, dtype=np.uint64), np.array(ends, dtype=np.uint64))


def full_state_range(n_sites: int, hamming_weight: Optional[int]):
    """[lo, hi] of the full candidate range for the sector."""
    lo = (1 << hamming_weight) - 1 if hamming_weight else 0
    hi = (lo << (n_sites - hamming_weight)) if hamming_weight \
        else (1 << n_sites) - 1
    if hamming_weight == 0:
        lo = hi = 0
    return lo, hi


def rank_state_ranges(n_sites: int, hamming_weight: Optional[int],
                      rank: int, n_ranks: int, oversub: int = 64):
    """CYCLIC equal-index-work chunk assignment for one rank of ``n_ranks``
    enumerating processes — the cross-process analog of the reference's
    per-locale dynamic chunk scheduling (StatesEnumeration.chpl:321-334),
    split in fixed-hamming *index* space (determineEnumerationRanges,
    :94-113).

    Equal candidate counts are NOT equal representative counts: canonical
    (orbit-minimal) representatives pile up at numerically small states,
    so one contiguous slice per rank would hand essentially all survivors
    to rank 0 (measured: 4 707 968 of 4 707 969 on chain_32_symm).
    ``oversub``·n_ranks chunks dealt round-robin average the density out
    while keeping each rank's chunk sequence ascending — every rank's
    part file stays internally sorted, and :func:`..sharded.load_shard`
    merge-sorts the per-rank slices.  Returns a (possibly empty) list of
    inclusive (lo, hi) ranges."""
    lo, hi = full_state_range(n_sites, hamming_weight)
    starts, ends = _ranges(lo, hi, hamming_weight, n_ranks * oversub)
    return [(int(s), int(e))
            for i, (s, e) in enumerate(zip(starts, ends))
            if i % n_ranks == rank]


def _stream_native(
    lib,
    n_sites: int,
    hamming_weight: Optional[int],
    group,
    n_chunks: Optional[int] = None,
    n_threads: Optional[int] = None,
    norm_tol: float = 1e-12,
    batch_tasks: int = 256,
    state_ranges=None,
):
    """Generator over (states, norms) survivor slabs in ascending state
    order — the chunk ranges are disjoint and ascending, so concatenating
    the slabs (or routing them anywhere) preserves global sortedness.
    Memory is bounded by one task batch's buffers.

    ``state_ranges=[(lo, hi), ...]`` restricts the scan to the given
    ascending disjoint sub-ranges (inclusive) — the multi-process
    enumeration path hands each rank its cyclic chunk set
    (:func:`rank_state_ranges`)."""
    lo, hi = full_state_range(n_sites, hamming_weight)

    ls, rs, ms, xor, chr_ = _group_tables_cheap_first(group)
    G, S = ms.shape
    grp = _Group(
        ms.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        ls.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        rs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        np.ascontiguousarray(xor).ctypes.data_as(
            ctypes.POINTER(ctypes.c_uint64)),
        np.ascontiguousarray(chr_).ctypes.data_as(
            ctypes.POINTER(ctypes.c_double)),
        G, S,
    )
    # keep the numpy arrays alive for the duration of the call
    keepalive = (ls, rs, ms, xor, chr_)

    n_threads = n_threads or os.cpu_count() or 1
    if n_chunks is None:
        n_chunks = max(4 * n_threads, 64)
    if state_ranges is not None:
        if not state_ranges:
            return
        per = max(1, n_chunks // len(state_ranges))
        parts = [_ranges(rlo, rhi, hamming_weight, per)
                 for rlo, rhi in state_ranges]
        starts = np.concatenate([p[0] for p in parts])
        ends = np.concatenate([p[1] for p in parts])
    else:
        starts, ends = _ranges(lo, hi, hamming_weight, n_chunks)
    ntasks = starts.size
    if ntasks == 0:
        return

    # Survivor capacity per task: candidates/G is the expectation; give 4×
    # headroom + constant. On overflow (-1) retry with the exact bound.
    # process tasks in batches to bound memory (smaller batches yield
    # earlier — at huge candidate counts the first, representative-dense
    # ranges alone can take many minutes)
    batch = max(1, min(ntasks, batch_tasks))
    use_h = 1 if hamming_weight not in (None, 0) else 0
    for b0 in range(0, ntasks, batch):
        b1 = min(b0 + batch, ntasks)
        nb = b1 - b0
        s_b = np.ascontiguousarray(starts[b0:b1])
        e_b = np.ascontiguousarray(ends[b0:b1])
        # per-task capacity: index span (exact candidate count) if cheap,
        # else a heuristic; overflow retries below with bigger buffers.
        if use_h:
            spans = (_host.fixed_hamming_rank(e_b).astype(np.int64)
                     - _host.fixed_hamming_rank(s_b).astype(np.int64) + 1)
        else:
            spans = (e_b - s_b + 1).astype(np.int64)
        caps = np.minimum(spans, np.maximum(spans // max(G // 4, 1), 4096))
        while True:
            offsets = np.zeros(nb, dtype=np.int64)
            offsets[1:] = np.cumsum(caps)[:-1]
            total_cap = int(caps.sum())
            buf_s = np.empty(total_cap, dtype=np.uint64)
            buf_n = np.empty(total_cap, dtype=np.float64)
            counts = np.zeros(nb, dtype=np.int64)
            rc = lib.dmt_enumerate_ranges(
                s_b.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                e_b.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                nb, use_h, ctypes.byref(grp), norm_tol,
                buf_s.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                buf_n.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                caps.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                int(n_threads),
            )
            if rc == 0:
                break
            caps = spans  # exact upper bound — cannot overflow
        for t in range(nb):
            o, c = offsets[t], counts[t]
            if c:
                yield buf_s[o:o + c].copy(), buf_n[o:o + c].copy()
    del keepalive


def enumerate_representatives_native(
    n_sites: int,
    hamming_weight: Optional[int],
    group,
    n_chunks: Optional[int] = None,
    n_threads: Optional[int] = None,
    norm_tol: float = 1e-12,
) -> Tuple[np.ndarray, np.ndarray]:
    """Streaming native enumeration; raises :class:`NativeBuildError` if
    the kernel cannot be built.

    Matches :func:`host.enumerate_representatives` exactly (same order,
    same norms) — property-tested in tests/test_enumeration.py.
    """
    lib = _load()
    parts_s, parts_n = [], []
    for s, n in _stream_native(lib, n_sites, hamming_weight, group,
                               n_chunks, n_threads, norm_tol):
        parts_s.append(s)
        parts_n.append(n)
    if not parts_s:
        return (np.empty(0, np.uint64), np.empty(0, np.float64))
    return np.concatenate(parts_s), np.concatenate(parts_n)


def lookup_owners(betas: np.ndarray, alphas: np.ndarray,
                  counts: np.ndarray,
                  n_threads: Optional[int] = None):
    """(owner, idx, found) for each state in ``betas`` against the per-shard
    sorted representative prefixes ``alphas[d][:counts[d]]`` — the routing
    plan's hot host loop in one threaded native pass."""
    lib = _load()
    betas = np.ascontiguousarray(betas, np.uint64)
    alphas = np.ascontiguousarray(alphas, np.uint64)
    counts = np.ascontiguousarray(counts, np.int64)
    D, M = alphas.shape
    n = betas.size
    owner = np.empty(n, np.int32)
    idx = np.empty(n, np.int32)
    found = np.empty(n, np.uint8)
    lib.dmt_lookup_owners(
        betas.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)), n,
        alphas.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        D, M,
        owner.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        found.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        int(n_threads or os.cpu_count() or 1),
    )
    return owner, idx, found.astype(bool)
