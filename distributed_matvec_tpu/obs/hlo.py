"""HLO-level cost attribution for compiled apply executables.

Every engine mode is one static program per config (the GSPMD premise),
so the optimized HLO of a compiled executable — together with XLA's own
``cost_analysis()`` totals — is a *stable, content-addressable*
description of the apply.  This module captures that description once
per compile:

* :func:`parse_hlo_ops` reads the optimized HLO text and lists every
  instruction with its opcode, output-shape bytes, and the ``op_name``
  metadata the tracer attached.
* :func:`classify_op` buckets each instruction into the §22 phase
  taxonomy (``plan_h2d`` / ``compute`` / ``exchange`` / ``accumulate``
  / ``overhead``) keyed on opcode first and ``op_name`` substrings for
  refinement (the traced jaxpr path, ``jax.named_scope`` names included).
* :func:`attribute_costs` distributes the executable's whole-program
  ``cost_analysis()`` totals (flops / bytes accessed) over the parsed
  ops so per-op and per-phase costs *sum exactly* to the program
  totals (the largest op absorbs rounding).
* :func:`diff_profiles` compares two profile artifacts op-by-op with
  the same direction-aware gate semantics as ``obs_report diff`` —
  every HLO cost is cost-like, growth is a regression.

Import-dual like ``obs/slo.py``: inside the package,
:func:`record_executable_costs` also emits an ``hlo_cost`` event and
writes a content-addressed artifact (``hlo-profile/<fp2>/<fp>.json``)
next to the XLA cache; loaded standalone by file (``tools/obs_report.py
profile`` and ``tools/profile_diff.py``, which must never import jax)
only the pure parse/attribute/diff surface exists and capture is inert.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import threading
from typing import Dict, List, Optional, Sequence, Tuple

try:                                    # package mode
    from ..utils.logging import log_debug as _log_debug
    from .events import emit as _emit
    from .events import obs_enabled as _obs_enabled
    from .metrics import counter as _counter
    _STANDALONE = False
except ImportError:                     # file-loaded by tools/*
    _STANDALONE = True

    def _obs_enabled():
        return False

    def _emit(kind, **fields):
        return None

    def _log_debug(msg):
        return None

    def _counter(name, **labels):
        raise RuntimeError("no metrics registry in standalone mode")

__all__ = [
    "PHASE_OPCODES",
    "classify_op",
    "parse_hlo_ops",
    "attribute_costs",
    "profile_fingerprint",
    "build_profile",
    "load_profile",
    "hottest_ops",
    "diff_profiles",
    "print_profile",
    "print_profile_diff",
    "record_executable_costs",
    "executable_costs",
    "reset_hlo",
]

#: Artifact schema version (bump on layout change, never reuse).
PROFILE_VERSION = 1

#: How many per-op rows ride on the ``hlo_cost`` event itself (the full
#: table lives in the artifact; the event stays ring-buffer friendly).
EVENT_TOP_OPS = 8

# ---------------------------------------------------------------------------
# phase classification

#: opcode → phase.  Collectives are exchange; scatter-shaped writes are
#: accumulate; host↔device staging is plan_h2d; free structural ops are
#: overhead; everything else (dot/gather/fusion/elementwise) is compute.
PHASE_OPCODES: Dict[str, str] = {
    "all-to-all": "exchange",
    "all-reduce": "exchange",
    "all-gather": "exchange",
    "all-reduce-start": "exchange",
    "all-reduce-done": "exchange",
    "collective-permute": "exchange",
    "collective-permute-start": "exchange",
    "collective-permute-done": "exchange",
    "reduce-scatter": "exchange",
    "send": "exchange",
    "recv": "exchange",
    "scatter": "accumulate",
    "select-and-scatter": "accumulate",
    "dynamic-update-slice": "accumulate",
    "parameter": "plan_h2d",
    "copy": "plan_h2d",
    "copy-start": "plan_h2d",
    "copy-done": "plan_h2d",
    "infeed": "plan_h2d",
    "outfeed": "plan_h2d",
    "tuple": "overhead",
    "get-tuple-element": "overhead",
    "bitcast": "overhead",
    "bitcast-convert": "overhead",
    "reshape": "overhead",
    "constant": "overhead",
    "iota": "overhead",
    "after-all": "overhead",
    "partition-id": "overhead",
    "replica-id": "overhead",
}

#: ``op_name`` metadata substrings that refine a compute-bucketed op —
#: fusions carry the traced jaxpr path, so a fused scatter-add still
#: lands in accumulate and a fused ppermute in exchange.
_OPNAME_PHASE: Tuple[Tuple[str, str], ...] = (
    ("ppermute", "exchange"),
    ("all_to_all", "exchange"),
    ("psum", "exchange"),
    ("all_gather", "exchange"),
    ("scatter-add", "accumulate"),
    ("scatter_add", "accumulate"),
    ("segment_sum", "accumulate"),
)

#: bytes per element for HLO shape dtypes (default 4 when unknown).
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

#: opcodes that can carry flops (flop totals are distributed over these,
#: weighted by output bytes; pure data movement never gets flops).
_FLOP_OPCODES = frozenset((
    "fusion", "dot", "convolution", "reduce", "reduce-window", "scatter",
    "select-and-scatter", "all-reduce", "reduce-scatter", "multiply",
    "add", "subtract", "divide", "exponential", "log", "rsqrt", "sqrt",
    "tanh", "power", "cholesky", "triangular-solve", "sort", "map",
))


def classify_op(opcode: str, op_name: str = "") -> str:
    """Phase bucket for one HLO instruction: opcode table first, then
    ``op_name`` metadata substrings refine compute-bucketed ops."""
    phase = PHASE_OPCODES.get(opcode, "compute")
    if phase == "compute" and op_name:
        low = op_name.lower()
        for sub, refined in _OPNAME_PHASE:
            if sub in low:
                return refined
    return phase


# ---------------------------------------------------------------------------
# HLO text parsing

_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<shape>\([^=]*?\)|[\w\[\]{},\s/#*]+?)\s+"
    r"(?P<opcode>[\w\-]+)\(")

_OPNAME_RE = re.compile(r'op_name="([^"]*)"')

_SHAPE_RE = re.compile(r"(?P<dtype>[a-z]+\d*)\[(?P<dims>[\d,]*)\]")


def _shape_bytes(shape: str) -> int:
    """Total bytes of one HLO shape string (tuple shapes sum their
    leaves; token/opaque shapes count zero)."""
    total = 0
    for m in _SHAPE_RE.finditer(shape):
        nelem = 1
        dims = m.group("dims")
        if dims:
            for d in dims.split(","):
                nelem *= int(d)
        total += nelem * _DTYPE_BYTES.get(m.group("dtype"), 4)
    return total


def parse_hlo_ops(hlo_text: str) -> List[dict]:
    """Every instruction of the optimized HLO as
    ``{"name", "opcode", "phase", "shape_bytes", "op_name"}`` rows.
    Computation headers / braces / metadata-only lines are skipped."""
    ops: List[dict] = []
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        opcode = m.group("opcode")
        nm = _OPNAME_RE.search(line)
        op_name = nm.group(1) if nm else ""
        ops.append({
            "name": m.group("name"),
            "opcode": opcode,
            "phase": classify_op(opcode, op_name),
            "shape_bytes": _shape_bytes(m.group("shape")),
            "op_name": op_name,
        })
    return ops


# ---------------------------------------------------------------------------
# cost attribution

def _distribute(total: float, weights: Sequence[float]) -> List[float]:
    """Split ``total`` proportionally to ``weights`` so the parts sum to
    ``total`` *exactly* — the largest-weight part absorbs the rounding
    remainder.  All-zero weights → uniform split."""
    n = len(weights)
    if n == 0 or total <= 0:
        return [0.0] * n
    wsum = float(sum(weights))
    if wsum <= 0:
        parts = [total / n] * n
    else:
        parts = [total * (w / wsum) for w in weights]
    # pin the exact sum on the largest part
    imax = max(range(n), key=lambda i: parts[i])
    parts[imax] += total - sum(parts)
    return parts


def attribute_costs(hlo_text: str, totals: Dict[str, float]) -> dict:
    """Distribute whole-program ``cost_analysis()`` totals over parsed
    ops.  Per-op weight is the output-shape byte count (the only
    structural size signal the HLO text carries); flops are spread over
    flop-capable opcodes only.  Per-op and per-phase sums equal the
    program totals exactly.  Returns ``{"ops": [...], "phases": {...},
    "totals": {...}}``."""
    ops = parse_hlo_ops(hlo_text)
    t_bytes = float(totals.get("bytes", 0.0))
    t_flops = float(totals.get("flops", 0.0))

    byte_w = [float(o["shape_bytes"]) for o in ops]
    op_bytes = _distribute(t_bytes, byte_w)
    flop_w = [float(o["shape_bytes"]) if o["opcode"] in _FLOP_OPCODES
              else 0.0 for o in ops]
    if not any(flop_w):                  # no flop-capable op parsed
        flop_w = byte_w
    op_flops = _distribute(t_flops, flop_w)

    out_ops: List[dict] = []
    phases: Dict[str, dict] = {}
    for o, b, fl in zip(ops, op_bytes, op_flops):
        row = {"name": o["name"], "opcode": o["opcode"],
               "phase": o["phase"], "bytes": b, "flops": fl}
        out_ops.append(row)
        ph = phases.setdefault(o["phase"],
                               {"bytes": 0.0, "flops": 0.0, "ops": 0})
        ph["bytes"] += b
        ph["flops"] += fl
        ph["ops"] += 1
    return {
        "ops": out_ops,
        "phases": phases,
        "totals": {"bytes": t_bytes, "flops": t_flops,
                   "transcendentals": float(
                       totals.get("transcendentals", 0.0))},
    }


def profile_fingerprint(hlo_text: str) -> str:
    """Content address of one compiled program: sha256 of its optimized
    HLO text.  A recompile that changes the program changes the
    fingerprint; an identical program re-lowered hits the same one."""
    return hashlib.sha256(hlo_text.encode()).hexdigest()


def build_profile(key: str, hlo_text: str, totals: Dict[str, float],
                  program: Optional[str] = None) -> dict:
    """Assemble the full content-addressed profile artifact dict."""
    attributed = attribute_costs(hlo_text, totals)
    return {
        "version": PROFILE_VERSION,
        "key": str(key),
        "program": str(program or key),
        "fingerprint": profile_fingerprint(hlo_text),
        "totals": attributed["totals"],
        "phases": attributed["phases"],
        "ops": attributed["ops"],
    }


def load_profile(path: str) -> dict:
    """Read one profile artifact from disk (raises on malformed files —
    callers are CLIs that want the traceback, not a None)."""
    with open(path) as f:
        prof = json.load(f)
    if not isinstance(prof, dict) or "ops" not in prof:
        raise ValueError(f"not an hlo profile artifact: {path}")
    return prof


def hottest_ops(profile: dict, top: int = 3) -> List[dict]:
    """The ``top`` most expensive ops by attributed bytes (the universal
    cost axis — flops are zero for movement-bound programs)."""
    ops = sorted(profile.get("ops", ()),
                 key=lambda o: (-float(o.get("bytes", 0.0)),
                                -float(o.get("flops", 0.0)),
                                o.get("name", "")))
    return ops[:max(int(top), 0)]


# ---------------------------------------------------------------------------
# differential profiling

def diff_profiles(base: dict, new: dict, threshold: float = 0.25,
                  top: int = 10) -> dict:
    """Op-by-op diff of two profile artifacts with ``obs_report diff``
    gate semantics: every HLO cost is cost-like, so growth beyond
    ``threshold`` (relative) is a regression.  Ops are matched by name
    first, falling back to ``opcode#ordinal`` so renamed-but-identical
    programs still align.  Returns ``{"rows", "regressions",
    "appeared", "vanished", "same_program"}``; rows/regressions are
    sorted worst-first and capped at ``top``."""
    def _index(prof):
        seen: Dict[str, int] = {}
        out = {}
        for o in prof.get("ops", ()):
            ordinal = seen.get(o["opcode"], 0)
            seen[o["opcode"]] = ordinal + 1
            out[o["name"]] = (o, f"{o['opcode']}#{ordinal}")
        return out

    bi, ni = _index(base), _index(new)
    b_alias = {alias: op for op, alias in bi.values()}
    matched: List[Tuple[dict, dict]] = []
    appeared: List[dict] = []
    used_aliases = set()
    for name, (op, alias) in ni.items():
        if name in bi:
            matched.append((bi[name][0], op))
            used_aliases.add(bi[name][1])
        elif alias in b_alias:
            matched.append((b_alias[alias], op))
            used_aliases.add(alias)
        else:
            appeared.append(op)
    vanished = [op for op, alias in bi.values()
                if alias not in used_aliases
                and op["name"] not in ni]

    rows: List[dict] = []
    for b_op, n_op in matched:
        for axis in ("bytes", "flops"):
            b_v = float(b_op.get(axis, 0.0))
            n_v = float(n_op.get(axis, 0.0))
            if b_v <= 0.0 and n_v <= 0.0:
                continue
            delta = n_v - b_v
            ratio = (n_v / b_v) if b_v > 0 else float("inf")
            rows.append({
                "name": n_op["name"], "opcode": n_op["opcode"],
                "phase": n_op.get("phase", "compute"), "axis": axis,
                "base": b_v, "new": n_v, "delta": delta, "ratio": ratio,
                "regressed": (delta > 0
                              and (b_v <= 0
                                   or delta / b_v > float(threshold))),
            })
    rows.sort(key=lambda r: (-(r["delta"] if r["delta"] > 0 else 0.0),
                             r["name"]))
    regressions = [r for r in rows if r["regressed"]]
    return {
        "rows": rows[:max(int(top), 1)],
        "regressions": regressions[:max(int(top), 1)],
        "appeared": appeared[:max(int(top), 1)],
        "vanished": vanished[:max(int(top), 1)],
        "same_program": (base.get("fingerprint")
                         == new.get("fingerprint")),
    }


# ---------------------------------------------------------------------------
# rendering (shared by obs_report profile and tools/profile_diff.py)

def _fmt_qty(v: float) -> str:
    for unit, div in (("G", 1e9), ("M", 1e6), ("k", 1e3)):
        if abs(v) >= div:
            return f"{v / div:.2f}{unit}"
    return f"{v:.0f}"


def print_profile(profile: dict, top: int = 10, out=None) -> None:
    """Human rendering of one profile artifact: identity line, phase
    table, hottest-op table."""
    import sys
    w = out or sys.stdout
    w.write(f"program   {profile.get('program', '?')}\n")
    w.write(f"key       {profile.get('key', '?')}\n")
    w.write(f"artifact  {profile.get('fingerprint', '?')[:16]}\n")
    t = profile.get("totals", {})
    w.write(f"totals    flops={_fmt_qty(t.get('flops', 0.0))}  "
            f"bytes={_fmt_qty(t.get('bytes', 0.0))}\n")
    w.write(f"{'phase':<20}{'bytes':>12}{'flops':>12}{'ops':>6}\n")
    for ph in sorted(profile.get("phases", {})):
        row = profile["phases"][ph]
        w.write(f"{ph:<20}{_fmt_qty(row['bytes']):>12}"
                f"{_fmt_qty(row['flops']):>12}{row['ops']:>6}\n")
    w.write(f"hottest ops (top {top}):\n")
    w.write(f"  {'op':<32}{'opcode':<22}{'phase':<14}"
            f"{'bytes':>10}{'flops':>10}\n")
    for o in hottest_ops(profile, top):
        w.write(f"  {o['name'][:31]:<32}{o['opcode'][:21]:<22}"
                f"{o['phase']:<14}{_fmt_qty(o['bytes']):>10}"
                f"{_fmt_qty(o['flops']):>10}\n")


def print_profile_diff(diff: dict, out=None) -> None:
    """Human rendering of a :func:`diff_profiles` result."""
    import sys
    w = out or sys.stdout
    if diff.get("same_program"):
        w.write("programs are byte-identical (same fingerprint)\n")
    n_reg = len(diff.get("regressions", ()))
    w.write(f"{len(diff.get('rows', ()))} changed op-axes, "
            f"{n_reg} regressed, {len(diff.get('appeared', ()))} new, "
            f"{len(diff.get('vanished', ()))} gone\n")
    if diff.get("rows"):
        w.write(f"  {'op':<32}{'axis':<7}{'base':>10}{'new':>10}"
                f"{'ratio':>8}  flag\n")
        for r in diff["rows"]:
            flag = "REGRESSED" if r["regressed"] else ""
            ratio = ("inf" if r["ratio"] == float("inf")
                     else f"{r['ratio']:.2f}x")
            w.write(f"  {r['name'][:31]:<32}{r['axis']:<7}"
                    f"{_fmt_qty(r['base']):>10}{_fmt_qty(r['new']):>10}"
                    f"{ratio:>8}  {flag}\n")
    for label, ops in (("new ops", diff.get("appeared", ())),
                       ("vanished ops", diff.get("vanished", ()))):
        for o in ops:
            w.write(f"  {label}: {o['name']} ({o['opcode']}, "
                    f"{_fmt_qty(float(o.get('bytes', 0.0)))}B)\n")


# ---------------------------------------------------------------------------
# package-mode capture (inert standalone)

_lock = threading.Lock()
_profiles: Dict[str, dict] = {}


def _cost_totals(compiled) -> Optional[Dict[str, float]]:
    """Normalize ``compiled.cost_analysis()`` — some backends return a
    list with one dict per computation, some a bare dict."""
    try:
        ca = compiled.cost_analysis()
    except Exception as e:
        _log_debug(f"cost_analysis unavailable: {e!r}")
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    return {
        "flops": float(ca.get("flops", 0.0) or 0.0),
        "bytes": float(ca.get("bytes accessed", 0.0) or 0.0),
        "transcendentals": float(ca.get("transcendentals", 0.0) or 0.0),
    }


def record_executable_costs(key: str, compiled,
                            program: Optional[str] = None,
                            **fields) -> Optional[dict]:
    """Capture the HLO cost profile of one freshly compiled executable:
    parse its optimized HLO, attribute ``cost_analysis()`` totals over
    ops and phases, store the profile in the process registry, emit an
    ``hlo_cost`` event (totals + phase split + top ops + artifact
    path), and persist the content-addressed artifact next to the XLA
    cache.  Soft-fail throughout; returns the profile dict or None."""
    if _STANDALONE or not _obs_enabled():
        return None
    totals = _cost_totals(compiled)
    if totals is None:
        return None
    try:
        hlo_text = compiled.as_text()
    except Exception as e:
        _log_debug(f"hlo text unavailable for {key}: {e!r}")
        return None
    try:
        prof = build_profile(key, hlo_text, totals, program=program)
    except Exception as e:
        _log_debug(f"hlo attribution failed for {key}: {e!r}")
        return None
    path = _save_profile_artifact(prof)
    if path:
        prof["artifact"] = path
    with _lock:
        _profiles[str(key)] = prof
    _counter("hlo_profile_count",
             program=prof["program"]).inc()
    phase_bytes = {f"phase_bytes_{ph}": row["bytes"]
                   for ph, row in prof["phases"].items()}
    phase_flops = {f"phase_flops_{ph}": row["flops"]
                   for ph, row in prof["phases"].items()}
    _emit("hlo_cost",
          key=prof["key"], program=prof["program"],
          fingerprint=prof["fingerprint"],
          artifact=prof.get("artifact", ""),
          flops=prof["totals"]["flops"],
          bytes=prof["totals"]["bytes"],
          transcendentals=prof["totals"]["transcendentals"],
          n_ops=len(prof["ops"]),
          top_ops=hottest_ops(prof, EVENT_TOP_OPS),
          **phase_bytes, **phase_flops, **fields)
    return prof


def _save_profile_artifact(prof: dict) -> Optional[str]:
    """Write the content-addressed artifact
    (``hlo-profile/<fp2>/<fp>.json``); soft-fail like every cache
    write.  Re-capturing an unchanged program is a cache hit: same
    fingerprint, same path, file simply rewritten with identical
    bytes."""
    from ..utils.artifacts import artifact_path, artifacts_enabled

    if not artifacts_enabled():
        return None
    try:
        path = artifact_path("hlo-profile", prof["fingerprint"], ".json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(prof, f, sort_keys=True)
        os.replace(tmp, path)
        return path
    except OSError as e:
        _log_debug(f"hlo-profile artifact save skipped: {e!r}")
        return None


def executable_costs() -> Dict[str, dict]:
    """Snapshot of every captured HLO cost profile, keyed by program
    specialization key."""
    with _lock:
        return {k: dict(v) for k, v in _profiles.items()}


def reset_hlo() -> None:
    """Drop all captured profiles (test isolation)."""
    with _lock:
        _profiles.clear()
