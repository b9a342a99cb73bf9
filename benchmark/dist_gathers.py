"""The gathers of one apply of ``DistributedEngine`` (PR 39), told apart by
their operands.  An apply on a chip makes two kinds of gather, each one
``fusion`` whose signature (``trace_reduce.signature``) names its result,
its table and its indices (the indices padded by the compiler):

    fusion f32[2348928,6](f32[11632128,6],s32[2349056])    a row gather
    fusion f32[9283200,2](f32[2348928,2],s32[9283584])     the pack

A *row gather* reads whole rows of the receive-extended vector, the chip's
own ``x`` with what the exchange brought behind it: ``shard_rows`` +
``send_values`` rows of three f32 parts a float64 (six for an (re, im)
pair), one a column of the main table and one a column of the tail.  The
*pack* gathers ``send_values`` values of the chip's own ``x``
(``shard_rows`` rows) into the send buffer, as the float64's two f32 halves
(a row of two for a pair).  The counts stand on the program's
``engine_init/build_plan`` span beside ``row_gather_slots`` and
``value_bytes``.  Nothing but the rows of table and result and the widths
decides: not a name, not a ``jax.named_scope`` (an executable loaded from a
cache that an older commit filled keeps that commit's scopes, PERF.md §7).
Applies are the solver's own count: ``steps_run`` + ``probe_applies`` of the
window's solves.
"""

import re

from . import program_spans

ROW = re.compile(
    r"^fusion f32\[(\d+),(\d+)\]\(f32\[(\d+),\2\],s32\[\d+\]\)$")
PACK = re.compile(
    r"^fusion f32\[(\d+)((?:,\d+)?)\]\(f32\[(\d+)\2\],s32\[\d+\]\)$")
COUNTS = ("shard_rows", "row_gather_slots", "send_values", "value_bytes")


def counts(run):
    """This run's plan-build span where it carries every count the readers
    need and the apply exchanges; ``None`` for a program that puts none
    there (the parent of PR 39) and for one device (nothing is packed)."""
    build = program_spans.build_span(run)
    if not build or any(build.get(k) is None for k in COUNTS) \
            or not build["send_values"]:
        return None
    return build


def applies(run):
    """Applies the device ran in the window's solves; ``None`` where the
    spans carry no counts."""
    solves = program_spans.window_solves(run)
    if not solves:
        return None
    return sum(e["steps_run"] + e["probe_applies"] for e in solves)


def seconds_an_apply(run, build):
    """``{"rows": s, "pack": s}``: own seconds of the fullest device's row
    gathers and pack gathers per apply; ``None`` where the trace holds
    neither or the window ran no apply."""
    n = applies(run)
    if not n:
        return None
    f64s = build["value_bytes"] // 8     # float64s a value: 1, or 2 a pair
    table = build["shard_rows"] + build["send_values"]
    pack_width = f",{f64s}" if f64s > 1 else ""
    total = {"rows": 0.0, "pack": 0.0}
    for sig, (seconds, _) in run.trace.fullest.own.items():
        row = ROW.match(sig)
        if row and int(row.group(3)) == table \
                and int(row.group(2)) == 3 * f64s:
            total["rows"] += seconds
            continue
        pack = PACK.match(sig)
        if pack and int(pack.group(1)) == build["send_values"] \
                and int(pack.group(3)) == build["shard_rows"] \
                and pack.group(2) == pack_width:
            total["pack"] += seconds
    if not any(total.values()):
        return None
    return {kind: s / n for kind, s in total.items()}


def ns_per(run, kind, count):
    """Own nanoseconds of the ``kind`` gathers (``"rows"`` or ``"pack"``)
    an apply over the span's ``count``; ``None`` where :func:`counts` or
    :func:`seconds_an_apply` finds nothing."""
    build = counts(run)
    if build is None:
        return None
    seconds = seconds_an_apply(run, build)
    if seconds is None or not seconds[kind] or not build[count]:
        return None
    return 1e9 * seconds[kind] / build[count]
