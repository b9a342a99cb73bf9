"""The row gathers of one apply above the VMEM line, told apart by the table
they read (PR 35).  Where ``x`` as a gather table is cut into ranges
(``LocalEngine._build_ell_ranges``, PR 33) an apply makes three kinds of row
gather: *near* ones, from a range of ``x`` that fits VMEM; the *un-permute*
ones, from a range's accumulator, as long; and *far* ones, from whole ``x``
in HBM.  In a device trace each is one ``fusion`` whose signature
(``trace_reduce.signature``) names its result, its table and its indices,

    fusion f32[1572864,6](f32[1572864,6],s32[1572864])

and the table's rows say which kind it is: at most ``range_rows`` (a range,
the last one shorter) is near or un-permute, more than that and at most
``table_rows`` (whole ``x``: the states, padded or not) is far.  Both counts
stand on the program's ``engine_init/build_structure`` span beside
``near_slots``, ``far_slots`` and ``unpermute_slots``.  Nothing but the
table's rows decides: not the result's length, not the row's width, not a
name.  A gather whose table is longer than whole ``x`` is none this format
makes, and the readers raise: a wrong rate is worse than none.
"""

import re

from . import program_spans

PROGRAM = r"jit_apply_fn"
#: a row gather's signature: result rows x parts, table rows x the same
#: parts, one index a result row
GATHER = re.compile(
    r"^fusion f32\[(\d+),(\d+)\]\(f32\[(\d+),\2\],s32\[\1\]\)$")
COUNTS = ("range_rows", "table_rows", "near_slots", "far_slots",
          "unpermute_slots")


def counts(run):
    """This run's build span where it carries every count the gather
    metrics read and the table is cut; ``None`` for a program that puts
    none there (the parent of PR 35) and for a cell under the VMEM line
    (``range_rows`` 0: one kind of gather, ``gather_ns_per_slot``'s)."""
    build = program_spans.build_span(run)
    if not build or any(build.get(k) is None for k in COUNTS) \
            or not build["range_rows"]:
        return None
    return build


def seconds_an_apply(run, build):
    """``{"near": s, "far": s}``: own seconds of the fullest device's row
    gathers of each class, per ``jit_apply_fn`` run of the window (near
    holds the un-permute gathers: the same table length, the same memory).
    ``None`` where the trace holds no apply."""
    _, runs = run.trace.fullest.module_runs(PROGRAM)
    if not runs:
        return None
    total = {"near": 0.0, "far": 0.0}
    for sig, (seconds, _) in run.trace.fullest.own.items():
        m = GATHER.match(sig)
        if not m:
            continue
        table = int(m.group(3))
        if table <= build["range_rows"]:
            total["near"] += seconds
        elif table <= build["table_rows"]:
            total["far"] += seconds
        else:
            raise RuntimeError(
                f"{sig}: a row gather from a table of {table} rows, longer "
                f"than a table range ({build['range_rows']}) and than "
                f"whole x ({build['table_rows']}): neither near nor far")
    return {kind: s / runs for kind, s in total.items()}


def ns_per_slot(run, kind, slot_counts):
    """Own nanoseconds of the ``kind`` gathers (``"near"`` or ``"far"``) an
    apply over the slots the span counts for them (the sum of
    ``slot_counts``); ``None`` where :func:`counts` or
    :func:`seconds_an_apply` finds nothing, or the class has no slot."""
    build = counts(run)
    if build is None:
        return None
    seconds = seconds_an_apply(run, build)
    slots = sum(build[k] for k in slot_counts)
    if seconds is None or not slots:
        return None
    return 1e9 * seconds[kind] / slots
