"""Layer: apply kernels.  The share of the slots one apply gathers that hold
a non-zero of the matrix: 100 x ``live_entries`` / ``gather_slots``, the
counts ``LocalEngine`` puts on its ``engine_init/build_structure`` span
(PR 27: ``gather_slots`` is every table slot an apply reads plus the rows of
the gather that puts the result back in basis order; ``live_entries`` the
non-zero coefficients).  The apply's time follows the slots, whatever they
hold, so this is how much of that time is spent on the matrix.  ``None``
where the span carries no such counts."""

from benchmark import program_spans


def read(run):
    build = program_spans.build_span(run)
    if not build or not build.get("gather_slots"):
        return None
    return 100.0 * build.get("live_entries", 0) / build["gather_slots"]
