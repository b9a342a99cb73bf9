"""Layer: apply kernels.  Above the VMEM line, the device time of the row
gathers whose table is whole ``x`` in HBM (more than ``range_rows`` rows and
at most ``table_rows``: the far levels' gathers), per apply, over
``far_slots`` off the program's build span.  The gathers' own time in the
trace (``run.trace.fullest.own``).  17.6 ns for a 16 B row at
``chain_28.apply``.  ``None`` where the span lacks a count (the parent of
PR 35), the table is not cut, or the trace holds no apply."""

from benchmark import gather_rates


def read(run):
    return gather_rates.ns_per_slot(run, "far", ("far_slots",))
