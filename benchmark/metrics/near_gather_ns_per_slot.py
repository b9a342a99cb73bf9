"""Layer: apply kernels.  Above the VMEM line, the device time of the row
gathers whose table is a range of ``x`` or a range's accumulator (at most
``range_rows`` rows: the near levels' gathers and the ones that put a
range's sums back in range order), per apply, over the slots they gather:
``near_slots`` + ``unpermute_slots`` off the program's build span.  The
gathers' own time in the trace (``run.trace.fullest.own``), not the whole
apply's: the rate ``gather_ns_per_slot`` averages with the far one.  4.317
ns where table, indices and result fit VMEM (``chain_28.apply``).  ``None``
where the span lacks a count (the parent of PR 35), the table is not cut,
or the trace holds no apply."""

from benchmark import gather_rates


def read(run):
    return gather_rates.ns_per_slot(run, "near", ("near_slots", "unpermute_slots"))
