"""Layer: apply kernels.  On a mesh, the device time of ``DistributedEngine``'s
row gathers (the columns of its ELL table and of its tail, from the chip's
own ``x`` with the exchange's values behind it) per apply, over the slots
they gather: ``row_gather_slots`` off the program's ``engine_init/build_plan``
span.  The gathers' own time in the trace (``run.trace.fullest.own``),
classified by ``benchmark/dist_gathers.py``; applies are the solves'
``steps_run`` + ``probe_applies``.  ``None`` where the span lacks a count
(the parent of PR 39) or the trace holds no such gather."""

from benchmark import dist_gathers


def read(run):
    return dist_gathers.ns_per(run, "rows", "row_gather_slots")
