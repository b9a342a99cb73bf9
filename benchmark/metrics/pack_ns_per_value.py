"""Layer: exchange.  On a mesh, the device time of the gather that packs the
send buffer (``S = x[qin]``: the values every peer reads from this chip) per
apply, over the values it packs: ``send_values`` off the program's
``engine_init/build_plan`` span (8 B a value, 16 for an (re, im) pair:
``value_bytes``).  The gathers' own time in the trace, classified by
``benchmark/dist_gathers.py``; applies are the solves' ``steps_run`` +
``probe_applies``.  ``None`` where the span lacks a count (the parent of PR
39) or the trace holds no such gather."""

from benchmark import dist_gathers


def read(run):
    return dist_gathers.ns_per(run, "pack", "send_values")
