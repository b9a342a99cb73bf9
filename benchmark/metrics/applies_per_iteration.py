"""Layer: solver.  Applies the device ran for each iteration the solver
counted: (``steps_run`` + ``probe_applies``) / ``steps_counted`` over the
window's solves, from the counts on the program's root ``lanczos`` spans.  1
when no block is redone and nothing probes."""

from benchmark import program_spans


def read(run):
    solves = program_spans.window_solves(run)
    if not solves:
        return None
    counted = sum(e["steps_counted"] for e in solves)
    ran = sum(e["steps_run"] + e["probe_applies"] for e in solves)
    return ran / counted if counted else None
