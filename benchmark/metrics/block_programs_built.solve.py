"""Layer: solver, host side.  Block programs a solve of the window had to
build (trace, lower, and compile or load from the persistent cache) because
the solver kept none from the call before: ``programs_built`` on the
program's root ``lanczos`` spans, per solve."""

from benchmark import program_spans


def read(run):
    solves = program_spans.window_solves(run)
    if not solves:
        return None
    return sum(e["programs_built"] for e in solves) / len(solves)
