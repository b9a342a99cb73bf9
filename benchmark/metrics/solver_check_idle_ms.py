"""Layer: solver, host side.  Idle time of the fullest device under the
solver's ``lanczos/start``, ``lanczos/check``, ``lanczos/restart`` and
``lanczos/epilogue`` spans (start vector and probe apply, copies of the
recurrence to the host, omega tracker, Ritz solve, convergence test, thick
restart, Ritz vectors), per counted iteration.  The program's spans on the
profiler's host line; nothing where the program has none."""

from benchmark import program_spans


def read(run):
    seconds = program_spans.idle_under(run.trace, program_spans.CHECKS)
    if seconds is None:
        return None
    return 1e3 * seconds / run.window["iterations"]
