"""Layer: solver, host side.  Idle time of the fullest device under the
solver's ``lanczos/dispatch`` spans (the block program's call: a trace, a
lowering, a cache load or a cached dispatch), and under the ``lanczos/wait``
that follows until that block program's first operation, per counted
iteration.  The program's spans on the profiler's host line; nothing where
the program has none."""

from benchmark import program_spans


def read(run):
    seconds = program_spans.dispatch_idle(run.trace)
    if seconds is None:
        return None
    return 1e3 * seconds / run.window["iterations"]
