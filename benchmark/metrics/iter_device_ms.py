"""Layer: solver.  Device time of the Lanczos block programs (union of
the ``XLA Ops`` inside ``jit_run_window`` / ``jit_run_block`` runs) per
counted iteration, on the fullest device.  A block the solver redoes with
the full sweep is device time, not a second count."""

PROGRAM = r"jit_run_(window|block)"


def read(run):
    seconds, runs = run.trace.fullest.module_runs(PROGRAM)
    return 1e3 * seconds / run.window["iterations"] if runs else None
