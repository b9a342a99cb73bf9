"""Layer: apply kernels.  Device time of the apply program's operations
(union of the ``XLA Ops`` inside ``jit_apply_fn`` runs) per apply."""

PROGRAM = r"jit_apply_fn"


def read(run):
    seconds, runs = run.trace.fullest.module_runs(PROGRAM)
    return 1e3 * seconds / runs if runs else None
