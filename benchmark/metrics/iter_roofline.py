"""Layer: solver.  The least time the chips could take for one
iteration's algorithmic bytes (``work.iteration_bytes``: one apply and the
three-term recurrence's vector passes) at chips x the published HBM peak,
over ``iter_device_ms``.  Bounded by bytes, not by operations."""

PROGRAM = r"jit_run_(window|block)"


def read(run):
    seconds, runs = run.trace.fullest.module_runs(PROGRAM)
    if not runs or not seconds:
        return None
    least = run.work.least_seconds(run.work.iteration_bytes(run.config),
                                   run.peaks, run.chips)
    return 100.0 * least / (seconds / run.window["iterations"])
