"""Layer: apply kernels.  The least time the chips could take for one
apply's algorithmic bytes (``work.apply_bytes``, from the configuration
alone) at the published HBM peak, over ``apply_device_ms``.  Bounded by
bytes, not by operations: the chip publishes no f64 peak."""

PROGRAM = r"jit_apply_fn"


def read(run):
    seconds, runs = run.trace.fullest.module_runs(PROGRAM)
    if not runs or not seconds:
        return None
    least = run.work.least_seconds(run.work.apply_bytes(run.config),
                                   run.peaks, run.chips)
    return 100.0 * least / (seconds / runs)
