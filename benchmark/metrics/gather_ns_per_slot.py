"""Layer: apply kernels.  Device time of one apply (``apply_device_ms``'s:
the busy time inside ``jit_apply_fn`` runs, per run) over the slots it
gathers: ``gather_slots`` off ``LocalEngine``'s
``engine_init/build_structure`` span, every table slot an apply reads plus
the rows of the gather that puts the result back in basis order.  The whole
apply's time, not the gathers' alone, so that the format's other costs (a
loop's own, pads, coefficient slices) count against the rate.  ``None``
where the span carries no such counts or the trace holds no apply."""

from benchmark import program_spans

PROGRAM = r"jit_apply_fn"


def read(run):
    build = program_spans.build_span(run)
    if not build or not build.get("gather_slots"):
        return None
    seconds, runs = run.trace.fullest.module_runs(PROGRAM)
    if not runs or not seconds:
        return None
    return 1e9 * seconds / runs / build["gather_slots"]
