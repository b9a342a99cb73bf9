"""Layer: exchange.  Device time of collective operations (all-to-all,
all-reduce, ...) on the fullest device per counted iteration.  A trace with
no collective in it gives nothing."""


def read(run):
    seconds = run.trace.fullest.collective_s
    return 1e3 * seconds / run.window["iterations"] if seconds else None
