"""Layer: device.  1 - the union of busy intervals over the traced
window, on the fullest device."""


def read(run):
    return 100.0 * (1.0 - run.trace.fullest.busy_s / run.trace.window_s)
