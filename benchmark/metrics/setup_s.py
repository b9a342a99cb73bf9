"""Process start to window open: import and device start, enumeration,
structure or plan build, warm-up of the cell's own programs."""


def read(run):
    return run.setup_s
