"""The whole window over all applies completed in it (host clock)."""


def read(run):
    return 1e3 * run.window["elapsed_s"] / run.window["applies"]
