"""Layer: compilation (XLA).  Programs compiled (not loaded from the
cache) between window open and close.  Every ``lanczos`` call builds its
block programs anew and loads them from the persistent cache: those are
counted as ``loaded`` in the result line's ``window`` object, not here."""


def read(run):
    return run.window_compiles["compiled"]
