"""Layer: compilation (XLA).  Programs compiled (not loaded from the
cache) between window open and close.  A steady window has none; one that
has any measured the compiler."""


def read(run):
    return run.window_compiles["compiled"]
