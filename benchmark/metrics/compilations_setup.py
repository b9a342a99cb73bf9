"""Layer: compilation (XLA).  Executables JAX asked for before the window
opens (``backend_compile_duration`` events): compiled or loaded from the
persistent cache; the split is in the result line's ``window`` object."""


def read(run):
    return run.setup_compiles["requests"]
