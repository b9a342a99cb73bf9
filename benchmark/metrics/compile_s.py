"""Layer: compilation (XLA).  Seconds of ``jax.monitoring``
backend-compile events before the window opens: compiling, or loading from
the persistent cache."""


def read(run):
    return run.setup_compiles["request_s"]
