"""The whole window over all Lanczos iterations completed in it (host
clock): solver start, block boundaries, Ritz checks, reorthogonalisation,
any redone block or restart and the Ritz-vector epilogue are inside it."""


def read(run):
    return 1e3 * run.window["elapsed_s"] / run.window["iterations"]
