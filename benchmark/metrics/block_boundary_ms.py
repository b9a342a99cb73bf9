"""Layer: solver, host side.  Idle time of the fullest device between
one block program and the next inside a solve (host Ritz solve, convergence
check, the omega tracker, the next block's dispatch; at a solve's start the
load of the block program), per counted iteration."""

PROGRAM = r"jit_run_(window|block)"


def read(run):
    return 1e3 * run.trace.boundary_seconds(PROGRAM) \
        / run.window["iterations"]
