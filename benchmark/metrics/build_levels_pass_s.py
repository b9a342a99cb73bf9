"""Layer: structure build.  The passes that cut the staircase levels of this
run's build: the program's ``ell/stair_levels`` spans under its
``engine_init/build_structure``, summed.  One in the one-pass build (row
order, one program a level and table, on the device), one a table range in
the range build (the host's cut of the range's two staircases and the
pieces' upload).  Nothing where the build made no such pass."""

from benchmark import build_passes


def read(run):
    return build_passes.pass_seconds(run, "ell/stair_levels")
