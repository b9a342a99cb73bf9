"""Layer: structure build.  Pass 2 of the two-pass build
(``LocalEngine._build_ell_lowmem``): the program's ``ell/pack`` span under
this run's ``engine_init/build_structure``, in which the kernels run again
on the rows in packed order and each chunk's columns are written into the
level buffers.  Nothing where the build took one pass."""

from benchmark import build_passes


def read(run):
    return build_passes.pass_seconds(run, "ell/pack")
