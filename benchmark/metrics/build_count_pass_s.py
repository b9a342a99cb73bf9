"""Layer: structure build.  Pass 1 of the two-pass build
(``LocalEngine._build_ell_lowmem``): the program's ``ell/count_rows`` span
under this run's ``engine_init/build_structure``, in which the kernels run
chunk by chunk and only each row's number of non-zeros is kept.  Nothing
where the build took one pass."""

from benchmark import build_passes


def read(run):
    return build_passes.pass_seconds(run, "ell/count_rows")
