"""Layer: structure build.  The fill passes of this run's build: the
program's ``ell/fill`` spans under its ``engine_init/build_structure``,
summed.  In a fill pass the kernels run chunk by chunk and each row's
entries are left-packed; the one-pass build (a gather table that fits VMEM)
makes one, the range build (``LocalEngine._build_ell_ranges``, PR 33) one a
table range.  It took the place of ``build_count_pass_s`` and
``build_pack_pass_s``, the two passes of a build that no cell takes since
PR 33.  Nothing where the build made no such pass."""

from benchmark import build_passes


def read(run):
    return build_passes.pass_seconds(run, "ell/fill")
