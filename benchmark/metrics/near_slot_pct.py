"""Layer: apply kernels.  The share of an apply's table slots that are
gathered from a range of ``x`` in VMEM: 100 x ``near_slots`` /
(``near_slots`` + ``far_slots``), the counts ``LocalEngine`` puts on its
``engine_init/build_structure`` span (PR 33).  How far the table cut
engages: a near slot costs a quarter of a far one.  ``None`` where the span
lacks a count (the parent of PR 35) or the table is not cut (every slot is
then a far one by the span's count, and all of them read VMEM)."""

from benchmark import gather_rates


def read(run):
    build = gather_rates.counts(run)
    if build is None or not build["near_slots"] + build["far_slots"]:
        return None
    return 100.0 * build["near_slots"] / (build["near_slots"]
                                          + build["far_slots"])
