"""Layer: solver.  What the programs need on top of what stays:
``memory_peak_bytes`` at the window's close less ``hbm_solve_resident_gb``'s
bytes.  The part of ``peak_hbm_gb`` that lives only while a program runs (a
block program's temporaries, the restart's, the epilogue's), or that the
build left as the peak where no solve passes it.  Nothing where the window's
solves take no synced ``lanczos/wait`` sample."""

from benchmark import hbm_samples


def read(run):
    sample = hbm_samples.resident(run)
    if sample is None:
        return None
    return (hbm_samples.peak_bytes(run)
            - sample["fullest"]["bytes_in_use"]) / 1e9
