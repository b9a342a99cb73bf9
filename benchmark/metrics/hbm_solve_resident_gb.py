"""Layer: solver.  What stays on the fullest device between two block
programs of a solve: the largest ``fullest.bytes_in_use`` among the window's
synced ``lanczos/wait`` samples (taken right after the host has waited for
the block program, with nothing in flight): engine, Krylov buffer, the
harness's vectors, and whatever else no program holds only while it runs.
With ``hbm_solve_transient_gb`` it makes up ``peak_hbm_gb`` to the byte.
Nothing where the window's solves take no such sample."""

from benchmark import hbm_samples


def read(run):
    sample = hbm_samples.resident(run)
    if sample is None:
        return None
    return sample["fullest"]["bytes_in_use"] / 1e9
