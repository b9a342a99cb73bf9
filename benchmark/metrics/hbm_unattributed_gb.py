"""Layer: device.  Resident between two block programs and registered by no
owner: at the sample ``hbm_solve_resident_gb`` reads, ``fullest.bytes_in_use``
less ``ledger_bytes`` (engine, Krylov buffer, plan).  The harness's vectors
and earlier solves' Ritz vectors are in it, and a second copy of the Krylov
buffer if one stays between programs.  Nothing where the window's solves
take no synced ``lanczos/wait`` sample."""

from benchmark import hbm_samples


def read(run):
    sample = hbm_samples.resident(run)
    if sample is None:
        return None
    return (sample["fullest"]["bytes_in_use"] - sample["ledger_bytes"]) / 1e9
