"""Layer: solver.  The Krylov buffer on the fullest device, by the program's
own memory ledger: ``ledger.solver`` of the window's synced ``lanczos/wait``
samples, the largest (the buffer's rows times a shard's share of a row; a
quarter of the buffer on four chips).  Nothing where the window's solves
take no such sample."""

from benchmark import hbm_samples


def read(run):
    found = hbm_samples.between_programs(run)
    if not found:
        return None
    return max(e["ledger"].get("solver", 0) for e in found) / 1e9
