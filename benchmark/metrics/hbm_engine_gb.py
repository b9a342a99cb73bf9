"""Layer: structure build.  What the built engine holds on the fullest
device, by the program's own memory ledger: ``ledger.engine`` (levels,
``pos_of``, lookup, basis rows, diagonal, operator tables; a shard's share
of each on a mesh) plus ``ledger.plan`` (nothing once a plan build's staging
is released) of the ``engine_init/<kind>`` sample of this run's build.  The
part of ``peak_hbm_gb`` that is there for every apply and every solve.
Nothing where the program takes no such sample."""

from benchmark import hbm_samples


def read(run):
    sample = hbm_samples.built(run)
    if sample is None:
        return None
    held = sample["ledger"]
    return (held.get("engine", 0) + held.get("plan", 0)) / 1e9
