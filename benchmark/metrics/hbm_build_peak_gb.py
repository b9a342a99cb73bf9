"""Layer: structure build.  The highest the fullest device reached until the
engine was built: ``fullest.peak_bytes_in_use`` of the ``engine_init/<kind>``
sample of this run's build (enumeration's uploads, the build's passes and
their temporaries).  With ``hbm_window_rise_gb`` it makes up ``peak_hbm_gb``
to the byte.  Nothing where the program takes no such sample."""

from benchmark import hbm_samples


def read(run):
    sample = hbm_samples.built(run)
    if sample is None:
        return None
    return sample["fullest"]["peak_bytes_in_use"] / 1e9
