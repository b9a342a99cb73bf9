"""Layer: device.  How far the warm-up's and the window's programs pushed
the peak past the build's: ``memory_peak_bytes`` at the window's close less
``hbm_build_peak_gb``'s bytes, not below 0.  0 says that ``peak_hbm_gb`` is
the build's and no apply or solve can move it; anything else that the
programs of the timed path set it.  Nothing where the program takes no
``engine_init/<kind>`` sample."""

from benchmark import hbm_samples


def read(run):
    sample = hbm_samples.built(run)
    if sample is None:
        return None
    build_peak = sample["fullest"]["peak_bytes_in_use"]
    return max(hbm_samples.peak_bytes(run) - build_peak, 0) / 1e9
