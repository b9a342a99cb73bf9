"""Published peaks of the chips the benchmark may run on, by ``device_kind``.

A device that is not in the table is an error, never a default: a roofline
share against a guessed peak is a guess.  The f64 the program computes in is
emulated on these chips and no f64 peak is published, so every roofline
share of this benchmark is bounded by bytes (HBM) and says so where it is
printed.
"""

_V5E = {
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "bf16_flop_per_s": 197e12,
    "int8_op_per_s": 393e12,
    "ici_bits_per_s": 1600e9,
    "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
              "393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s ICI",
}

#: ``jax.devices()[0].device_kind`` -> peaks.  JAX calls a v5e "TPU v5 lite".
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks_for(device_kind):
    """The peaks row of ``device_kind``; ``KeyError`` for an unknown chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add a row "
            f"with its source to benchmark/peaks.py (known: "
            f"{sorted(PEAKS)})") from None
