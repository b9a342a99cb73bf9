"""The work one apply and one solver iteration need, from the configuration
alone: no table shape, padding or phase count of the program enters, so a
PR that changes the format does not change the roofline's numerator.

Bytes, not operations: the chips publish no f64 peak (f64 is emulated), so
both roofline shares are bounded by HBM bandwidth.

One apply ``y = H x`` of the symmetry-reduced matrix in a plain sparse-row
form: every non-zero (the diagonal and the ``offdiag_nonzeros`` the
configuration states, counted once by the plain reference) as one value
and a 4-byte column index, a 4-byte row pointer per row, one read of ``x``
and one write of ``y``.  A value is what the configuration states its
sector to be: 8 bytes (float64) where it is real, as it is where the file
says nothing, and 16 (complex128; on the device an (re, im) pair of
float64) where the file says ``"sector": "complex"``.

One Lanczos iteration: one apply, and the three-term recurrence fused as
far as its reductions allow: read ``w``, ``v`` and ``v_prev`` once and write
the next vector once.  Reorthogonalisation is the implementation's remedy
for rounding, not work the algorithm needs, and is not counted.
"""

VALUE_BYTES = {"real": 8, "complex": 16}    # float64, complex128
INDEX_BYTES = 4      # a column index or row pointer below 2**32 states
RECURRENCE_PASSES = 4


def sector(config):
    """``"real"`` or ``"complex"``: what the configuration's file states
    under ``sector`` and nothing else (absent is real); the harness does not
    guess from the YAML."""
    stated = config.get("sector", "real")
    if stated not in VALUE_BYTES:
        raise ValueError(f"configuration states sector {stated!r}: "
                         f"one of {sorted(VALUE_BYTES)}, or nothing")
    return stated


def is_complex(config):
    return sector(config) == "complex"


def value_bytes(config):
    return VALUE_BYTES[sector(config)]


def apply_bytes(config):
    """Algorithmic bytes of one apply, on all chips together."""
    n = int(config["number_states"])
    nnz = int(config["offdiag_nonzeros"]) + n
    value = value_bytes(config)
    return (nnz * (value + INDEX_BYTES) + (n + 1) * INDEX_BYTES
            + 2 * n * value)


def iteration_bytes(config):
    """Algorithmic bytes of one Lanczos iteration, on all chips together."""
    n = int(config["number_states"])
    return apply_bytes(config) + RECURRENCE_PASSES * n * value_bytes(config)


def least_seconds(nbytes, peaks, chips):
    """The least time ``chips`` chips could take to move ``nbytes``."""
    return nbytes / (peaks["hbm_bytes_per_s"] * chips)
