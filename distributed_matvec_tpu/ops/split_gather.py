"""Exact triple-float32 split gathers for 64-bit values on TPU.

TPU has no native 64-bit types: XLA emulates f64/c128 as 32-bit pairs, and
an emulated-f64 gather issues *two* index-rate-bound gathers — gathers pay
per index, not per byte.  Splitting ``x`` into three f32 parts
``x = a + b + c`` (24-bit mantissa each, 72 ≥ 53 bits total) turns every
table gather into ONE gather of a ``[..., 3]`` f32 row at the f32 index
rate (an earlier round read 42 M elem/s for the f64 gather it replaced).
On the attached v5e that rate is one of two, whatever the table's size or
the indices' locality (PERF.md §5 and §6, PR 31): 4.32 ns a slot (232 M
rows/s) where the table, the indices and the gathered rows fit the chip's
128 MiB of VMEM together, so that the compiler leaves the gather's result
there (memory space ``S(1)`` in the optimised HLO; a row of 3 is padded to
4 lanes, 16 B), and 6.06 ns (165 M rows/s) where the result goes to HBM:
over chain_32_symm's 4.7 M-row table the step lies between gathers of 2.0
and 3.3 M rows.  ``LocalEngine`` cuts its gathers to the fitting side
(``parallel/engine.py::gather_row_blocks``), and where ``x`` itself is too
long to be a table in VMEM (13 to 18 ns a slot from HBM: PERF.md §5) it
cuts the table and gathers each range's own entries from that range
(``gather_table_ranges``).  The split is **bit-exact**:

* ``a = f32(x)``, ``b = f32(x − a)``, ``c = f32(x − a − b)`` — consecutive
  roundings, so ``b ≲ ulp32(a)``, ``c ≲ ulp32(b)``.
* Reassembly ``(f64(a) + f64(b)) + f64(c)`` is exact: ``a + b`` spans ≤ 50
  mantissa bits, and the final add rounds to the representable true value
  ``x`` itself.
* Parts smaller than the f32 denormal floor (|x| < ~1e-41) are flushed; the
  absolute error is < 1e-41 — far below the engine tolerance (atol 1e-14,
  reference TestMatrixVectorProduct.chpl:15-16) for the solver-normalized
  vectors the engines consume.
* Precondition: |x| must stay below f32 max (~3.4e38).  Inf/NaN inputs and
  finite values beyond that bound poison the split (``f32(x) = inf`` →
  ``x − inf = NaN``) and the result is NaN — loud, not silently wrong.
  Engine vectors are solver-normalized, far inside the bound.

complex128 uses six parts (re then im).  The ``split_gather`` config knob
gates the rewrite: ``"auto"`` (default) enables it exactly when the default
JAX backend is TPU — on CPU the native f64 gather is faster than
split + join.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..utils.config import get_config

__all__ = ["split_gather_enabled", "split_parts", "join_parts",
           "prep_gather"]


def split_gather_enabled() -> bool:
    """True when gathers should use the triple-f32 form (see module doc)."""
    knob = get_config().split_gather
    if knob == "on":
        return True
    if knob == "off":
        return False
    if knob != "auto":
        raise ValueError(
            f"unknown split_gather setting {knob!r} (use auto | on | off)")
    return jax.default_backend() == "tpu"


def prep_gather(x, dtype, enabled: bool):
    """Row-gather closure over ``x``: ``gather(idx) == x[idx]`` numerically.

    When ``enabled``, ``x`` is pre-split once and every gather moves one
    ``[..., P]`` f32 row instead of an emulated-64-bit element (see module
    doc); otherwise the plain gather is returned.

    Batched/pair vectors (trailing axes) are flattened so each gather moves
    ONE contiguous ``[k·P]`` f32 row: on v5e the row-gather rate is flat up
    to width ~6 (tools/gather_bound.py), so a k=2 batch costs nearly the
    same as a single vector — XLA would otherwise issue separate gathers
    per trailing-axis slice (measured 1.14× instead of ~2× per-vector).
    """
    if not enabled:
        return lambda i: x[i]
    xs = split_parts(x)
    tail = xs.shape[1:]
    flat = xs.reshape(xs.shape[0], -1)
    return lambda i: join_parts(flat[i].reshape(i.shape + tail), dtype)


def _split3(x):
    a = x.astype(jnp.float32)
    r = x - a.astype(jnp.float64)
    b = r.astype(jnp.float32)
    c = (r - b.astype(jnp.float64)).astype(jnp.float32)
    return jnp.stack([a, b, c], axis=-1)


def _join3(g):
    return (g[..., 0].astype(jnp.float64) + g[..., 1].astype(jnp.float64)
            + g[..., 2].astype(jnp.float64))


def split_parts(x):
    """f64 ``[...]`` → f32 ``[..., 3]``; c128 ``[...]`` → f32 ``[..., 6]``."""
    if jnp.iscomplexobj(x):
        return jnp.concatenate([_split3(x.real), _split3(x.imag)], axis=-1)
    return _split3(x)


def join_parts(g, dtype):
    """Inverse of :func:`split_parts` on gathered rows (consumes last axis)."""
    if jnp.issubdtype(jnp.dtype(dtype), jnp.complexfloating):
        return jax.lax.complex(_join3(g[..., :3]), _join3(g[..., 3:]))
    return _join3(g)
