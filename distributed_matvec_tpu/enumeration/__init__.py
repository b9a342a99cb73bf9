"""Basis-state enumeration: portable NumPy path + native C++ kernel.

Dispatch (the ``_enumerateStates`` analog, StatesEnumeration.chpl:257-265):
the streaming C++ kernel handles projected sectors (compiled on first use,
``native.py``); the NumPy path covers trivial/spin-inversion-only sectors.
The choice is made by sector, never by a failed build: a kernel that cannot
be built raises ``native.NativeBuildError``.  ``enumeration_backend`` config:
``auto`` | ``native`` (same dispatch) | ``numpy`` (NumPy for every sector —
the tests' reference, and the explicit way out on a host with no compiler).
"""

from typing import Optional, Tuple

import numpy as np

from . import host  # noqa: F401
from ..utils.config import get_config

__all__ = ["host", "enumerate_representatives"]


def enumerate_representatives(
    n_sites: int, hamming_weight: Optional[int], group
) -> Tuple[np.ndarray, np.ndarray]:
    from ..obs.metrics import counter
    from ..utils.timers import timed

    backend = get_config().enumeration_backend
    projected = group is not None and not group.is_trivial
    spin_inv_only = (
        projected and len(group.perms) == 2 and group.flip[1]
        and group.networks[1].shifts == (0,)
    )
    if backend != "numpy" and projected and not spin_inv_only:
        from . import native

        counter("enumeration", backend="native").inc()
        with timed(f"enumerate[native] n={n_sites} hw={hamming_weight} "
                   f"G={len(group)}"):
            return native.enumerate_representatives_native(
                n_sites, hamming_weight, group)
    counter("enumeration", backend="numpy").inc()
    with timed(f"enumerate[numpy] n={n_sites} hw={hamming_weight}"):
        return host.enumerate_representatives(n_sites, hamming_weight, group)
