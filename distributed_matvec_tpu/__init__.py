"""distributed_matvec_tpu — a TPU-native framework for distributed matrix-free
quantum-Hamiltonian linear algebra.

A from-scratch JAX/XLA re-design with the capabilities of
``twesterhout/distributed-matvec`` (Chapel + GASNet + Haskell kernels +
PRIMME): symmetry-reduced basis enumeration, hash-sharded state distribution
over a ``jax.sharding.Mesh``, matrix-free ``y = H·x`` with on-device operator
application and ICI ``all_to_all`` amplitude routing, layout shuffles, and
iterative eigensolvers (Lanczos/LOBPCG).

Layers (bottom → top; compare SURVEY.md §1):
  utils/        — config flags, logging, tree timers               (L-cross)
  models/       — expressions → nonbranching terms, symmetry groups,
                  bases, operators, YAML configs, lattice builders (L2)
  enumeration/  — representative enumeration (host)                (L4)
  ops/          — jitted device kernels (diag/off-diag apply,
                  state_info orbit scans, searchsorted indexing)   (L5)
  parallel/     — mesh/sharding, all_to_all matvec engine,
                  block↔hashed shuffles, collective reductions     (L0/L5)
  solve/        — eigensolvers (Lanczos, LOBPCG) + drivers         (L6)
"""

# Basis states are uint64 bitstrings and the accuracy contract is double
# precision (atol 1e-14 / rtol 1e-12 — reference TestMatrixVectorProduct.chpl:15-16),
# so 64-bit types are a hard requirement, enabled before any tracing happens.
# (On TPU, XLA lowers u64/f64 to 32-bit pairs; the hot kernels are
# integer/VPU-bound so the cost is acceptable — see SURVEY.md §7 hard part 4.)
import jax as _jax

_jax.config.update("jax_enable_x64", True)

from . import models, utils  # noqa: F401
from .models.basis import SpinBasis, SpinfulFermionBasis, SpinlessFermionBasis
from .models.operator import Operator
from .models.yaml_io import Config, load_config_from_yaml
from .utils.config import get_config, update_config

__version__ = "0.1.0"

__all__ = [
    "SpinBasis",
    "SpinlessFermionBasis",
    "SpinfulFermionBasis",
    "Operator",
    "Config",
    "load_config_from_yaml",
    "get_config",
    "update_config",
    "__version__",
]
