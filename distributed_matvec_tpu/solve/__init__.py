"""Solvers (L6) — eigenpairs (the PRIMME/Diagonalize analog, SURVEY.md
§7.7) plus the dynamics family (DESIGN.md §29): Chebyshev/KPM spectral
densities, Krylov time evolution — every solver drives the same engines
through the same matvec contract."""

from .evolve import EvolveResult, krylov_evolve  # noqa: F401
from .kpm import (KPMResult, exact_moments, jackson_kernel,  # noqa: F401
                  kpm_dos, kpm_moments, kpm_spectral_function,
                  lorentz_kernel, reconstruct_dos, spectral_bounds)
from .lanczos import (LanczosResult, krylov_buffer, lanczos,  # noqa: F401
                      lanczos_block)
from .lobpcg import lobpcg  # noqa: F401

# module aliases so the refusal-message pointers ("solve.kpm",
# "solve.evolve") resolve as written
from . import evolve, kpm  # noqa: F401, E402
