"""The system under test, as the benchmark drives it.

The only file of the benchmark that imports ``distributed_matvec_tpu``.  It
calls the entries a user's run goes through — ``load_config_from_yaml``,
``basis.build()``, ``LocalEngine`` / ``DistributedEngine``, ``eng.matvec``
and ``solve.lanczos(eng.matvec, ...)`` as ``apps/diagonalize.py`` calls them
— and hands back plain arrays and numbers.  It measures nothing and checks
nothing: the clock, the trace and the comparison live beside it.
"""

import os

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Solve:
    """What one ``lanczos`` call returned, kept on the device until the
    window has closed."""

    def __init__(self, result, to_block):
        self.iterations = int(result.num_iters)
        self.converged = bool(result.converged)
        self.restarts = int(result.restarts)
        self.eigenvalue = float(result.eigenvalues[0])
        self.residual = float(result.residual_norms[0])
        self._vector = result.eigenvectors[0]
        self._to_block = to_block

    def vector(self):
        """The Ritz vector on the host, in the order of the sorted basis."""
        return np.asarray(self._to_block(self._vector), np.float64)


class System:
    def __init__(self, config):
        self.config = config
        self.engine = self.basis = self.operator = None

    # -- set-up -----------------------------------------------------------

    def start(self):
        """Import the program and point its caches into the checkout.
        Returns JAX's device list."""
        # every run enumerates and builds its structure, as a user with a
        # new Hamiltonian does; nothing is read or written under $HOME
        os.environ["DMT_ARTIFACT_CACHE"] = "off"
        # programs under the 1 s threshold of utils/cache.py are cached too
        os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                              "0")
        import jax
        from distributed_matvec_tpu.utils.cache import (
            enable_compilation_cache)

        self.cache_dir = enable_compilation_cache()
        return jax.devices()

    def enumerate(self):
        import distributed_matvec_tpu as dmt

        cfg = dmt.load_config_from_yaml(
            os.path.join(ROOT, self.config["model"]), hamiltonian=True)
        cfg.basis.build()
        self.basis, self.operator = cfg.basis, cfg.hamiltonian
        return int(cfg.basis.number_states)

    def build_engine(self):
        eng = self.config["engine"]
        if eng["kind"] == "local":
            from distributed_matvec_tpu.parallel.engine import LocalEngine

            self.engine = LocalEngine(self.operator, mode=eng.get("mode"))
            self._build_scope = "build_structure"
        elif eng["kind"] == "distributed":
            from distributed_matvec_tpu.parallel.distributed import (
                DistributedEngine)

            self.engine = DistributedEngine(
                self.operator, n_devices=int(eng["devices"]),
                mode=eng.get("mode"))
            self._build_scope = "build_plan"
        else:
            raise ValueError(f"unknown engine kind {eng['kind']!r}")
        if getattr(self.engine, "structure_restored", False):
            raise RuntimeError("the engine restored its structure from an "
                               "artifact: the run did not build it")

    def timers(self):
        """The program's own spans that the set-up metrics read."""
        t = self.engine.timer
        return {"structure_build_s": t.scope_total(self._build_scope),
                "structure_compile_s": t.scope_total(self._build_scope,
                                                     "compile")}

    @property
    def hashed(self):
        return hasattr(self.engine, "to_hashed")

    # -- the timed entries -----------------------------------------------

    def to_device(self, x):
        """A host vector in basis order, as the engine's ``matvec`` takes
        it."""
        import jax.numpy as jnp

        return self.engine.to_hashed(x) if self.hashed else jnp.asarray(x)

    def to_block(self, y):
        """A device vector back on the host in basis order."""
        return self.engine.from_hashed(y) if self.hashed else np.asarray(y)

    def apply(self, xd):
        return self.engine.matvec(xd)

    def solve(self, params, start_seed=None):
        """One ground-state solve as ``apps/diagonalize.py`` makes it.  The
        app's start vector is fixed, and so is the default here:
        ``lanczos``'s own default seed on one chip, ``random_hashed(seed=42)``
        on a mesh.  ``start_seed`` gives another start vector."""
        from distributed_matvec_tpu.solve import lanczos

        eng, start = self.engine, {}
        if self.hashed:
            start["v0"] = eng.random_hashed(
                seed=42 if start_seed is None else start_seed)
        else:
            start["n"] = eng.n_states
            if start_seed is not None:
                start["seed"] = start_seed
        res = lanczos(
            eng.matvec, k=int(params["k"]),
            tol=float(params["tol"]), max_iters=int(params["max_iters"]),
            max_basis_size=params.get("max_basis_size"),
            min_restart_size=params.get("min_restart_size"),
            compute_eigenvectors=bool(params["eigenvectors"]), **start)
        return Solve(res, self.to_block)

    def warm_epilogue(self, params):
        """Compile the solver's Ritz-vector combination for every basis
        size a solve can end at.  ``lanczos`` jits it per number of rows,
        so the one block of the warm-up leaves the sizes a whole solve ends
        at (64 rows as a rule) to the window.  A private name of the
        program: where it is gone, the warm-up goes without."""
        import importlib

        import jax.numpy as jnp

        # the package re-exports the function under the module's name
        module = importlib.import_module(
            "distributed_matvec_tpu.solve.lanczos")
        combine = getattr(module, "_combine_rows", None)
        rows_of = getattr(module, "_buffer_rows", None)
        if combine is None or rows_of is None:
            return
        eng, cap = self.engine, int(params["max_basis_size"])
        v = eng.random_hashed(seed=0) if self.hashed \
            else jnp.zeros(eng.n_states, jnp.float64)
        # the buffer as the solver makes it, so that its layout is the same
        V = jnp.zeros((rows_of(cap),) + v.shape, v.dtype).at[0].set(v)
        Vf = V.reshape(V.shape[0], -1)
        step = int(params["warm_up_iters"])
        for m in range(step, cap + 1, step):
            combine(jnp.ones((m, int(params["k"])), v.dtype),
                    Vf).block_until_ready()

    def memory_peak_bytes(self):
        """``peak_bytes_in_use`` of the fullest device."""
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.devices()]
        peaks = [p for p in peaks if p is not None]
        if not peaks:
            raise RuntimeError("the backend reports no memory statistics")
        return int(max(peaks))

    def close(self):
        """Free the program's state on the device."""
        self.engine = self.basis = self.operator = None
