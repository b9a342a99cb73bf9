"""The system under test, as the benchmark drives it.

The only file of the benchmark that imports ``distributed_matvec_tpu``.  It
calls the entries a user's run goes through — ``load_config_from_yaml``,
``basis.build()``, ``LocalEngine`` / ``DistributedEngine``, ``eng.matvec``
and ``solve.lanczos(eng.matvec, ...)`` as ``apps/diagonalize.py`` calls them
— and hands back plain arrays, numbers and the program's own events.  It
measures nothing and checks nothing: the clock, the trace and the comparison
live beside it.

A configuration that states ``"sector": "complex"`` (``work.is_complex``)
runs in pair form: the host side of the benchmark holds complex128 vectors
in basis order, the engine takes and returns float64 arrays with a trailing
(re, im) axis, and the two meet here, outside the window, through the
program's ``pair_from_complex`` / ``complex_from_pair``.
"""

import os

import numpy as np

from . import work

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the keys every event of the program carries, whatever its kind
ENVELOPE = ("seq", "ts", "proc", "rank", "n_ranks", "kind", "trace_id",
            "job_id", "span_id")


def program_ring():
    """The program's in-memory event ring, oldest first (the newest 65,536
    events of the process); [] for a program that keeps none."""
    try:
        from distributed_matvec_tpu.obs.events import events
    except ImportError:
        return []
    return events()


def whole_ring():
    """The whole ring in the shape of a run's events, for a reader that is
    called outside the harness with a run that was handed none (``tests/
    test_span_surface.py`` reads ``gather_fill_pct`` off the one engine it
    has just built): every event counts as the build's and as the window's,
    and events are lost where the ring no longer starts at the first."""
    ring = program_ring()
    return {"build": ring, "window": ring,
            "lost": bool(ring) and int(ring[0].get("seq", 0)) > 0}


class Solve:
    """What one ``lanczos`` call returned, kept on the device until the
    window has closed."""

    def __init__(self, result, to_block, dtype=np.float64):
        self.iterations = int(result.num_iters)
        self.converged = bool(result.converged)
        self.restarts = int(result.restarts)
        self.eigenvalue = float(result.eigenvalues[0])
        self.residual = float(result.residual_norms[0])
        self._vector = result.eigenvectors[0]
        self._to_block, self._dtype = to_block, dtype

    def vector(self):
        """The Ritz vector on the host, in the order of the sorted basis:
        float64, or complex128 for a complex sector."""
        return np.asarray(self._to_block(self._vector), self._dtype)


class System:
    def __init__(self, config):
        self.config = config
        self.complex = work.is_complex(config)
        self.engine = self.basis = self.operator = None
        self._events = {"build": [], "window": [], "lost": False}
        self._mark = None

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
        """Build the engine the configuration names, keep the events the
        program emitted meanwhile, and hold the engine to the sector the
        configuration states."""
        self._mark_events()
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
        self._events["build"] = self._events_since_mark()
        if getattr(self.engine, "structure_restored", False):
            raise RuntimeError("the engine restored its structure from an "
                               "artifact: the run did not build it")
        pair, real = bool(self.engine.pair), bool(self.engine.real)
        if pair != self.complex or real == self.complex:
            raise RuntimeError(
                f"the configuration states a {work.sector(self.config)} "
                f"sector and the engine came up with pair={pair}, "
                f"real={real}: the configuration's files disagree (or a "
                "complex sector did not get its (re, im) pair form)")

    def timers(self):
        """The program's own spans that the set-up metrics read."""
        t = self.engine.timer
        return {"structure_build_s": t.scope_total(self._build_scope),
                "structure_compile_s": t.scope_total(self._build_scope,
                                                     "compile")}

    @property
    def hashed(self):
        return hasattr(self.engine, "to_hashed")

    # -- the program's own events -----------------------------------------

    def _mark_events(self):
        ring = program_ring()
        self._mark = ring[-1] if ring else None

    def _events_since_mark(self):
        """What the program emitted since ``_mark_events``.  The ring keeps
        the newest 65,536 events: where the mark has left it, what is kept
        is handed over and the snapshot says that some is lost."""
        ring = program_ring()
        if self._mark is None:
            return ring
        for i in range(len(ring) - 1, -1, -1):
            if ring[i] is self._mark:
                return ring[i + 1:]
        self._events["lost"] = True
        return ring

    def open_window(self):
        self._mark_events()

    def close_window(self):
        """This run's events: ``build`` (emitted while the engine was
        built) and ``window`` (between ``open_window`` and here), and
        whether the program's ring let some of them go."""
        self._events["window"] = self._events_since_mark()
        return self._events

    def engine_counts(self):
        """The payload of the ``engine_init`` event of this run's build:
        the engine's form and its counts (``pair``, ``mode``,
        ``table_ranges``, ``near_slots``, ``far_slots``, ``row_blocks``,
        ``scanned_columns``, ...), so that a result line says what it
        ran."""
        inits = [e for e in self._events["build"]
                 if e.get("kind") == "engine_init"]
        if not inits:
            return {}
        return {k: v for k, v in inits[-1].items() if k not in ENVELOPE}

    # -- the timed entries -----------------------------------------------

    def to_device(self, x):
        """A host vector in basis order, as the engine's ``matvec`` takes
        it.  A complex sector's vector becomes the engine's own layout here,
        once: float64 with a trailing (re, im) axis, ``[N, 2]`` on one chip
        and whatever ``to_hashed`` makes of that on a mesh, so that the
        timed ``apply`` never converts on the host."""
        import jax.numpy as jnp

        if self.complex:
            from distributed_matvec_tpu.ops.kernels import pair_from_complex

            x = pair_from_complex(np.asarray(x, np.complex128))
        return self.engine.to_hashed(x) if self.hashed else jnp.asarray(x)

    def to_block(self, y):
        """A device vector back on the host in basis order (complex128 for
        a complex sector)."""
        y = self.engine.from_hashed(y) if self.hashed else np.asarray(y)
        if self.complex:
            from distributed_matvec_tpu.ops.kernels import complex_from_pair

            y = complex_from_pair(y)
        return y

    def apply(self, xd):
        return self.engine.matvec(xd)

    def solve(self, params, start_seed=None):
        """One ground-state solve as ``apps/diagonalize.py`` makes it.  The
        app's start vector is fixed, and so is the default here:
        ``lanczos``'s own default seed on one chip, ``random_hashed(seed=42)``
        on a mesh.  ``start_seed`` gives another start vector.  For a pair
        engine the app passes the same arguments: ``lanczos`` reads ``pair``
        off the engine behind ``matvec`` and draws an ``[N, 2]`` start
        vector, and ``build_engine`` has held the engine to the
        configuration's sector."""
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
        return Solve(res, self.to_block,
                     np.complex128 if self.complex else np.float64)

    def warm_epilogue(self, params):
        """Compile the solver's Ritz-vector combination for every basis
        size a solve can end at.  ``lanczos`` jits it per number of rows,
        so the one block of the warm-up leaves the sizes a whole solve ends
        at (64 rows as a rule) to the window.  Where the traffic says that
        its solves restart (``"warm_restart": true``: under its cap every
        solve does), the thick restart's program too, at the restart size
        the solver works out for this cap: one block never fills the buffer,
        so the first solve of a cold compile cache would compile it inside
        the window (1.97 s of a 26 s solve; my chip run, PR 34).  Private
        names of the program: where one is gone, or works its sizes out
        otherwise, the warm-up goes without and
        ``compilations_in_window.solve`` says so."""
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
            else jnp.zeros((eng.n_states,) + ((2,) if self.complex else ()),
                           jnp.float64)
        # the buffer as the solver makes it, so that its layout is the same
        # (a pair vector's rows are [N, 2], flattened as the solver does)
        V = jnp.zeros((rows_of(cap),) + v.shape, v.dtype).at[0].set(v)
        Vf = V.reshape(V.shape[0], -1)
        step = int(params["warm_up_iters"])
        k = int(params["k"])
        for m in range(step, cap + 1, step):
            combine(jnp.ones((m, k), v.dtype), Vf).block_until_ready()
        make_restart = getattr(module, "_make_restart", None)
        if params.get("warm_restart") and make_restart is not None:
            # lanczos's own choice of how many Ritz vectors a restart keeps
            keep = params.get("min_restart_size") \
                or max(2 * k + 2, min(cap // 3, 24))
            keep = int(np.clip(keep, k, cap - 2))
            del Vf                      # the restart donates the buffer
            make_restart(cap, v.shape, v.dtype, keep)(
                V, jnp.zeros((cap, keep), v.dtype)).block_until_ready()

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
