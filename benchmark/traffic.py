"""The one generator of traffic: it reads a mix's parameters from
``benchmark/traffic/<name>.json`` and drives the system with them.

Two kinds are known, both closed loops of one client (a solver run has one
caller, who waits for each answer before asking for the next):

``apply``         ``y = eng.matvec(x)`` on one unit-norm standard-normal
                  ``x`` made from the seed, waited for, again and again.
                  Where the configuration states a complex sector,
                  ``x = (a + i b) / ||a + i b||`` with ``a`` the real
                  sector's draw and ``b`` from a stream of its own; it
                  crosses to the device once, before the window, in the
                  engine's (re, im) layout.
``ground_state``  whole ground-state solves, one after the other.  A solve
                  is one request: the one in flight when ``--seconds`` have
                  passed runs to its end and is counted and checked like
                  the others ("late, not wrong"), so the window is a whole
                  number of solves and every answer in it can be compared.
                  Every solve starts from the vector the traffic file
                  names (``start_seed``; ``null`` is the app's own fixed
                  start), whatever ``--seed`` is: the start vector decides
                  whether the solver needs four blocks of iterations or
                  five, and a run that does other work than the next
                  reads another time per iteration.  ``--seed`` draws the
                  rows the check compares.

A new mix of a known kind is a new JSON file.  A new kind (block Lanczos,
KPM moments, a burst of service jobs) is a class here with ``warm_up``,
``window`` and the answers its check compares, registered in ``KINDS``.
"""

import json
import os
import time

import numpy as np

from . import check, work

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def _seed_sequence(seed, stream):
    """Independent streams of one ``--seed`` (any whole number)."""
    return np.random.SeedSequence([int(seed) & 0xFFFFFFFFFFFFFFFF, stream])


class _Mix:
    def limits(self):
        """The limit of each number compared, from the traffic file."""
        return self.params["limits"]

    def reference(self, config):
        return check.Reference(config, self.seed,
                               int(self.params["check_rows"]))


class Apply(_Mix):

    def __init__(self, params, seed):
        self.params, self.seed = params, seed
        self.x = self.answers = None

    def prepare(self, system, n_states):
        """The window's input, from the seed."""
        rng = np.random.default_rng(_seed_sequence(self.seed, 0))
        x = rng.standard_normal(n_states)
        if work.is_complex(system.config):
            rng = np.random.default_rng(_seed_sequence(self.seed, 1))
            x = x + 1j * rng.standard_normal(n_states)
        self.x = x / np.linalg.norm(x)
        self.xd = system.to_device(self.x)

    def warm_up(self, system, n_states):
        self.prepare(system, n_states)
        for _ in range(int(self.params["warm_up_applies"])):
            system.apply(self.xd).block_until_ready()

    def window(self, system, seconds, annotate):
        first = last = None
        done = 0
        t0 = time.perf_counter()
        while True:
            with annotate("bench/apply"):
                last = system.apply(self.xd)
                last.block_until_ready()
            done += 1
            if first is None:
                first = last
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        self.answers = [first, last]
        return {"elapsed_s": elapsed, "applies": done, "requests": done}

    def collect(self, system):
        """Host copies of the window's first and last result."""
        out = [system.to_block(y) for y in self.answers]
        self.answers = self.xd = None          # the device's copies go
        return out

    def compare(self, ref, collected):
        return check.compare_apply(ref, self.x, collected)

    def control(self, ref, collected):
        """The control's answers in the place of the program's."""
        return [check.control_apply(ref, self.x)]


class GroundState(_Mix):

    def __init__(self, params, seed):
        self.params, self.seed = params, seed
        self.answers = []

    def prepare(self, system, n_states):
        """Nothing: the solver makes its own start vector."""

    def warm_up(self, system, n_states):
        """The solver at the window's own shapes.  ``warm_up_iters`` of the
        traffic file is one block: the probe apply, both block programs and
        (``warm_epilogue``) the Ritz-vector combination.  A configuration
        whose programs depend on more than shapes states
        ``"solver_warm_up_iters": null`` and gets one whole solve: on a
        mesh the block a solve redoes and its epilogue take the Krylov
        buffer as the block program left it, not as the solver first laid
        it out, and compile again for that."""
        iters = system.config.get("solver_warm_up_iters",
                                  self.params["warm_up_iters"])
        if iters is None:
            self._solve(system)
            return
        self._solve(system, max_iters=int(iters))
        system.warm_epilogue(self.params)

    def _solve(self, system, **changed):
        return system.solve(dict(self.params, **changed),
                            self.params["start_seed"])

    def window(self, system, seconds, annotate):
        iterations = 0
        t0 = time.perf_counter()
        while True:
            with annotate("bench/solve"):
                solve = self._solve(system)
            self.answers.append(solve)
            iterations += solve.iterations
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        return {"elapsed_s": elapsed, "iterations": iterations,
                "solves": len(self.answers), "requests": len(self.answers),
                "restarts": sum(s.restarts for s in self.answers)}

    def collect(self, system):
        """Every solve of the window, its Ritz vector on the host."""
        out = [{"eigenvalue": s.eigenvalue, "residual": s.residual,
                "converged": s.converged, "iterations": s.iterations,
                "vector": s.vector()} for s in self.answers]
        self.answers = []                      # the device's copies go
        return out

    def compare(self, ref, collected):
        return check.compare_eigenpairs(ref, self.params, collected)

    def control(self, ref, collected):
        """The control's answers in the place of the program's."""
        return check.control_eigenpairs(ref, collected)


KINDS = {"apply": Apply, "ground_state": GroundState}


def make(name, seed):
    params = load(name)
    try:
        kind = KINDS[params["kind"]]
    except KeyError:
        raise ValueError(f"traffic {name!r}: unknown kind "
                         f"{params.get('kind')!r}") from None
    return kind(params, seed)
