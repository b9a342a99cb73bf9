"""The comparison that decides ``correct``.

What the timed path produced in the window — the applies' results, the
solves' Ritz pairs — is compared with the configuration's plain reference
(``benchmark/references/<name>.py``), which imports nothing of the program
and is given nothing the program made: it enumerates the basis itself from
the YAML, applies H to sampled rows from the definition, and has a ground
energy of its own (the ring's from the Bethe ansatz).  A reference is a
module with ``Spec(path)``, ``enumerate_representatives(spec)``,
``apply_rows(spec, reps, x, rows, dtype)``, ``count_offdiagonal(spec, reps,
rows)`` and ``ground_energy(spec)``.  Where the configuration states a
complex sector (``work.is_complex``) the vectors compared are complex128 on
the host, and everything here is complex arithmetic: ``|.|`` is the modulus
and a squared norm is ``sum |r|^2``.  Every number compared has a
limit of its own; a run is correct when each number is at or under its
limit (a NaN is over it).  Limits come from the traffic file, and those the
configuration states itself (the apply contract, the solver's tolerance)
are 1 on a number already divided by them.
"""

import importlib.util
import os
import sys

import numpy as np

from . import work

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_reference(config):
    """(module, spec) of the configuration's plain reference."""
    name = config["reference"]
    path = os.path.join(HERE, "references", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_reference_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, mod.Spec(os.path.join(ROOT, config["model"]))


def sample_rows(seed, n_states, count):
    """Sorted rows to compare, drawn from the seed."""
    rng = np.random.default_rng(
        np.random.SeedSequence([int(seed) & 0xFFFFFFFFFFFFFFFF, 99]))
    return np.sort(rng.choice(n_states, size=min(count, n_states),
                              replace=False))


class Reference:
    """The reference's own basis, built once per run after the window."""

    def __init__(self, config, seed, count):
        self.config = config
        self.mod, self.spec = load_reference(config)
        self.reps = self.mod.enumerate_representatives(self.spec)
        self.rows = sample_rows(seed, self.reps.size, count)

    def resample(self, seed):
        """Other rows for another seed; the basis stays."""
        self.rows = sample_rows(seed, self.reps.size, self.rows.size)

    def apply_rows(self, x, dtype=None):
        """(H x)[rows] in ``dtype`` arithmetic; by default in the precision
        the configuration states."""
        if dtype is None:
            dtype = _precisions(self.config)[0]
        return self.mod.apply_rows(self.spec, self.reps, x, self.rows, dtype)

    def e0(self):
        """The ground energy a Ritz value is compared with: the
        configuration's own ``ground_energy`` where its file brings one
        (stored beside how it was computed, as a reference that has no
        closed form asks), else the reference's."""
        stored = self.config.get("ground_energy")
        if stored is not None:
            return float(stored)
        return self.mod.ground_energy(self.spec)


def _precisions(config):
    """(what the configuration states, the nearest precision below it)."""
    if work.is_complex(config):
        return np.complex128, np.complex64
    return np.float64, np.float32


def compare_apply(ref, x, answers):
    """Numbers of the ``apply_rows`` check: ``answers`` are the host copies
    of what ``eng.matvec(x)`` returned in the window."""
    g = ref.config["guarantees"]
    out = {"basis_size_diff": max(abs(len(y) - ref.reps.size)
                                  for y in answers)}
    if out["basis_size_diff"]:
        out["apply_err_over_tol"] = float("nan")
        return out
    want = ref.apply_rows(x)
    # np.abs is the modulus: of complex numbers where the sector is complex
    tol = g["apply_atol"] + g["apply_rtol"] * np.abs(want)
    out["apply_err_over_tol"] = float(max(
        np.max(np.abs(np.asarray(y)[ref.rows] - want) / tol)
        for y in answers))
    return out


def compare_eigenpairs(ref, params, solves):
    """Numbers of the ``eigenpair`` check, the worst over the window's
    solves: each of ``solves`` is a dict with ``eigenvalue``, ``residual``
    (the solver's own estimate), ``converged`` and ``vector`` (host, basis
    order)."""
    tol = float(params["tol"])
    e0 = ref.e0()
    n = ref.reps.size
    out = {"basis_size_diff": max(abs(len(s["vector"]) - n) for s in solves),
           "unconverged_solves": sum(not s["converged"] for s in solves)}
    worst = {"claimed_residual_over_tol": 0.0, "residual_over_tol": 0.0,
             "e0_rel_err": 0.0, "norm_err": 0.0}
    if out["basis_size_diff"]:
        return dict(out, **{k: float("nan") for k in worst})
    for s in solves:
        theta, v = float(s["eigenvalue"]), np.asarray(s["vector"])
        scale = tol * max(1.0, abs(theta))
        # ||H v - theta v|| from the sampled rows: an unbiased estimate of
        # the squared norm sum |r|^2, by the reference's H; theta is real
        r = ref.apply_rows(v) - theta * v[ref.rows]
        est = np.sqrt(n / ref.rows.size
                      * float(np.sum((r * np.conj(r)).real)))
        got = {"claimed_residual_over_tol": float(s["residual"]) / scale,
               "residual_over_tol": float(est / scale),
               "e0_rel_err": abs(theta - e0) / abs(e0),
               "norm_err": abs(float(np.linalg.norm(v)) - 1.0)}
        for k, val in got.items():
            # a NaN stays a NaN: max() would drop it
            worst[k] = val if not val <= worst[k] else worst[k]
    return dict(out, **worst)


def control_apply(ref, x):
    """The control of the ``apply_rows`` check: the reference put in the
    program's place and computed in float32, the nearest precision below
    the float64 the configuration states (complex64 below complex128).
    Only the sampled rows are filled; the comparison reads no others."""
    stated, below = _precisions(ref.config)
    y = np.zeros(ref.reps.size, stated)
    y[ref.rows] = ref.apply_rows(x, below)
    return y


def control_eigenpairs(ref, solves):
    """The control of the ``eigenpair`` check: each Ritz pair rounded to
    float32 (the vector of a complex sector to complex64).  A solver that
    computed in float32 could at best return the float32 number nearest to
    each exact component, so what this reads is the least that any float32
    solve could read."""
    stated, below = _precisions(ref.config)
    return [dict(s, eigenvalue=float(np.float32(s["eigenvalue"])),
                 vector=np.asarray(s["vector"]).astype(below)
                 .astype(stated)) for s in solves]


def judge(numbers, limits):
    """({name: {"value", "limit"}}, correct).  A number with no limit is a
    fault of the benchmark's files, not of the run: it raises."""
    table, ok = {}, True
    for name, value in numbers.items():
        limit = limits[name]
        table[name] = {"value": value, "limit": limit}
        ok = ok and bool(value <= limit)
    return table, ok


def report(table, stream=sys.stderr):
    """Each number compared beside its limit, as the run's last lines."""
    for name, row in table.items():
        verdict = "ok" if row["value"] <= row["limit"] else "OVER"
        print(f"check {name}: value={row['value']!r} "
              f"limit={row['limit']!r} {verdict}", file=stream, flush=True)
