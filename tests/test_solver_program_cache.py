"""Each block program of a solve has one argument signature: on a mesh the
recurrence's (α, β) are born replicated, as the programs leave them, so the
full-sweep program is traced once a solve, not twice.  The thick restart's
program closes over no engine and is kept by its geometry between solves,
so a program built ahead of a solve is the one the solve runs."""

import gc
import importlib
import weakref

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from distributed_matvec_tpu import obs

from test_operator import build_heisenberg

L = importlib.import_module("distributed_matvec_tpu.solve.lanczos")
lanczos = L.lanczos


@pytest.fixture
def clean_obs():
    obs.reset_all()
    L._make_restart.cache_clear()
    yield
    obs.reset_all()


def _op(n_sites=12):
    op = build_heisenberg(n_sites, n_sites // 2)
    op.basis.build()
    return op


def _engine(op, where):
    if where == "one_device":
        from distributed_matvec_tpu.parallel.engine import LocalEngine

        return LocalEngine(op, mode="ell")
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine

    return DistributedEngine(op, n_devices=4)


def _start(eng, seed=5):
    if hasattr(eng, "random_hashed"):
        return dict(v0=eng.random_hashed(seed=seed))
    return dict(n=eng.n_states, seed=seed)


def _solve(matvec, **kw):
    """The solve's result and its root span's counts."""
    kw = dict(dict(k=1, tol=1e-10), **kw)
    res = lanczos(matvec, **kw)
    root = [e for e in obs.events("span") if e["name"] == "lanczos"][-1]
    return res, root


WHERE = ["one_device", "four_devices"]
RESTARTING = dict(max_basis_size=24, check_every=8)


@pytest.mark.parametrize("where", WHERE)
def test_the_full_sweep_is_traced_once_in_one_solve(clean_obs, monkeypatch,
                                                    where):
    """A thick-restarting selective solve runs the full-sweep program first
    with zero steps and again after every restart: one trace, one compiled
    program, for all of them, on one device and on the mesh."""
    eng = _engine(_op(), where)
    traces, runs, programs = [], [], []
    make = L._make_block_runner

    def counting(mv, *a, **k):
        def traced(x, ops):
            traces.append(x.shape)      # runs only while tracing
            return mv(x, ops)
        run_block = make(traced, *a, **k)
        programs.append(run_block)

        def run(*args):
            runs.append(int(args[4]))
            return run_block(*args)
        return run

    monkeypatch.setattr(L, "_make_block_runner", counting)
    res, root = _solve(eng.matvec, **RESTARTING, **_start(eng))
    assert res.converged and res.restarts >= 1
    assert runs[0] == 0 and len(runs) >= 1 + res.restarts
    assert len(traces) == 1
    (run_block,) = programs
    assert run_block._cache_size() == 1


@pytest.mark.parametrize("where", WHERE)
def test_the_recurrence_is_born_where_the_programs_leave_it(where):
    """Replicated over the start vector's mesh, else committed to its
    device; a start vector that is not committed leaves them uncommitted,
    as the parent's ``jnp.zeros`` were."""
    eng = _engine(_op(10), where)
    start = eng.random_hashed(seed=1) if where == "four_devices" \
        else jax.device_put(np.ones(eng.n_states), jax.devices()[0])
    zeros = L._recurrence_zeros(24, start)
    assert zeros.shape == (24,) and zeros.dtype == np.float64
    assert not np.asarray(zeros).any() and zeros.committed
    if where == "four_devices":
        assert zeros.sharding == NamedSharding(start.sharding.mesh,
                                               PartitionSpec())
    else:
        assert zeros.sharding == start.sharding
    assert not L._recurrence_zeros(8, jax.numpy.ones(3)).committed


@pytest.mark.parametrize("where", WHERE)
def test_a_second_restarting_solve_takes_the_kept_restart(clean_obs, where):
    """The block programs live for the call; the thick restart's program
    is the first solve's, and the second solve repeats the first bit for
    bit."""
    eng = _engine(_op(), where)
    kw = dict(RESTARTING, **_start(eng))
    first, r1 = _solve(eng.matvec, **kw)
    second, r2 = _solve(eng.matvec, **kw)
    assert first.converged and second.converged and first.restarts
    assert (r1["programs_built"], r1["programs_reused"]) == (2, 0)
    assert (r2["programs_built"], r2["programs_reused"]) == (2, 1)
    assert second.eigenvalues.tobytes() == first.eigenvalues.tobytes()
    assert second.residual_norms.tobytes() == first.residual_norms.tobytes()
    assert (second.num_iters, second.restarts) == (first.num_iters,
                                                   first.restarts)
    assert r2["steps_run"] == r1["steps_run"]


# what the second solve changes, and whether it takes the first's restart
SECOND = {"same": ({}, 1), "max_basis_size": ({"max_basis_size": 28}, 0),
          "min_restart_size": ({"min_restart_size": 6}, 0),
          "no_restart": ({"max_basis_size": None}, 0)}


@pytest.mark.parametrize("second", list(SECOND))
def test_the_restart_is_kept_by_its_geometry(clean_obs, second):
    """Another cap or restart size builds its own restart program; a solve
    that never restarts counts none, though its geometry's is kept."""
    op = _op()
    eng = _engine(op, "one_device")
    first, _ = _solve(eng.matvec, **RESTARTING, **_start(eng))
    kw, reused = SECOND[second]
    res, root = _solve(eng.matvec, **dict(RESTARTING, **kw), **_start(eng))
    assert res.converged
    assert bool(res.restarts) == (second != "no_restart")
    assert root["programs_reused"] == reused
    np.testing.assert_allclose(res.eigenvalues, first.eigenvalues,
                               rtol=1e-10)


@pytest.mark.parametrize("where", WHERE)
def test_a_solve_does_not_hold_the_engine(clean_obs, where):
    """The kept restart program closes over no engine: once the caller lets
    the engine go, it goes."""
    eng = _engine(_op(), where)
    res, _ = _solve(eng.matvec, **RESTARTING, **_start(eng))
    assert res.restarts
    gone = weakref.ref(eng)
    del eng, res
    gc.collect()
    assert gone() is None


def test_a_plain_callable_solves_as_an_engine_does(clean_obs):
    """A closure over the engine's ``matvec`` traces the matvec eagerly
    into the same programs: the same counts, and the same eigenvalue."""
    op = _op()
    eng = _engine(op, "one_device")
    start = dict(n=op.basis.number_states, seed=5)
    ref, r0 = _solve(eng.matvec, **start)
    res, root = _solve(lambda x: eng.matvec(x), **start)
    assert res.converged
    assert (root["programs_built"], root["programs_reused"]) == (2, 0)
    assert r0["programs_built"] == 2
    np.testing.assert_allclose(res.eigenvalues, ref.eigenvalues, rtol=1e-12)
