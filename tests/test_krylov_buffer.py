"""The Krylov buffer is born once, in one program, with its vector's
sharding (PR 38): ``lanczos/start`` makes it through ``krylov_buffer``, one
jitted program whose only array of the buffer's size is its output, laid out
over the mesh as the start vector is; a checkpoint's rows go in through one
program that donates the buffer.  No eager update of an array of the buffer's
shape is left in ``solve/lanczos.py``: the parent's
``jnp.zeros(...).at[0].set(row)`` held two whole buffers while it ran, and on
a mesh both of them whole on device 0."""

import importlib
import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_matvec_tpu import obs
from distributed_matvec_tpu.obs import memory as obs_memory
from distributed_matvec_tpu.utils.config import update_config

from test_operator import build_heisenberg

L = importlib.import_module("distributed_matvec_tpu.solve.lanczos")
lanczos = L.lanczos


@pytest.fixture
def clean_obs():
    obs.reset_all()
    yield
    obs.reset_all()


def _local(n_sites, syms=()):
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    op = build_heisenberg(n_sites, n_sites // 2, None, list(syms))
    op.basis.build()
    return op, LocalEngine(op, mode="ell")


def _distributed(n_sites, n_devices=4):
    if len(jax.devices()) < n_devices:
        pytest.skip(f"needs {n_devices} devices")
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine

    op = build_heisenberg(n_sites, n_sites // 2)
    op.basis.build()
    return op, DistributedEngine(op, n_devices=n_devices)


def _eager_buffer_programs(mcap, row):
    """The parent's buffer: ``jnp.zeros`` and an eager update of row 0, and
    an eager update a restored row."""
    def make(r):
        return jnp.zeros((L._buffer_rows(mcap),) + r.shape, r.dtype) \
            .at[0].set(r)

    return make, lambda V, i, r: V.at[i].set(r)


# ---------------------------------------------------------------------------
# (a) one program, the buffer its output only; no eager update in a solve


def _main_signature(lowered):
    """(argument types, result types) of a lowered program's ``@main``."""
    text = lowered.as_text()
    head = text[text.index("@main("):]
    head = head[:head.index("{\n")]
    args, _, results = head.partition("->")
    return (re.findall(r"tensor<([^>]*)>", args),
            re.findall(r"tensor<([^>]*)>", results))


@pytest.mark.parametrize("shape", [(1000,), (1000, 2)], ids=["real", "pair"])
def test_the_maker_takes_no_array_of_the_buffers_shape(shape):
    rows = L._buffer_rows(40)
    make, set_row = L._make_buffer_programs(rows, shape, np.dtype("float64"),
                                            None)
    row = jnp.arange(float(np.prod(shape))).reshape(shape) + 1.0
    dims = "x".join(map(str, shape))
    args, results = _main_signature(make.lower(row))
    assert args == [f"{dims}xf64"] and results == [f"{rows}x{dims}xf64"]
    V = make(row)
    assert V.shape == (rows,) + shape and V.dtype == jnp.float64
    np.testing.assert_array_equal(np.asarray(V[0]), np.asarray(row))
    assert not np.asarray(V[1:]).any()
    # the same pair of programs the next time: nothing is traced again
    assert L._buffer_programs(40, row) == (make, set_row)
    # the public name, as a harness imports it, is that program
    from distributed_matvec_tpu import solve
    assert solve.krylov_buffer is L.krylov_buffer
    np.testing.assert_array_equal(np.asarray(L.krylov_buffer(40, row)),
                                  np.asarray(V))


def _programs_traced(caplog):
    return re.findall(r"Finished tracing \+ transforming (\S+) for pjit",
                      caplog.text)


def test_a_solve_dispatches_no_eager_update_of_the_buffer(clean_obs, caplog):
    """Every program jax builds during a solve, by name: the buffer's one
    ``make`` and no ``scatter`` / ``dynamic_update_slice`` (the parent's
    eager ``.at[0].set`` is a program called ``scatter``).  A basis size no
    other test uses, so that no program of these shapes is cached."""
    op, eng = _local(14)
    n = op.basis.number_states
    L._make_buffer_programs.cache_clear()
    with caplog.at_level(logging.WARNING, logger="jax"), \
            jax.log_compiles(True):
        res = lanczos(eng.matvec, n, k=1, tol=1e-10, max_basis_size=53)
        solve = _programs_traced(caplog)
        # the same log sees the parent's form
        _eager_buffer_programs(53, jnp.ones(n))[0](jnp.ones(n))
        parent = _programs_traced(caplog)[len(solve):]
    assert res.converged
    assert solve.count("make") == 1
    assert not [p for p in solve if "scatter" in p or "update" in p], solve
    assert "scatter" in parent


# ---------------------------------------------------------------------------
# (b) on a mesh the buffer is born sharded as the start vector is


def test_the_buffer_is_born_with_the_vectors_sharding(clean_obs, monkeypatch):
    op, eng = _distributed(12)
    v0 = eng.random_hashed(seed=11)
    assert isinstance(v0.sharding, NamedSharding)
    want = NamedSharding(v0.sharding.mesh, P(None, *v0.sharding.spec))

    V = L.krylov_buffer(96, v0)
    rows = L._buffer_rows(96)
    assert V.sharding == want and V.shape == (rows,) + v0.shape
    quarter = (rows, v0.shape[0] // 4) + v0.shape[1:]
    assert [s.data.shape for s in V.addressable_shards] == [quarter] * 4
    assert len({s.device for s in V.addressable_shards}) == 4
    np.testing.assert_array_equal(np.asarray(V[0]), np.asarray(v0))

    # in a solve: what ``lanczos/start`` registers in the memory ledger
    seen = {}
    track_tree = obs_memory.track_tree

    def recording(path, tree, **meta):
        handle = track_tree(path, tree, **meta)
        seen.update(sharding=tree[0].sharding, nbytes=tree[0].nbytes,
                    small=sum(a.nbytes for a in tree[1:]),
                    per_device=dict(
                        obs_memory.ledger_entries()[path]["per_device"]))
        return handle

    monkeypatch.setattr(L.obs_memory, "track_tree", recording)
    res = lanczos(eng.matvec, v0=v0, k=1, tol=1e-10)
    assert res.converged and seen["sharding"] == want
    held = sorted(seen["per_device"].values())
    # a quarter of the buffer on every chip; alpha and beta (768 B each at
    # the default cap) are born on the first device alone
    assert len(held) == 4 and held[:3] == [seen["nbytes"] // 4] * 3
    assert held[3] == seen["nbytes"] // 4 + seen["small"]


def test_one_device_takes_the_same_path_without_a_sharding():
    row = jnp.ones(64)
    assert not isinstance(row.sharding, NamedSharding)
    V = L.krylov_buffer(8, row)
    assert V.devices() == row.devices()
    key = (L._buffer_rows(8), (64,), np.dtype("float64"), None)
    assert L._make_buffer_programs(*key) == L._buffer_programs(8, row)


# ---------------------------------------------------------------------------
# (c) the buffer's bits are the parent's: eigenvalues and iteration counts


def _solve_case(case):
    if case == "solve":
        op, eng = _local(14)
        return dict(matvec=eng.matvec, n=op.basis.number_states, k=2,
                    tol=1e-10, seed=3)
    if case == "pair":
        update_config(complex_pair="on")
        op, eng = _local(10, syms=[([*range(1, 10), 0], 1)])
        assert eng.pair and not op.effective_is_real
        return dict(matvec=eng.matvec, n=op.basis.number_states, k=1,
                    tol=1e-10, seed=2)
    if case == "thick_restart":
        op, eng = _local(12)
        return dict(matvec=eng.matvec, n=op.basis.number_states, k=1,
                    tol=1e-10, seed=4, max_basis_size=24, check_every=8,
                    max_iters=400)
    op, eng = _distributed(12)
    return dict(matvec=eng.matvec, v0=eng.random_hashed(seed=11), k=1,
                tol=1e-10)


@pytest.mark.parametrize(
    "case", ["solve", "pair", "thick_restart", "four_devices"])
def test_a_solve_repeats_the_parents(clean_obs, monkeypatch, case):
    """The same solve over the parent's eager buffer and over the program's:
    every eigenvalue and residual bit for bit, the same iterations and
    restarts."""
    try:
        kw = _solve_case(case)
        matvec = kw.pop("matvec")
        new = lanczos(matvec, **kw, compute_eigenvectors=True)
        monkeypatch.setattr(L, "_buffer_programs", _eager_buffer_programs)
        old = lanczos(matvec, **kw, compute_eigenvectors=True)
    finally:
        update_config(complex_pair="auto")
    assert new.converged and new.num_iters == old.num_iters
    assert new.restarts == old.restarts
    assert (case == "thick_restart") == bool(new.restarts)
    assert new.eigenvalues.tobytes() == old.eigenvalues.tobytes()
    assert new.residual_norms.tobytes() == old.residual_norms.tobytes()
    for a, b in zip(new.eigenvectors, old.eigenvectors):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


# ---------------------------------------------------------------------------
# (d) a checkpoint's rows go in through the donated setter


def test_the_setter_donates_the_buffer_and_writes_the_row():
    rows = L._buffer_rows(16)
    make, set_row = L._make_buffer_programs(rows, (50,), np.dtype("float64"),
                                            None)
    lowered = set_row.lower(jnp.zeros((rows, 50)), 3, jnp.ones(50))
    assert re.search(r"tensor<%dx50xf64> \{[^}]*(tf\.aliasing_output|"
                     r"jax\.buffer_donor)" % rows, lowered.as_text())
    want = np.zeros((rows, 50))
    want[0] = np.arange(50.0)
    V = make(jnp.asarray(want[0]))
    for i in (1, 2, 7):
        want[i] = np.arange(50.0) * i
        # a row of another dtype is cast, as ``.at[i].set`` casts it
        old, V = V, set_row(V, i, jnp.asarray(want[i], jnp.float32))
        assert old.is_deleted()
    np.testing.assert_array_equal(np.asarray(V), want)


@pytest.mark.parametrize("engine", ["one_device", "four_devices"])
def test_a_restore_writes_the_checkpoints_rows(clean_obs, monkeypatch,
                                               tmp_path, engine):
    """A solve cut after 24 iterations and resumed: the buffer the first
    block program gets holds the checkpoint's rows and zeros above them,
    laid out as a fresh solve's buffer, and the resumed solve returns what
    the parent's eager restore returns, bit for bit."""
    if engine == "one_device":
        op, eng = _local(12)
        kw = dict(n=op.basis.number_states, seed=5)
        like = jnp.zeros(op.basis.number_states)
    else:
        op, eng = _distributed(12)
        kw = dict(v0=eng.random_hashed(seed=5))
        like = kw["v0"]
    kw.update(k=1, tol=1e-11, check_every=8)
    ck = str(tmp_path / "lz.h5")
    cut = lanczos(eng.matvec, max_iters=24, checkpoint_path=ck,
                  checkpoint_every=1, **kw)
    assert not cut.converged

    seen = {}
    restore = L._restore_ckpt
    make_runner = L._make_block_runner

    def restoring(*a, **k):
        got = restore(*a, **k)
        seen["rows"] = [np.asarray(r) for r in got["V_rows"]]
        return got

    def runner(*a, **k):
        run = make_runner(*a, **k)

        def first(V, *rest):
            if "V" not in seen:
                seen.update(V=np.asarray(V), sharding=V.sharding)
            return run(V, *rest)
        return first

    monkeypatch.setattr(L, "_restore_ckpt", restoring)
    monkeypatch.setattr(L, "_make_block_runner", runner)
    new = lanczos(eng.matvec, max_iters=300, checkpoint_path=ck,
                  checkpoint_every=100, **kw)
    assert new.resumed_from == 24 and new.converged
    assert len(seen["rows"]) == 25
    np.testing.assert_array_equal(seen["V"][:25], np.stack(seen["rows"]))
    assert not seen["V"][25:].any()
    assert seen["sharding"] == L.krylov_buffer(96, like).sharding

    monkeypatch.setattr(L, "_buffer_programs", _eager_buffer_programs)
    old = lanczos(eng.matvec, max_iters=300, checkpoint_path=ck,
                  checkpoint_every=100, **kw)
    assert old.resumed_from == 24 and old.num_iters == new.num_iters
    assert old.eigenvalues.tobytes() == new.eigenvalues.tobytes()


# ---------------------------------------------------------------------------
# the file holds no eager update of an array of the buffer's shape


def test_lanczos_holds_no_eager_update_of_the_buffer():
    """Every ``V.at[...]`` / ``Vf.at[...]`` of the module is gone; the two
    ``.at[...].set`` on ``alph`` / ``bet`` (768 B) stay."""
    import inspect

    source = inspect.getsource(L)
    assert not re.findall(r"\bVf?\.at\[", source)
    assert "jnp.zeros((_buffer_rows(mcap),)" not in source
