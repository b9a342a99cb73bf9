"""A configuration may state a complex sector (PR 34), and one that states
nothing runs what it ran before.

(a) The ring reference's complex path against a construction that shares
nothing with it: H as a sum of Kronecker products on the full space, cut to
the sector's weight, and the projector ``P_k = (1/|G|) sum chi(g)^* U_g``
from permutation matrices made a bit at a time.  (b) A toy complex
configuration (a 12-site ring at k = 1, 75 states) through
``harness.run_cell`` in pair form, ``apply`` and ``ground_state``: ``correct``
true, the complex64 control false, only ``[N, 2]`` float64 arrays in the
timed loop.  (c) The toy real configuration draws the ``x`` and the rows,
and the reference gives the answers, that the parent of PR 34 gives: the
numbers pinned here were printed by the parent's tree.  No number read here
is a device metric."""

import ast
import hashlib
import json
import os
import time

import numpy as np
import pytest

from benchmark import check, harness, trace_reduce, traffic, work
from conftest import ROOT, load_ring_reference, momentum_ring_yaml
from test_lattice_heisenberg import kron_hamiltonian

NO_CHECK = dict(chip_check=lambda devices, chips: None)


# ---------------------------------------------------------------------------
# (a) the reference against the dense projected matrix


def dense_sector(n, k, inversion):
    """(representatives, H in the sector's basis, the projector's rank, H
    on the weight's states) by brute force.  The YAML's conventions: the
    permutation ``[1, ..., n-1, 0]`` sends site ``i`` to site ``i + 1``, a
    generator of sector ``k`` has the character ``exp(-2 pi i k / n)``, and
    the basis state of a representative is ``P |r> / ||P |r>||``."""
    full = (1 << n) - 1
    states = [s for s in range(1 << n) if bin(s).count("1") == n // 2]
    index = {s: i for i, s in enumerate(states)}
    rows = np.array(states)
    H = kron_hamiltonian(n, [(i, (i + 1) % n) for i in range(n)])
    H = H[rows][:, rows].toarray()

    def translate(s):               # bit i of s to bit i + 1, one at a time
        return sum(((s >> i) & 1) << ((i + 1) % n) for i in range(n))

    elements = []                   # (image of every state, character)
    for flip in ((False, True) if inversion else (False,)):
        image = [s ^ full if flip else s for s in states]
        for j in range(n):
            chi = np.exp(-2j * np.pi * k * j / n) * (inversion if flip else 1)
            elements.append((list(image), chi))
            image = [translate(s) for s in image]
    P = np.zeros((len(states),) * 2, complex)
    for image, chi in elements:
        for s, t in zip(states, image):
            P[index[t], index[s]] += np.conj(chi) / len(elements)
    assert np.allclose(P @ P, P, atol=1e-13) and np.allclose(P, P.conj().T)
    assert np.allclose(P @ H, H @ P, atol=1e-12)
    reps, columns = [], []
    for s in states:
        if s != min(image[index[s]] for image, _ in elements):
            continue
        norm = np.linalg.norm(P[:, index[s]])
        if norm > 1e-9:
            reps.append(s)
            columns.append(P[:, index[s]] / norm)
    B = np.array(columns).T
    return (np.array(reps, np.uint64), B.conj().T @ H @ B,
            int(round(np.trace(P).real)), H)


SECTORS = [(n, k, inv) for n in (10, 12) for k in (1, 2, 3)
           for inv in (None, 1, -1)]


@pytest.fixture(scope="module")
def ref():
    return load_ring_reference()


@pytest.mark.parametrize("n, k, inversion", SECTORS)
def test_the_complex_path_against_the_dense_projected_matrix(
        tmp_path, ref, n, k, inversion):
    spec = ref.Spec(momentum_ring_yaml(tmp_path / "r.yaml", n, k, inversion))
    assert spec.complex and spec.k == k
    assert spec.group_order == n * (2 if inversion else 1)
    reps = ref.enumerate_representatives(spec)
    want_reps, dense, rank, H = dense_sector(n, k, inversion)
    # a representative whose stabiliser's characters cancel is no state
    assert reps.size == rank
    assert np.array_equal(reps, want_reps)
    rows = np.arange(reps.size)
    rng = np.random.default_rng(n + k)
    x = rng.standard_normal(reps.size) + 1j * rng.standard_normal(reps.size)
    got = ref.apply_rows(spec, reps, x, rows)
    assert got.dtype == np.complex128
    np.testing.assert_allclose(got, dense @ x, rtol=0, atol=1e-13)
    some = rows[::3]
    np.testing.assert_array_equal(ref.apply_rows(spec, reps, x, some),
                                  got[some])
    # the control: complex64 arithmetic is a different answer
    low = ref.apply_rows(spec, reps, x, rows, np.complex64)
    assert low.dtype == np.complex64
    assert ref.apply_rows(spec, reps, x, rows, np.float32).dtype == \
        np.complex64
    assert 1e-8 < np.max(np.abs(low - got)) < 1e-3
    # the spectrum's lowest value: the reference's H column by column
    M = np.array([ref.apply_rows(spec, reps, e, rows)
                  for e in np.eye(reps.size)]).T
    np.testing.assert_allclose(M, M.conj().T, rtol=0, atol=1e-13)
    lowest = np.linalg.eigvalsh(M)[0]
    assert lowest == pytest.approx(np.linalg.eigvalsh(dense)[0], abs=1e-12)
    assert np.min(np.abs(np.linalg.eigvalsh(H) - lowest)) < 1e-10
    off = dense - np.diag(np.diag(dense))
    assert ref.count_offdiagonal(spec, reps, rows) == \
        int(np.count_nonzero(np.abs(off) > 1e-12))
    with pytest.raises(NotImplementedError, match="brings its stored"):
        ref.ground_energy(spec)


def test_orbits_whose_characters_cancel_are_left_out(tmp_path, ref):
    """12 sites, 924 states of weight 6: 75 orbits of period 12, three of
    period 6, one of period 4 and one of period 2.  At k = 1 only the full
    orbits stay; at k = 2 the period-6 orbits stay too (2 x 6 / 12 is whole)
    and periods 4 and 2 cancel."""
    sizes = {}
    for k in (1, 2, 3, 4):
        spec = ref.Spec(momentum_ring_yaml(tmp_path / f"k{k}.yaml", 12, k))
        sizes[k] = ref.enumerate_representatives(spec).size
    assert sizes == {1: 75, 2: 78, 3: 76, 4: 78}
    trivial = ref.Spec(momentum_ring_yaml(tmp_path / "k0.yaml", 12, 0))
    assert not trivial.complex
    assert ref.enumerate_representatives(trivial).size == 80
    rep, stab, phase = ref.orbit_minimum(
        np.array([0b010101010101, 0b101010101010, 0b000000111111], np.uint32),
        ref.Spec(momentum_ring_yaml(tmp_path / "k1.yaml", 12, 1)))
    assert rep.tolist() == [0b010101010101, 0b010101010101, 0b000000111111]
    assert stab.tolist() == [0, 0, 1] and phase[2] == 1.0


def test_the_ring_reference_imports_nothing_of_the_program():
    path = os.path.join(ROOT, "benchmark", "references", "ring_heisenberg.py")
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0
            imported.add(node.module.split(".")[0])
    assert imported == {"numpy", "yaml"}


# ---------------------------------------------------------------------------
# (b) the toy complex configuration through the harness


@pytest.fixture
def complex_bench(tmp_path, ref):
    """``BENCHMARK.json`` with ``chain_32_symm`` cut to the 12-site ring at
    k = 1 and stating a complex sector; its ground energy is this test's
    own (the dense projected matrix's lowest eigenvalue), as a configuration
    with a ``ground_state`` cell has to bring it."""
    bench = harness.load_benchmark()
    model = momentum_ring_yaml(tmp_path / "ring_12_k1.yaml", 12, 1)
    spec = ref.Spec(model)
    reps = ref.enumerate_representatives(spec)
    _, dense, _, _ = dense_sector(12, 1, None)
    entry = harness.find(bench["configs"], "chain_32_symm", "configuration")
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    config.update(
        model=model, sector="complex", number_spins=12, hamming_weight=6,
        spin_inversion=None, group_order=12, bonds=12,
        number_states=int(reps.size),
        offdiag_nonzeros=ref.count_offdiagonal(spec, reps,
                                               np.arange(reps.size)),
        ground_energy=float(np.linalg.eigvalsh(dense)[0]))
    assert (config["number_states"], config["offdiag_nonzeros"]) == (75, 464)
    path = tmp_path / "ring_12_k1.json"
    path.write_text(json.dumps(config))
    entry["file"] = str(path)
    return bench


@pytest.fixture
def complex_pair():
    """``complex_pair(setting)`` sets the program's option for this test."""
    from distributed_matvec_tpu.utils.config import get_config, update_config

    was = get_config().complex_pair
    yield lambda setting: update_config(complex_pair=setting)
    update_config(complex_pair=was)


@pytest.fixture
def pair_system(toy_system, complex_pair):
    """The toy system in pair form: on the CPU a complex sector would
    otherwise run complex128 (``complex_pair="auto"`` takes pair form on a
    TPU only)."""
    complex_pair("on")
    return toy_system


def _run(bench, system, workload, seed=2_147_483_659, trace=False, **more):
    return harness.run_cell(bench, workload, seed, 0.2, trace,
                            time.perf_counter(), system_factory=system,
                            **NO_CHECK, **more)


def _over(table):
    return {k for k, row in table.items() if not row["value"] <= row["limit"]}


def test_a_complex_apply_runs_in_pair_form(complex_bench, pair_system):
    seen = []

    class Watched(pair_system):
        """Records what crosses into ``eng.matvec``."""

        def build_engine(self):
            super().build_engine()
            inner = self.engine.matvec

            def matvec(x, *args, **kwargs):
                seen.append((type(x).__module__.split(".")[0], x.shape,
                             str(x.dtype)))
                return inner(x, *args, **kwargs)
            self.engine.matvec = matvec

    res = _run(complex_bench, Watched, "chain_32_symm.apply")
    assert res["correct"] is True and res["failed"] == 0, res["checks"]
    assert res["checks"]["apply_err_over_tol"]["value"] < 0.5
    # warm-up and window alike: device arrays of (re, im) pairs, never a
    # complex array, never a host array converted at the call
    assert len(seen) == 2 + res["window"]["applies"]
    assert set(seen) == {("jaxlib", (75, 2), "float64")} or \
        set(seen) == {("jax", (75, 2), "float64")}
    assert res["window"]["engine"]["pair"] is True
    assert res["window"]["engine"]["n_states"] == 75
    assert res["window"]["window_compiles"]["compiled"] == 0
    json.dumps(res)


def test_a_complex_ground_state_runs_in_pair_form(complex_bench,
                                                  pair_system):
    res = _run(complex_bench, pair_system, "chain_32_symm.ground_state")
    assert res["correct"] is True, res["checks"]
    assert res["window"]["engine"]["pair"] is True
    assert res["checks"]["e0_rel_err"]["value"] < 1e-12
    assert res["checks"]["norm_err"]["value"] < 1e-12
    assert res["window"]["solves"] >= 1
    json.dumps(res)


def test_a_complex_solve_on_four_virtual_chips(tmp_path, ref, pair_system):
    """``chain_32_symm_x4.ground_state`` cut to the 16-site ring at k = 1
    (800 states): ``DistributedEngine`` in pair form, the vectors through
    ``to_hashed`` / ``from_hashed`` in whatever rank they take there.  The
    ground energy is the reference's own here (ARPACK on its ``apply_rows``;
    the reference itself is held to the dense projector at 10 and 12
    sites)."""
    from scipy.sparse.linalg import LinearOperator, eigsh

    bench = harness.load_benchmark()
    model = momentum_ring_yaml(tmp_path / "ring_16_k1.yaml", 16, 1)
    spec = ref.Spec(model)
    reps = ref.enumerate_representatives(spec)
    rows = np.arange(reps.size)
    H = LinearOperator(
        (reps.size,) * 2, dtype=complex,
        matvec=lambda v: ref.apply_rows(spec, reps, v.ravel(), rows))
    lowest = eigsh(H, k=1, which="SA", tol=1e-13,
                   return_eigenvectors=False)[0]
    entry = harness.find(bench["configs"], "chain_32_symm_x4", "c")
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    config.update(
        model=model, sector="complex", number_spins=16, hamming_weight=8,
        number_states=int(reps.size),
        offdiag_nonzeros=ref.count_offdiagonal(spec, reps, rows),
        ground_energy=float(lowest))
    assert config["number_states"] == 800
    path = tmp_path / "ring_16_k1.json"
    path.write_text(json.dumps(config))
    entry["file"] = str(path)
    res = _run(bench, pair_system, "chain_32_symm_x4.ground_state")
    assert res["correct"] is True, res["checks"]
    assert res["window"]["engine"]["pair"] is True
    assert res["window"]["engine"]["engine"] == "distributed"
    assert res["checks"]["e0_rel_err"]["value"] < 1e-12
    assert res["device"]["count"] == 4


@pytest.mark.parametrize("workload, over", [
    ("chain_32_symm.apply", {"apply_err_over_tol"}),
    ("chain_32_symm.ground_state", {"residual_over_tol", "e0_rel_err"}),
])
def test_the_complex64_control_is_not_correct(complex_bench, pair_system,
                                              workload, over):
    cell = harness.find(complex_bench["workloads"], workload, "workload")
    config = harness.load_config(complex_bench, cell["config"])
    system = pair_system(config)
    system.start()
    n = system.enumerate()
    system.build_engine()
    mix = traffic.make(cell["traffic"], 4_000_000_007)
    mix.warm_up(system, n)
    mix.window(system, 0.0, harness.annotator(False))
    answers = mix.collect(system)
    first = answers[0] if workload.endswith("apply") \
        else answers[0]["vector"]
    assert first.dtype == np.complex128 and first.shape == (75,)
    ref = mix.reference(config)
    sound, ok = check.judge(mix.compare(ref, answers), mix.limits())
    assert ok, sound
    control = mix.control(ref, answers)
    low = control[0] if workload.endswith("apply") else control[0]["vector"]
    assert low.dtype == np.complex128       # rounded to complex64 and back
    table, ok = check.judge(mix.compare(ref, control), mix.limits())
    assert not ok
    assert over <= _over(table), table
    if workload.endswith("apply"):      # six orders on either side of 1
        assert sound["apply_err_over_tol"]["value"] < 0.1
        assert table["apply_err_over_tol"]["value"] > 1e3


def test_a_complex_input_is_a_complex_draw_of_the_seed(complex_bench,
                                                       toy_bench):
    """``x = (a + i b) / ||a + i b||``: ``a`` the real sector's draw (stream
    0), ``b`` from a stream of its own."""
    class Host:
        def __init__(self, config):
            self.config = config

        def to_device(self, x):
            return x

    seed, n = 4_000_000_007, 75
    mixes = {}
    for name, bench in (("complex", complex_bench), ("real", toy_bench)):
        mixes[name] = traffic.make("apply", seed)
        mixes[name].prepare(
            Host(harness.load_config(bench, "chain_32_symm")), n)
    z, a = mixes["complex"].x, mixes["real"].x
    assert z.dtype == np.complex128 and a.dtype == np.float64
    assert np.linalg.norm(z) == pytest.approx(1.0, abs=1e-15)
    np.testing.assert_allclose(z.real / np.linalg.norm(z.real), a,
                               rtol=0, atol=1e-15)
    assert abs(np.vdot(z.real, z.imag)) < 0.5 * np.linalg.norm(z.real) \
        * np.linalg.norm(z.imag)                # not the same stream twice


def test_the_rooflines_numerator_is_16_bytes_a_value(complex_bench,
                                                     pair_system,
                                                     recorded_trace):
    """``apply_roofline`` of a traced complex rehearsal, by hand: the CPU's
    trace has no device plane, so the reduction is handed a recorded one and
    only the numerator is this run's."""
    recorded = recorded_trace("chain32_apply")

    class Described(pair_system):
        def start(self):
            class Device:
                platform, device_kind = "cpu", "TPU v5 lite"
            return [Device() for _ in super().start()]

    res = _run(complex_bench, Described, "chain_32_symm.apply", trace=True,
               reduce_trace=lambda directory: trace_reduce.reduce_file(
                   recorded))
    assert res["correct"] is True
    config = harness.load_config(complex_bench, "chain_32_symm")
    n, nnz = 75, 464 + 75
    nbytes = nnz * (16 + 4) + (n + 1) * 4 + 2 * n * 16
    assert work.apply_bytes(config) == nbytes == 13_484
    assert work.iteration_bytes(config) == nbytes + 4 * n * 16
    seconds, runs = trace_reduce.reduce_file(recorded).fullest.module_runs(
        r"jit_apply_fn")
    assert res["metrics"]["apply_roofline"]["value"] == pytest.approx(
        100.0 * (nbytes / 819e9) / (seconds / runs))


@pytest.mark.parametrize("stated, pair_form, message", [
    ("complex", False, "states a complex sector"),
    (None, True, "states a real sector"),
])
def test_a_configuration_that_disagrees_with_its_engine_raises(
        complex_bench, toy_system, complex_pair, stated, pair_form, message):
    """Before the window: a complex configuration on an engine that did not
    come up in pair form, or the reverse, is a fault of the files."""
    config = harness.load_config(complex_bench, "chain_32_symm")
    if stated is None:
        del config["sector"]
    complex_pair("on" if pair_form else "off")
    system = toy_system(config)
    system.start()
    system.enumerate()
    with pytest.raises(RuntimeError, match=message):
        system.build_engine()


# ---------------------------------------------------------------------------
# (c) a real configuration runs what it ran on the parent


def _sha(a):
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()[:16]


def test_a_real_configuration_draws_and_is_compared_as_on_the_parent(
        toy_bench, toy_system):
    """The 16-site toy of ``chain_32_symm.apply`` at one seed: the input,
    the rows compared, the reference's answers on them, the control's, the
    limits and the work's bytes, as the parent's tree printed them (PR 34:
    ``/root/scratch/pins.py`` run in an export of commit f235101)."""
    seed = 4_000_000_007
    config = harness.load_config(toy_bench, "chain_32_symm")
    assert "sector" not in config
    system = toy_system(config)
    system.start()
    n = system.enumerate()
    system.build_engine()
    mix = traffic.make("apply", seed)
    mix.warm_up(system, n)
    assert mix.x.dtype == np.float64 and _sha(mix.x) == "3a50a17379b9ab93"
    assert mix.xd.shape == (257,) and str(mix.xd.dtype) == "float64"
    mix.window(system, 0.0, harness.annotator(False))
    answers = mix.collect(system)
    assert all(y.dtype == np.float64 and y.shape == (257,) for y in answers)
    ref = mix.reference(config)
    assert _sha(ref.rows) == "e3e135cb9bc57b0f"
    want = ref.apply_rows(mix.x)
    assert want.dtype == np.float64 and _sha(want) == "ec555423753750f4"
    assert want[:3].tolist() == [-0.44284219928456175, 0.09219098052114671,
                                 -0.6114393672270256]
    control = check.control_apply(ref, mix.x)
    assert control.dtype == np.float64 and _sha(control) == "25d46f9608ac54da"
    assert ref.e0() == -28.569185442467123
    assert mix.limits() == {"apply_err_over_tol": 1.0, "basis_size_diff": 0}
    assert work.apply_bytes(config) == 29_516
    assert work.iteration_bytes(config) == 37_740
    table, ok = check.judge(mix.compare(ref, answers), mix.limits())
    assert ok and list(table) == ["basis_size_diff", "apply_err_over_tol"]


def test_a_real_solve_returns_a_float64_vector(toy_bench, toy_system):
    res = _run(toy_bench, toy_system, "chain_32_symm.ground_state")
    assert res["correct"] is True
    assert res["window"]["engine"]["pair"] is False
    assert list(res["checks"]) == [
        "basis_size_diff", "unconverged_solves", "claimed_residual_over_tol",
        "residual_over_tol", "e0_rel_err", "norm_err"]
