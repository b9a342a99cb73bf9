"""The chain's complex momentum sector solved on four chips (the benchmark's
``chain_32_k1_x4``: ``chain_32_k1``'s 9,390,656 states hash-partitioned over
a mesh, ``DistributedEngine`` in (re, im) pair form, ``lanczos`` on its
``matvec`` with ``random_hashed(seed=42)`` as ``benchmark/system.py`` calls
it) on four of the eight virtual devices, at rings of 16 sites (translation
character k = 1, no spin flip: 800 states) and of 20 (the configuration's
own text, spin inversion +1: 4,587 states).  The apply is held against the
benchmark's plain reference (``benchmark/references/ring_heisenberg.py``)
on every row, the solve against the lowest eigenvalue of the sector's whole
matrix (``benchmark/references/ring_sector_energy.py``, which imports
nothing of the program and is held here to a dense projected matrix built
a third way), the Krylov buffer against the memory ledger, and the plan's
six counts against counts made from the reference's rows.  Beside them the
configuration's file and the readers of the two new per-layer metrics, on
the gathers' signatures of both four-chip cells.
"""

import ast
import importlib.util
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from distributed_matvec_tpu.enumeration.host import shard_index
from distributed_matvec_tpu.models.yaml_io import load_config_from_yaml
from distributed_matvec_tpu.obs import memory as obs_memory
from distributed_matvec_tpu.ops import kernels as K
from distributed_matvec_tpu.parallel.distributed import DistributedEngine
from distributed_matvec_tpu.parallel.engine import choose_ell_split
from distributed_matvec_tpu.utils.config import get_config, update_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFS = os.path.join(ROOT, "benchmark", "references")
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
YAML_K1 = os.path.join(CONFIGS, "chain_32_k1.yaml")
ATOL, RTOL = 1e-14, 1e-12       # the configuration's apply contract
D = 4
#: (sites, translation sector, spin flip) -> states
RINGS = {(16, 1, None): 800, (20, 1, 1): 4_587, (16, 0, 1): 415}
PAIR = [(16, 1, None), (20, 1, 1)]


def _module(name):
    spec = importlib.util.spec_from_file_location(
        name + "_for_x4_tests", os.path.join(REFS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reference():
    return _module("ring_heisenberg")


@pytest.fixture(scope="module")
def sector_energy():
    return _module("ring_sector_energy")


def ring_yaml(path, n, k, inversion):
    """``benchmark/configs/chain_32_k1.yaml``'s own text at ``n`` sites, in
    the translation sector ``k``, with the spin flip's line dropped where
    ``inversion`` is None: nothing else changed."""
    with open(YAML_K1, encoding="utf-8") as f:
        text = f.read()
    swaps = [("number_spins: 32", f"number_spins: {n}"),
             ("hamming_weight: 16", f"hamming_weight: {n // 2}"),
             ("sector: 1", f"sector: {k}"),
             (str([*range(1, 32), 0]), str([*range(1, n), 0])),
             (str([[i, (i + 1) % 32] for i in range(32)]).replace(" ", "")
              .replace("],[", "], ["),
              str([[i, (i + 1) % n] for i in range(n)]))]
    if inversion is None:
        swaps.append(("  spin_inversion: 1\n", ""))
    for old, new in swaps:
        assert text.count(old) == 1, old
        text = text.replace(old, new)
    with open(path, "w", encoding="utf-8") as f:
        f.write(text)
    return str(path)


@pytest.fixture(scope="module")
def ring(tmp_path_factory, reference):
    """``ring(key)``: (operator with its basis built, the reference's spec,
    its representatives, the YAML's path), once a module."""
    made = {}

    def get(key):
        if key not in made:
            n, k, inversion = key
            path = ring_yaml(tmp_path_factory.mktemp("ring")
                             / f"ring_{n}_{k}.yaml", n, k, inversion)
            cfg = load_config_from_yaml(path, hamiltonian=True)
            cfg.basis.build()
            spec = reference.Spec(path)
            reps = reference.enumerate_representatives(spec)
            np.testing.assert_array_equal(cfg.basis.representatives, reps)
            assert reps.size == RINGS[key]
            made[key] = (cfg.hamiltonian, spec, reps, path)
        return made[key]
    return get


@pytest.fixture
def pair_form():
    """Complex sectors in (re, im)-f64 pair form, as on a TPU."""
    prev = get_config().complex_pair
    update_config(complex_pair="on")
    yield
    update_config(complex_pair=prev)


def _engine(op, devices=D):
    eng = DistributedEngine(op, n_devices=devices)
    assert eng.mode == "ell" and eng.n_devices == devices
    return eng


# ---------------------------------------------------------------------------
# (a) the pair-form apply on the mesh, every row against the reference


@pytest.mark.parametrize("key", PAIR, ids=["16_sites", "20_sites"])
def test_pair_form_distributed_apply_matches_the_reference(
        key, ring, reference, pair_form):
    """One apply of a unit complex vector through ``to_hashed``, the
    exchange of (re, im) pairs and ``from_hashed``: every row at the
    configuration's contract; the reference in complex64 fails it."""
    op, spec, reps, _ = ring(key)
    eng = _engine(op)
    assert eng.pair and not eng.real
    rng = np.random.default_rng(39 + key[0])
    x = rng.standard_normal(reps.size) + 1j * rng.standard_normal(reps.size)
    x /= np.linalg.norm(x)
    xh = eng.to_hashed(x)
    assert xh.shape == (D, eng.shard_size, 2) and xh.dtype == np.float64
    y = K.complex_from_pair(eng.from_hashed(eng.matvec(xh)))
    rows = np.arange(reps.size)
    want = reference.apply_rows(spec, reps, x, rows)
    tol = ATOL + RTOL * np.abs(want)
    assert np.max(np.abs(y - want) / tol) <= 1.0
    low = reference.apply_rows(spec, reps, x, rows, np.complex64)
    assert np.max(np.abs(low - want) / tol) > 1e3


@pytest.mark.parametrize("key, devices, form", [
    (PAIR[0], 4, "gathered"), ((16, 0, 1), 4, "gathered"),
    (PAIR[0], 8, "packed"), ((16, 0, 1), 8, "packed")],
    ids=["pair_gathered", "real_gathered", "pair_packed", "real_packed"])
def test_both_exchange_forms_match_local_engine_and_the_reference(
        key, devices, form, ring, reference, pair_form):
    """The ELL apply in either form of its exchange, as the plan's own
    numbers choose it: on four devices the rings' peers read nearly all of
    each shard and the shards are all-gathered (``pack_values`` 0), on
    eight the exchange packs C values a peer (``pack_values`` 8 C).  Every
    row against ``LocalEngine`` and against the plain reference at the
    configuration's contract, in pair form and in a real sector."""
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    op, spec, reps, _ = ring(key)
    eng = _engine(op, devices)
    assert eng.exchange_form == form
    counts = eng._plan_counts
    C = eng.query_capacity
    assert counts["pack_values"] == (0 if form == "gathered"
                                     else devices * C)
    assert counts["send_values"] == (
        (devices - 1) * eng.shard_size if form == "gathered"
        else devices * C)
    rng = np.random.default_rng(40 + devices)
    x = rng.standard_normal(reps.size)
    if eng.pair:
        x = x + 1j * rng.standard_normal(reps.size)
    x /= np.linalg.norm(x)
    y = eng.from_hashed(eng.matvec(eng.to_hashed(x)))
    local = LocalEngine(op)
    y_local = local.matvec(K.pair_from_complex(x) if local.pair else x)
    y_local = np.asarray(y_local)
    if eng.pair:
        y = K.complex_from_pair(y)
        y_local = K.complex_from_pair(y_local)
    want = reference.apply_rows(spec, reps, x, np.arange(reps.size))
    np.testing.assert_allclose(y, y_local, atol=ATOL, rtol=RTOL)
    np.testing.assert_allclose(y, want, atol=ATOL, rtol=RTOL)


# ---------------------------------------------------------------------------
# (b) the J-aware Lanczos on the mesh against the sector's whole matrix


@pytest.mark.parametrize("key, cap", [(PAIR[0], 96), (PAIR[0], 24),
                                      (PAIR[1], 96)],
                         ids=["16_sites", "16_sites_restarts", "20_sites"])
def test_lanczos_on_the_mesh_finds_the_sector_energy(
        key, cap, ring, reference, sector_energy, pair_form):
    """``lanczos(eng.matvec, v0=eng.random_hashed(seed=42))`` as the
    benchmark's system calls it, under the traffic's k, tol and cap (and
    under a cap of 24, where it restarts): the Ritz value within 1e-10 of
    ARPACK's on the reference's matrix, the Ritz vector's residual by the
    reference within 5 x tol x |E0| on every row, its norm 1."""
    from distributed_matvec_tpu.solve import lanczos

    op, spec, reps, path = ring(key)
    eng = _engine(op)
    tol = 1e-10
    res = lanczos(eng.matvec, v0=eng.random_hashed(seed=42), k=1, tol=tol,
                  max_iters=1000, max_basis_size=cap,
                  compute_eigenvectors=True)
    assert res.converged and bool(res.restarts) == (cap == 24)
    e0 = sector_energy.ground_energy(path)
    theta = float(res.eigenvalues[0])
    assert abs(theta - e0) <= 1e-10 * abs(e0)
    v = K.complex_from_pair(eng.from_hashed(res.eigenvectors[0]))
    rows = np.arange(reps.size)
    r = reference.apply_rows(spec, reps, v, rows) - theta * v
    assert np.linalg.norm(r) <= 5 * tol * abs(theta)
    assert abs(np.linalg.norm(v) - 1.0) <= 1e-12


def test_the_pair_buffer_is_sharded_a_quarter_a_device(ring, pair_form,
                                                       monkeypatch):
    """The Krylov buffer of a pair-form solve is born sharded over the
    mesh as the hashed ``[4, M, 2]`` vectors are: by the memory ledger's
    entry of ``lanczos/start``, every device holds a quarter of it (and
    alpha and beta, 768 B each, whole: replicated, as the block programs
    leave them)."""
    import importlib

    L = importlib.import_module("distributed_matvec_tpu.solve.lanczos")
    op, _, _, _ = ring(PAIR[0])
    eng = _engine(op)
    seen = {}
    track_tree = obs_memory.track_tree

    def recording(path, tree, **meta):
        handle = track_tree(path, tree, **meta)
        if path.endswith("krylov_basis"):
            V = tree[0]     # donated to the first block program later
            seen.update(shape=V.shape, nbytes=V.nbytes,
                        shards=[a.data.shape for a in V.addressable_shards],
                        small=sum(a.nbytes for a in tree[1:]),
                        per_device=dict(obs_memory.ledger_entries()[path]
                                        ["per_device"]))
        return handle

    monkeypatch.setattr(L.obs_memory, "track_tree", recording)
    res = L.lanczos(eng.matvec, v0=eng.random_hashed(seed=42), k=1,
                    tol=1e-10)
    assert res.converged
    rows = L._buffer_rows(96)
    assert seen["shape"] == (rows, D, eng.shard_size, 2)
    assert seen["shards"] == [(rows, 1, eng.shard_size, 2)] * D
    held = sorted(seen["per_device"].values())
    assert held == [seen["nbytes"] // D + seen["small"]] * D


# ---------------------------------------------------------------------------
# (c) the plan's six counts against counts made from the reference's rows


def _independent_counts(spec, reps, reference, T, pair, D=D):
    """The seven counts from the reference's own rows: a row's live terms
    are its antiparallel bonds whose target's orbit has a norm; shards by
    the partition's hash; the split of the table by the engine's rule on
    that histogram; the exchange gathered where all-gathering the shards
    moves no more values than packing C a peer, (D - 1) M <= D C."""
    s = reps.astype(np.uint32)
    owner = shard_index(reps, D)
    per_shard = np.bincount(owner, minlength=D)
    M = -(-int(per_shard.max()) // 128) * 128
    live = np.zeros(s.size, np.int64)
    remote = [set() for _ in range(D * D)]
    index = {int(r): i for i, r in enumerate(reps)}
    for i, j in spec.bonds:
        differ = (((s >> np.uint32(i)) ^ (s >> np.uint32(j)))
                  & np.uint32(1)).astype(bool)
        rows = np.flatnonzero(differ)
        rep, stab, _ = reference.orbit_minimum(
            s[rows] ^ np.uint32((1 << i) | (1 << j)), spec)
        keep = stab > 0
        live[rows[keep]] += 1
        for row, target in zip(rows[keep], rep[keep]):
            col = index[int(target)]
            if owner[col] != owner[row]:
                remote[owner[row] * D + owner[col]].add(col)
    C = -(-max(len(q) for q in remote) // 8) * 8
    hist = np.bincount(live, minlength=T + 1)
    hist[0] += D * M - reps.size
    T0, S, Tmax = choose_ell_split(hist, D * M, T, real_rows=reps.size)
    tail_rows = max(int((live[owner == d] > T0).sum()) for d in range(D)) \
        if S else 0
    gathered = (D - 1) * M <= D * C
    return {"shard_rows": M,
            "row_gather_slots": M * T0 + tail_rows * (Tmax - T0),
            "live_entries": max(int(live[owner == d].sum())
                                for d in range(D)),
            "send_values": (D - 1) * M if gathered else D * C,
            "pack_values": 0 if gathered else D * C,
            "row_bytes": 32 if pair else 16,
            "value_bytes": 16 if pair else 8}


@pytest.mark.parametrize("key, devices", [
    (PAIR[0], 4), (PAIR[1], 4), ((16, 0, 1), 4), (PAIR[0], 8),
    ((16, 0, 1), 8)],
    ids=["pair_16", "pair_20", "real_16", "pair_16_8_devices",
         "real_16_8_devices"])
def test_the_plan_counts_equal_independent_counts(key, devices, ring,
                                                  reference, pair_form):
    """``shard_rows``, ``row_gather_slots``, ``live_entries``,
    ``send_values``, ``pack_values``, ``row_bytes`` and ``value_bytes`` on
    the ``engine_init/build_plan`` span and in the ``engine_init`` event,
    in pair form and in a real sector (k = 0) alike; on four devices the
    rings' peers read nearly all of each shard and the exchange is
    gathered (nothing packed, ``send_values`` 3 M), on eight it is packed
    (``send_values`` = ``pack_values`` = 8 C)."""
    from distributed_matvec_tpu import obs

    op, spec, reps, _ = ring(key)
    obs.reset_all()
    eng = _engine(op, devices)
    pair = key[1] != 0
    assert eng.pair == pair
    want = _independent_counts(spec, reps, reference,
                               op.number_off_diag_terms, pair, devices)
    assert (want["pack_values"] == 0) == (devices == 4)
    build = [e for e in obs.events("span")
             if e.get("name") == "engine_init/build_plan"][-1]
    init = obs.events("engine_init")[-1]
    assert {k: build[k] for k in want} == want
    assert {k: init[k] for k in want} == want
    assert want["shard_rows"] == eng.shard_size
    obs.reset_all()


# ---------------------------------------------------------------------------
# (d) the reference's energy against a dense projected matrix


def _dense_sector_lowest(n, k, inversion):
    """The lowest eigenvalue of ``B^H H B`` on the weight's states: H from
    Kronecker products of Pauli matrices, B the normalised projections
    ``P|r> / ||P|r>||`` of the orbits' smallest states, with
    ``P = (1/|G|) sum chi(g)^* g`` and every group element applied a bit
    at a time."""
    import scipy.sparse as sp

    paulis = [np.array([[0, 1], [1, 0]], complex),
              np.array([[0, -1j], [1j, 0]]),
              np.array([[1, 0], [0, -1]], complex)]

    def site(p, i):                 # bit i of the state is site i
        out = sp.identity(1, format="csr")
        for j in reversed(range(n)):
            out = sp.kron(out, p if j == i else sp.identity(2), "csr")
        return out

    H = sum(site(p, i) @ site(p, (i + 1) % n) for p in paulis
            for i in range(n))
    states = np.array([s for s in range(1 << n)
                       if bin(s).count("1") == n // 2])
    H = H[states][:, states]
    where = {int(s): i for i, s in enumerate(states)}

    def turn(s):                    # bit i to bit i + 1
        return sum(((s >> i) & 1) << ((i + 1) % n) for i in range(n))

    elements = []                   # (state -> image, character)
    for flip in ((False, True) if inversion else (False,)):
        image = {int(s): int(s) ^ ((1 << n) - 1) if flip else int(s)
                 for s in states}
        for j in range(n):
            elements.append((dict(image), np.exp(-2j * np.pi * k * j / n)
                             * (inversion if flip else 1)))
            image = {s: turn(t) for s, t in image.items()}
    columns = []
    for s in states:
        s = int(s)
        if s != min(image[s] for image, _ in elements):
            continue
        col = np.zeros(states.size, complex)
        for image, chi in elements:
            col[where[image[s]]] += np.conj(chi) / len(elements)
        norm = np.linalg.norm(col)
        if norm > 1e-9:
            columns.append(col / norm)
    B = np.array(columns).T
    return np.linalg.eigvalsh(B.conj().T @ (H @ B))[0], B.shape[1]


@pytest.mark.parametrize("n, k, inversion", [
    (10, 1, None), (12, 1, 1), (12, 2, -1), (16, 1, 1), (16, 3, None)])
def test_the_sector_energy_is_the_dense_projected_matrix(
        tmp_path, n, k, inversion, reference, sector_energy):
    """``ring_sector_energy`` builds the sector's matrix bond by bond from
    ``ring_heisenberg``'s ``orbit_minimum`` and ``apply_rows``' amplitudes:
    Hermitian, the reference's ``count_offdiagonal`` elements beside the
    diagonal, and its lowest eigenvalue the dense projected matrix's to
    1e-12."""
    lines = [f"basis:\n  number_spins: {n}\n  hamming_weight: {n // 2}\n"]
    if inversion is not None:
        lines.append(f"  spin_inversion: {inversion}\n")
    bonds = [[i, (i + 1) % n] for i in range(n)]
    lines.append(f"  symmetries:\n    - {{permutation: "
                 f"{[*range(1, n), 0]}, sector: {k}}}\n"
                 "hamiltonian:\n  name: Heisenberg\n  terms:\n")
    for axis in "ˣʸᶻ":
        lines.append(f"    - {{expression: \"σ{axis}₀ σ{axis}₁\", "
                     f"sites: {bonds}}}\n")
    path = tmp_path / "ring.yaml"
    path.write_text("".join(lines), encoding="utf-8")
    spec = reference.Spec(str(path))
    reps = reference.enumerate_representatives(spec)
    H = sector_energy.sector_matrix(spec, reps)
    assert abs(H - H.conj().T).max() < 1e-13
    assert H.nnz == reps.size + reference.count_offdiagonal(
        spec, reps, np.arange(reps.size))
    want, size = _dense_sector_lowest(n, k, inversion)
    assert size == reps.size
    got = sector_energy.ground_energy(str(path))
    assert got == pytest.approx(want, abs=1e-12)
    assert sector_energy.lowest_eigenvalue(H) == got


def test_the_sector_energy_imports_nothing_of_the_program():
    with open(os.path.join(REFS, "ring_sector_energy.py"),
              encoding="utf-8") as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0
            imported.add(node.module.split(".")[0])
    assert imported == {"importlib", "os", "sys", "time", "numpy", "scipy"}


# ---------------------------------------------------------------------------
# (e) the configuration and the benchmark's entries


def _load(name):
    with open(os.path.join(CONFIGS, name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def test_the_configuration_is_chain_32_k1_on_four_chips():
    x4, one = _load("chain_32_k1_x4"), _load("chain_32_k1")
    for key in ("model", "reference", "sector", "number_spins",
                "hamming_weight", "spin_inversion", "group_order", "bonds",
                "candidates", "number_states", "offdiag_nonzeros", "dtype",
                "assumed", "reduced"):
        assert x4[key] == one[key], key
    assert (x4["number_states"], x4["offdiag_nonzeros"]) == (9_390_656,
                                                             155_077_440)
    assert x4["engine"] == {"kind": "distributed", "devices": 4,
                            "mode": None}
    assert x4["solver_warm_up_iters"] is None and x4["solver_warm_up_why"]
    assert isinstance(x4["ground_energy"], float)
    assert -54 < x4["ground_energy"] < -53
    assert "ring_sector_energy.py" in x4["ground_energy_from"]
    assert "reduced" in x4 and x4["reduced"] == []
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == x4["name"])
    assert entry["file"] == "benchmark/configs/chain_32_k1_x4.json"
    assert entry["source"] == x4["source"] and entry["reduced"] == []
    cell = next(w for w in bench["workloads"]
                if w["name"] == "chain_32_k1_x4.ground_state")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "chain_32_k1_x4", "ground_state", 4)
    assert len(cell["why"]) <= 200
    for name in ("dist_gather_ns_per_slot", "pack_ns_per_value"):
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == ["chain_32_symm_x4.ground_state",
                                       "chain_32_k1_x4.ground_state"]
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           name + ".py"))


# ---------------------------------------------------------------------------
# (f) the two readers on the gathers of both four-chip cells


def _run(counts, own, applies):
    """A traced run as the readers see it: the build span with its counts,
    one solve's root span, and the fullest device's own times."""
    build = dict(kind="span", name="engine_init/build_plan", span_id="b",
                 dur_ms=1.0, **counts)
    solve = dict(kind="span", name="lanczos", cat="solve", span_id="s",
                 dur_ms=1.0, steps_counted=applies - 2,
                 steps_run=applies - 1, probe_applies=1, programs_built=2)
    return SimpleNamespace(
        config={"engine": {"kind": "distributed"}},
        events={"build": [build], "window": [solve], "lost": False},
        window={"solves": 1},
        trace=SimpleNamespace(fullest=SimpleNamespace(
            own={sig: [s, 0] for sig, s in own.items()})))


def _reader(name):
    import sys

    sys.path.insert(0, ROOT)
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(ROOT, "benchmark", "metrics",
                                       name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_readers_classify_both_cells_gathers():
    """``chain_32_symm_x4.ground_state``'s gathers as its ledger line of PR
    38 names them (82 applies a window: 1,640 main and 984 tail row gathers,
    164 packs) and ``chain_32_k1_x4``'s as the compiler names them for a
    described v5e (``tests/test_tpu_compile.py``): the row gathers' own time
    over ``row_gather_slots``, the packs' over ``send_values``; a reader
    finds nothing in a run whose span carries no counts (the parent)."""
    rows, pack = _reader("dist_gather_ns_per_slot"), \
        _reader("pack_ns_per_value")
    real = _run(dict(shard_rows=1_177_216, send_values=4_651_488,
                     row_gather_slots=1_177_216 * 20 + 62_545 * 12,
                     value_bytes=8, row_bytes=16),
                {"fusion f32[1177216,3](f32[5828704,3],s32[1177600])":
                 9.971384521,
                 "fusion f32[62545,3](f32[5828704,3],s32[63488])":
                 0.319189172,
                 "fusion f32[4651488](f32[1177216],s32[4652032])":
                 5.123104995,
                 "fusion f32[1177216](f32[1177216,3])": 0.114320387}, 82)
    assert rows(real) == pytest.approx(
        1e9 * (9.971384521 + 0.319189172) / 82 / 24_294_860)
    assert pack(real) == pytest.approx(1e9 * 5.123104995 / 82 / 4_651_488)
    assert 5.1 < rows(real) < 5.2 and 13.4 < pack(real) < 13.5
    pair = _run(dict(shard_rows=2_348_928, send_values=9_283_200,
                     row_gather_slots=2_348_928 * 20 + 124_160 * 8,
                     value_bytes=16, row_bytes=32),
                {"fusion f32[2348928,6](f32[11632128,6],s32[2349056])": 2.0,
                 "fusion f32[124160,6](f32[11632128,6],s32[124928])": 0.1,
                 "fusion f32[9283200,2](f32[2348928,2],s32[9283584])": 1.0},
                10)
    assert rows(pair) == pytest.approx(1e9 * 2.1 / 10 / (
        2_348_928 * 20 + 124_160 * 8))
    assert pack(pair) == pytest.approx(1e9 * 1.0 / 10 / 9_283_200)
    bare = _run({}, {"fusion f32[2348928,6](f32[11632128,6],s32[2349056])":
                     2.0}, 10)
    assert rows(bare) is None and pack(bare) is None
