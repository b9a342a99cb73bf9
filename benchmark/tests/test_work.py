"""The work counts against brute force on a 16-site ring, the reference
against a dense construction, and the peaks table."""

import json
import os

import numpy as np
import pytest

from benchmark import harness, peaks, work
from conftest import ROOT, load_ring_reference as _reference, ring_yaml


def _dense_reduced(n, symmetric):
    """The symmetry-reduced matrix by brute force, in plain Python integers:
    H on all weight-n/2 states, projected on normalised orbit sums."""
    full = (1 << n) - 1
    states = [s for s in range(1 << n) if bin(s).count("1") == n // 2]
    index = {s: i for i, s in enumerate(states)}
    H = np.zeros((len(states),) * 2)
    for s in states:
        for i in range(n):
            j = (i + 1) % n
            if (s >> i) & 1 == (s >> j) & 1:
                H[index[s], index[s]] += 1.0
            else:
                H[index[s], index[s]] -= 1.0
                H[index[s ^ (1 << i) ^ (1 << j)], index[s]] += 2.0

    def orbit(s):
        if not symmetric:
            return {s}
        out = set()
        for v in (s, s ^ full):
            for w in (v, int(format(v, f"0{n}b")[::-1], 2)):
                for k in range(n):
                    out.add(((w << k) | (w >> (n - k))) & full)
        return out

    reps = sorted({min(orbit(s)) for s in states})
    B = np.zeros((len(states), len(reps)))
    for c, r in enumerate(reps):
        members = orbit(r)
        for s in members:
            B[index[s], c] = 1.0 / np.sqrt(len(members))
    return np.array(reps, np.uint64), B.T @ H @ B


@pytest.mark.parametrize("n, symmetric", [(16, True), (12, True), (10, False)])
def test_reference_against_brute_force(tmp_path, n, symmetric):
    ref = _reference()
    spec = ref.Spec(ring_yaml(tmp_path / "r.yaml", n, symmetric))
    reps = ref.enumerate_representatives(spec)
    want_reps, dense = _dense_reduced(n, symmetric)
    assert np.array_equal(reps, want_reps)
    rows = np.arange(reps.size)
    x = np.random.default_rng(n).standard_normal(reps.size)
    assert np.allclose(ref.apply_rows(spec, reps, x, rows), dense @ x,
                       rtol=0, atol=1e-12)
    off = dense - np.diag(np.diag(dense))
    assert ref.count_offdiagonal(spec, reps, rows) == \
        int(np.count_nonzero(np.abs(off) > 1e-12))
    # the float32 control is a different answer
    gap = np.abs(ref.apply_rows(spec, reps, x, rows, np.float32) - dense @ x)
    assert gap.max() > 1e-9


def test_toy_offdiag_count_matches_the_test_suite_fixture(tmp_path):
    ref = _reference()
    spec = ref.Spec(ring_yaml(tmp_path / "r.yaml", 16))
    assert ref.Spec is ref.RingSpec and not spec.complex
    reps = ref.enumerate_representatives(spec)
    assert reps.size == 257
    assert ref.count_offdiagonal(spec, reps, np.arange(257)) == 1774


def test_bethe_energy_matches_the_repo_anchors():
    ref = _reference()
    # .claude/skills/verify/SKILL.md: E0/4 of the N-site ring
    for n, e0_over_4 in ((10, -4.5154463544), (12, -5.3873909174),
                         (16, -7.1422963606)):
        assert ref.bethe_e0(n) / 4 == pytest.approx(e0_over_4, abs=2e-10)


def test_ground_energy_is_asked_for_by_specification(tmp_path):
    """``check.py`` hands the reference its specification: the Bethe value
    where the fully symmetric sector holds the ground state (a multiple of
    four sites), and a refusal where it does not."""
    ref = _reference()
    for n in (12, 16):
        spec = ref.Spec(ring_yaml(tmp_path / f"r{n}.yaml", n))
        assert ref.ground_energy(spec) == ref.bethe_e0(n)
    plain = ref.Spec(ring_yaml(tmp_path / "p.yaml", 10, symmetric=False))
    assert ref.ground_energy(plain) == ref.bethe_e0(10)
    with pytest.raises(NotImplementedError, match="momentum pi"):
        ref.ground_energy(ref.Spec(ring_yaml(tmp_path / "r10.yaml", 10)))


def test_reference_refuses_what_it_does_not_cover(tmp_path):
    ref = _reference()
    path = ring_yaml(tmp_path / "r.yaml", 12)
    text = open(path, encoding="utf-8").read().replace("sector: 0}",
                                                       "sector: 1}", 1)
    open(path, "w", encoding="utf-8").write(text)
    # k = 1 beside the reflection, which maps it to -k
    with pytest.raises(NotImplementedError, match="maps k to -k"):
        ref.Spec(path)
    open(path, "w", encoding="utf-8").write(
        text.replace("sector: 1}", "sector: 6}", 1))
    with pytest.raises(NotImplementedError, match="sector 6 of 12"):
        ref.Spec(path)          # k = n / 2: a real character this file lacks
    open(path, "w", encoding="utf-8").write(
        text.replace("sector: 1}", "sector: 0}", 1)
        .replace("sector: 0}\n", "sector: 1}\n").replace(
            "sector: 1}\n", "sector: 0}\n", 1))
    with pytest.raises(NotImplementedError, match="odd reflection"):
        ref.Spec(path)
    open(path, "w", encoding="utf-8").write(
        text.replace("sector: 1}", "sector: 0}", 1)
        .replace("spin_inversion: 1", "spin_inversion: -1"))
    with pytest.raises(NotImplementedError, match="-1 in a real sector"):
        ref.Spec(path)


def test_work_counts():
    config = {"number_states": 257, "offdiag_nonzeros": 1774}
    nnz = 1774 + 257
    assert work.apply_bytes(config) == nnz * 12 + 258 * 4 + 2 * 257 * 8
    assert work.iteration_bytes(config) == \
        work.apply_bytes(config) + 4 * 257 * 8
    # "real" says what saying nothing says; a complex value is 16 bytes,
    # an index what it was
    assert work.apply_bytes(dict(config, sector="real")) == \
        work.apply_bytes(config)
    cplx = dict(config, sector="complex")
    assert work.is_complex(cplx) and not work.is_complex(config)
    assert work.apply_bytes(cplx) == nnz * 20 + 258 * 4 + 2 * 257 * 16
    assert work.iteration_bytes(cplx) == \
        work.apply_bytes(cplx) + 4 * 257 * 16
    with pytest.raises(ValueError, match="sector 'imaginary'"):
        work.apply_bytes(dict(config, sector="imaginary"))
    v5e = peaks.peaks_for("TPU v5 lite")
    assert work.least_seconds(819e9, v5e, 1) == pytest.approx(1.0)
    assert work.least_seconds(819e9, v5e, 4) == pytest.approx(0.25)


#: ``work.apply_bytes`` and ``work.iteration_bytes`` of the four committed
#: configurations as the parent of PR 34 computes them (8 B a value): no
#: roofline numerator of a cell moved when the value's bytes became the
#: configuration's to state
PARENT_BYTES = {
    "chain_32_symm": (1_081_536_100, 1_232_191_108),
    "chain_32_symm_x4": (1_081_536_100, 1_232_191_108),
    "square_5x5": (1_788_903_204, 1_955_312_804),
    "chain_28": (8_272_934_404, 9_556_665_604),
}


def test_the_committed_configurations_keep_the_parents_bytes():
    bench = harness.load_benchmark()
    assert {c["name"] for c in bench["configs"]} == set(PARENT_BYTES)
    for name, (apply_b, iteration_b) in PARENT_BYTES.items():
        config = harness.load_config(bench, name)
        assert "sector" not in config and not work.is_complex(config)
        assert work.apply_bytes(config) == apply_b, name
        assert work.iteration_bytes(config) == iteration_b, name


def test_committed_config_states_its_sizes():
    for name in ("chain_32_symm", "chain_32_symm_x4"):
        with open(os.path.join(ROOT, "benchmark", "configs",
                               name + ".json")) as f:
            config = json.load(f)
        # 1.082 GB an apply: 1.3 ms at the v5e's 819 GB/s
        assert work.apply_bytes(config) == 1_081_536_100
        assert config["reduced"] == [] and config["assumed"] == []


def test_peaks_table_refuses_an_unknown_chip():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
