"""The work counts against brute force on a 16-site ring, the reference
against a dense construction, and the peaks table."""

import importlib.util
import os

import numpy as np
import pytest

from benchmark import peaks, work
from conftest import ROOT, ring_yaml


def _reference():
    path = os.path.join(ROOT, "benchmark", "references", "ring_heisenberg.py")
    spec = importlib.util.spec_from_file_location("ring_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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
    spec = ref.RingSpec(ring_yaml(tmp_path / "r.yaml", n, symmetric))
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
    spec = ref.RingSpec(ring_yaml(tmp_path / "r.yaml", 16))
    reps = ref.enumerate_representatives(spec)
    assert reps.size == 257
    assert ref.count_offdiagonal(spec, reps, np.arange(257)) == 1774


def test_bethe_energy_matches_the_repo_anchors():
    ref = _reference()
    # .claude/skills/verify/SKILL.md: E0/4 of the N-site ring
    for n, e0_over_4 in ((10, -4.5154463544), (12, -5.3873909174),
                         (16, -7.1422963606)):
        assert ref.bethe_e0(n) / 4 == pytest.approx(e0_over_4, abs=2e-10)


def test_reference_refuses_what_it_does_not_cover(tmp_path):
    ref = _reference()
    path = ring_yaml(tmp_path / "r.yaml", 12)
    text = open(path, encoding="utf-8").read().replace("sector: 0}",
                                                       "sector: 1}", 1)
    open(path, "w", encoding="utf-8").write(text)
    with pytest.raises(NotImplementedError):
        ref.RingSpec(path)


def test_work_counts():
    config = {"number_states": 257, "offdiag_nonzeros": 1774}
    nnz = 1774 + 257
    assert work.apply_bytes(config) == nnz * 12 + 258 * 4 + 2 * 257 * 8
    assert work.iteration_bytes(config) == \
        work.apply_bytes(config) + 4 * 257 * 8
    v5e = peaks.peaks_for("TPU v5 lite")
    assert work.least_seconds(819e9, v5e, 1) == pytest.approx(1.0)
    assert work.least_seconds(819e9, v5e, 4) == pytest.approx(0.25)


def test_committed_config_states_its_sizes():
    import json

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
