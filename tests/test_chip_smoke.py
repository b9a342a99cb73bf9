"""``chip_smoke.py`` rehearsed without the chip.

The script's phase functions take the platform, the model file and the
device count as function arguments, so the same code that runs
chain_32_symm on the TPU is driven here at 16 sites on the CPU — one device
and four virtual ones — and the script itself is shown to refuse a machine
with no TPU.
"""

import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

CHAIN_16_SYMM_STATES = 257


@pytest.fixture(scope="module")
def compiles():
    return chip_smoke.CompileCounter()


@pytest.fixture
def symm_yaml(tmp_path):
    return chip_smoke.write_ring_yaml(str(tmp_path / "chain_16_symm.yaml"),
                                      16, symmetric=True)


def test_environment_line_then_refusal(capsys):
    """The environment line is printed and the native enumerator built
    BEFORE the platform is insisted on, so a refusal says why."""
    device = chip_smoke.phase_environment("cpu")
    assert device["platform"] == "cpu" and device["count"] == len(
        jax.devices())
    with pytest.raises(RuntimeError, match="needs a tpu device"):
        chip_smoke.phase_environment("tpu")
    out = capsys.readouterr().out
    assert out.count("[chip_smoke] environment:") == 2
    for word in ("jax=", "libtpu=", "compile_cache=", "cxx=", "native_build=",
                 "-march=x86-64-v3", "native_result="):
        assert word in out, word
    assert "-march=native" not in out


def test_anchor_phase(tmp_path, compiles, capsys):
    seen = chip_smoke.phase_anchor(str(tmp_path), compiles, platform="cpu")
    assert abs(seen["e0"] / 4 - chip_smoke.ANCHOR_E0_OVER_4) < chip_smoke.ANCHOR_TOL
    assert seen["n_states"] == 12870
    assert "[chip_smoke] anchor:" in capsys.readouterr().out


def test_solve_phase_one_device(tmp_path, symm_yaml, compiles, capsys):
    """The full-size phase at 16 sites: native enumeration, tables and the
    eigenvector on the device, the sampled-row apply check, the residual."""
    seen = chip_smoke.phase_solve(
        symm_yaml, str(tmp_path), expect_states=CHAIN_16_SYMM_STATES,
        tol=1e-10, compiles=compiles, platform="cpu")
    assert seen["engine"] == "LocalEngine"
    assert seen["apply_rows"] == CHAIN_16_SYMM_STATES
    assert seen["apply_err"] < 1e-14
    assert abs(seen["e0"] / 4 - chip_smoke.ANCHOR_E0_OVER_4) < chip_smoke.ANCHOR_TOL
    line = [li for li in capsys.readouterr().out.splitlines()
            if li.startswith("[chip_smoke] solve:")][0]
    for word in ("enumerated_by=native", "basis_s=", "structure_build_s=",
                 "compile_s=", "solve_s=", "iterations=", "compilations=",
                 "peak_bytes=", "apply_max_err=", "residual="):
        assert word in line, word


def test_solve_phase_checks_bite(tmp_path, symm_yaml, compiles):
    with pytest.raises(AssertionError, match="number_states"):
        chip_smoke.phase_solve(symm_yaml, str(tmp_path), expect_states=256,
                               tol=1e-10, compiles=compiles, platform="cpu")
    with pytest.raises(AssertionError, match="not on tpu"):
        chip_smoke.phase_solve(
            symm_yaml, str(tmp_path), expect_states=CHAIN_16_SYMM_STATES,
            tol=1e-10, compiles=compiles, platform="tpu")


def test_restored_structure_fails_the_solve(tmp_path, symm_yaml, compiles,
                                           monkeypatch):
    """With the artifact layer on, a second solve restores the tables
    instead of building them — the phase refuses that, and ``main`` switches
    the layer off before anything runs."""
    monkeypatch.setenv("DMT_ARTIFACT_CACHE", "on")
    monkeypatch.setenv("DMT_ARTIFACT_DIR", str(tmp_path / "artifacts"))
    args = dict(expect_states=CHAIN_16_SYMM_STATES, tol=1e-10,
                compiles=compiles, platform="cpu")
    chip_smoke.phase_solve(symm_yaml, str(tmp_path), name="first", **args)
    with pytest.raises(AssertionError, match="restored its structure"):
        chip_smoke.phase_solve(symm_yaml, str(tmp_path), name="second",
                               **args)
    assert chip_smoke.main([]) == 1          # no TPU here
    assert os.environ["DMT_ARTIFACT_CACHE"] == "off"


def test_four_chips_phase_on_virtual_devices(tmp_path, symm_yaml, compiles):
    """``--chips 4`` on four virtual CPU devices: every device holds a
    shard of the tables and of the vector, the hashed apply matches the
    host rows, and E0 matches the one-device solve."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    dist, local = chip_smoke.phase_four_chips(
        symm_yaml, str(tmp_path), expect_states=CHAIN_16_SYMM_STATES,
        tol=1e-10, compiles=compiles, platform="cpu")
    assert dist["engine"] == "DistributedEngine"
    assert local["engine"] == "LocalEngine"
    assert len(dist["table_bytes"]) == 4
    assert len(dist["vector_shard_bytes"]) == 4
    assert min(dist["vector_shard_bytes"]) > 0
    assert abs(dist["e0"] - local["e0"]) <= 1e-10 * abs(local["e0"])


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one_chip", "four_chips"])
def test_script_without_a_tpu_exits_nonzero(tmp_path, argv):
    """No TPU: non-zero exit, the reason on stderr, no result line, and
    nothing solved on the CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py"),
                        *argv], capture_output=True, text=True, timeout=300,
                       env=env, cwd=str(tmp_path))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "[chip_smoke] environment:" in r.stdout
    assert "[chip_smoke] anchor" not in r.stdout
    assert "needs a tpu device" in r.stderr


def test_script_alone_fails_without_result(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the repo
    the script fails and prints no result."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"],
                       capture_output=True, text=True, timeout=300,
                       env=dict(env, JAX_PLATFORMS="cpu"), cwd=str(tmp_path))
    assert r.returncode != 0 and '"ok"' not in r.stdout
    assert "distributed_matvec_tpu" in r.stderr
