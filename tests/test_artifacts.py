"""Default-on artifact cache: warm-start engines, basis checkpoints,
fingerprint safety, and the batched multi-RHS apply they feed.

The suite-wide conftest forces ``DMT_ARTIFACT_CACHE=off`` (hermeticity —
engines must not restore structures a previous session left in ~/.cache);
these tests re-enable the layer against a session-scoped tmp root.
"""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from distributed_matvec_tpu.parallel.engine import LocalEngine

from test_operator import build_heisenberg

ATOL, RTOL = 1e-13, 1e-12
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="session")
def artifact_root_dir(tmp_path_factory):
    # session-scoped: JAX's persistent compilation cache dir is process
    # global once set, so it must outlive any single test's tmp_path
    return str(tmp_path_factory.mktemp("artifacts"))


@pytest.fixture
def artifacts_on(artifact_root_dir, monkeypatch):
    monkeypatch.setenv("DMT_ARTIFACT_CACHE", "on")
    monkeypatch.setenv("DMT_ARTIFACT_DIR", artifact_root_dir)
    return artifact_root_dir


def test_artifacts_off_no_restore(tmp_path, monkeypatch):
    """With the layer off (the suite default) engines never restore."""
    from distributed_matvec_tpu.utils.artifacts import (
        artifacts_enabled, default_structure_cache)
    assert not artifacts_enabled()
    assert default_structure_cache("ab" * 32) is None
    op = build_heisenberg(10, 5, None, ())
    e1 = LocalEngine(op, mode="ell")
    e2 = LocalEngine(op, mode="ell")
    assert not e1.structure_restored and not e2.structure_restored


def test_warm_start_round_trip(artifacts_on, rng):
    """Cold build fills the cache; a warm engine over a FRESH basis object
    restores representatives + structure with zero structure-build kernel
    launches, and its matvec matches the cold engine to the golden
    tolerances."""
    op1 = build_heisenberg(12, 6, 1, ())
    e1 = LocalEngine(op1, mode="ell")
    assert not e1.structure_restored          # cold: cache was empty
    n = op1.basis.number_states
    x = rng.random(n) - 0.5
    y1 = np.asarray(e1.matvec(x))

    # fresh operator/basis objects: nothing carried over in memory
    op2 = build_heisenberg(12, 6, 1, ())
    assert not op2.basis.is_built
    e2 = LocalEngine(op2, mode="ell")
    assert e2.basis_restored                  # representatives from basis/
    assert e2.structure_restored              # tables from structure/
    # zero structure-build kernel launches: the timer scope never opened
    assert "build_structure" not in e2.timer.root.children
    np.testing.assert_allclose(np.asarray(e2.matvec(x)), y1,
                               atol=ATOL, rtol=RTOL)


def test_fingerprint_mismatch_rebuilds(artifacts_on, rng):
    """A different operator (2H) or different padding (batch_size) must
    MISS the cache and rebuild cleanly — restored tables keyed by content,
    never by name."""
    op = build_heisenberg(10, 5, None, ())
    e1 = LocalEngine(op, mode="ell", batch_size=64)
    assert not e1.structure_restored
    n = op.basis.number_states
    x = rng.random(n) - 0.5

    # same basis, same batch: hit
    e2 = LocalEngine(build_heisenberg(10, 5, None, ()), mode="ell",
                     batch_size=64)
    assert e2.structure_restored

    # scaled operator: different term tables -> miss, and 2H·x == 2·(H·x)
    op2 = 2.0 * build_heisenberg(10, 5, None, ())
    e3 = LocalEngine(op2, mode="ell", batch_size=64)
    assert not e3.structure_restored
    np.testing.assert_allclose(np.asarray(e3.matvec(x)),
                               2.0 * np.asarray(e1.matvec(x)),
                               atol=1e-12)

    # different padding geometry: different fingerprint -> miss
    e4 = LocalEngine(build_heisenberg(10, 5, None, ()), mode="ell",
                     batch_size=32)
    assert not e4.structure_restored
    np.testing.assert_allclose(np.asarray(e4.matvec(x)),
                               np.asarray(e1.matvec(x)), atol=ATOL,
                               rtol=RTOL)


def test_size_cap_skips_default_save(artifacts_on, monkeypatch, rng):
    """A structure beyond artifact_max_gb is rebuilt per process instead of
    filling the cache disk (default-path saves only)."""
    from distributed_matvec_tpu.utils.config import get_config
    monkeypatch.setattr(get_config(), "artifact_max_gb", 1e-9)
    op = build_heisenberg(8, 4, None, ())
    e1 = LocalEngine(op, mode="ell")
    assert not e1.structure_restored
    e2 = LocalEngine(build_heisenberg(8, 4, None, ()), mode="ell")
    assert not e2.structure_restored          # save was size-capped away


def test_basis_artifact_round_trip(artifacts_on):
    from distributed_matvec_tpu.utils.artifacts import make_or_restore_basis
    op1 = build_heisenberg(14, 7, None, ())
    assert make_or_restore_basis(op1.basis) is False     # fresh build
    op2 = build_heisenberg(14, 7, None, ())
    assert make_or_restore_basis(op2.basis) is True      # checkpoint hit
    np.testing.assert_array_equal(op1.basis.representatives,
                                  op2.basis.representatives)
    np.testing.assert_array_equal(op1.basis.norms, op2.basis.norms)
    # a different sector must not hit the same checkpoint
    op3 = build_heisenberg(14, 6, None, ())
    assert make_or_restore_basis(op3.basis) is False


def test_compact_mode_warm_start(artifacts_on, rng):
    op1 = build_heisenberg(10, 5, None, ())
    e1 = LocalEngine(op1, mode="compact")
    assert not e1.structure_restored
    x = rng.random(op1.basis.number_states) - 0.5
    y1 = np.asarray(e1.matvec(x))
    e2 = LocalEngine(build_heisenberg(10, 5, None, ()), mode="compact")
    assert e2.structure_restored
    assert "build_structure" not in e2.timer.root.children
    np.testing.assert_allclose(np.asarray(e2.matvec(x)), y1,
                               atol=ATOL, rtol=RTOL)


def test_distributed_warm_start(artifacts_on, rng):
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine
    op1 = build_heisenberg(10, 5, None, ())
    e1 = DistributedEngine(op1, n_devices=2, mode="ell", batch_size=64)
    assert not e1.structure_restored
    x = rng.random(op1.basis.number_states) - 0.5
    y1 = np.asarray(e1.matvec_global(x))
    e2 = DistributedEngine(build_heisenberg(10, 5, None, ()), n_devices=2,
                           mode="ell", batch_size=64)
    assert e2.basis_restored and e2.structure_restored
    assert "build_plan" not in e2.timer.root.children
    np.testing.assert_allclose(np.asarray(e2.matvec_global(x)), y1,
                               atol=ATOL, rtol=RTOL)


def test_batched_multi_rhs_matches_single(rng):
    """[N, 4] native apply == 4 single applies at the golden tolerances
    (the acceptance contract of the batched gather-once path)."""
    op = build_heisenberg(12, 6, None, ())
    eng = LocalEngine(op, mode="ell")
    n = op.basis.number_states
    X = rng.random((n, 4)) - 0.5
    Y = np.asarray(eng.matvec(X))
    assert Y.shape == (n, 4)
    for j in range(4):
        np.testing.assert_allclose(Y[:, j], np.asarray(eng.matvec(X[:, j])),
                                   atol=ATOL, rtol=RTOL)


def test_warm_cache_tool(artifact_root_dir, tmp_path):
    """tools/warm_cache.py fills the cache; a second run restores
    everything (the `make warm-cache` contract)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_X64="true")
    env.pop("DMT_ARTIFACT_CACHE", None)
    root = str(tmp_path / "warmroot")

    def run():
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "warm_cache.py"),
             "--configs", "smoke", "--artifact-dir", root],
            capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
        assert r.returncode == 0, (r.stdout[-800:], r.stderr[-1500:])
        import json
        lines = [json.loads(li) for li in r.stdout.splitlines() if li]
        assert lines[0]["artifact_root"] == root
        return {d["config"]: d for d in lines[1:]}

    cold = run()
    assert not cold["chain_16"]["basis_restored"]
    assert not cold["chain_16"]["structure_restored"]
    warm = run()
    assert warm["chain_16"]["basis_restored"]
    assert warm["chain_16"]["structure_restored"]
    assert os.path.isdir(os.path.join(root, "structure"))
    assert os.path.isdir(os.path.join(root, "basis"))


# -- where the XLA compile cache lives (utils/cache.py) ----------------------

_CACHE_PROBE = """
import json, os, sys
import apps.diagonalize                      # entry points enable the cache
import jax, jax.numpy as jnp
from distributed_matvec_tpu.utils.cache import enable_compilation_cache
d = enable_compilation_cache()
jax.jit(lambda a: a * 2 + 1)(jnp.arange(8.0)).block_until_ready()
print(json.dumps({"returned": d,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def _cache_probe(tmp_path, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1", **env_extra)
    if "JAX_COMPILATION_CACHE_DIR" not in env_extra:
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=str(tmp_path))
    assert r.returncode == 0, r.stderr[-2000:]
    import json
    return json.loads(r.stdout.splitlines()[-1])


def test_compile_cache_env_var_is_left_to_jax(tmp_path, monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set the code sets no directory at all
    (JAX reads the variable itself) and the cache is written there."""
    from distributed_matvec_tpu.utils import cache

    updates = []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "x"))
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append(k))
    assert cache.enable_compilation_cache() == str(tmp_path / "x")
    assert "jax_compilation_cache_dir" not in updates
    monkeypatch.undo()

    placed = str(tmp_path / "placed")
    got = _cache_probe(tmp_path, JAX_COMPILATION_CACHE_DIR=placed)
    assert got == {"returned": placed, "config": placed}
    assert os.listdir(placed)                # the process cached there


def test_compile_cache_default_is_fixed_inside_checkout(tmp_path):
    """Without the variable the directory is one fixed path inside the
    checkout — no ``~``, pid or time in it — and two processes (with
    different HOMEs and working directories) agree on it."""
    from distributed_matvec_tpu.utils.cache import CHECKOUT_CACHE_DIR

    assert CHECKOUT_CACHE_DIR == os.path.join(REPO, ".cache", "xla")
    ro = tmp_path / "ro_home"
    ro.mkdir()
    ro.chmod(0o555)
    a = _cache_probe(tmp_path, HOME=str(ro))
    (tmp_path / "elsewhere").mkdir()
    b = _cache_probe(tmp_path / "elsewhere", HOME=str(tmp_path / "missing"))
    assert a == b == {"returned": CHECKOUT_CACHE_DIR,
                      "config": CHECKOUT_CACHE_DIR}


def test_entry_points_and_engine_work_without_a_usable_home(tmp_path):
    """HOME read-only, then HOME missing: every entry point imports, and a
    default (artifact layer ON) engine builds — cache writes fail soft."""
    code = """
import apps.diagonalize, apps.dynamics, apps.solve_service
import __graft_entry__
from distributed_matvec_tpu.models.basis import SpinBasis
from distributed_matvec_tpu.models.lattices import (chain_edges,
                                                    heisenberg_from_edges)
from distributed_matvec_tpu.parallel.engine import LocalEngine
op = heisenberg_from_edges(SpinBasis(8, 4), chain_edges(8))
eng = LocalEngine(op, mode="ell")
print("built", eng.n_states, eng.structure_restored)
"""
    ro = tmp_path / "ro_home"
    ro.mkdir()
    ro.chmod(0o555)
    base = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    for k in ("DMT_ARTIFACT_CACHE", "DMT_ARTIFACT_DIR", "HOME"):
        base.pop(k, None)
    for env in (dict(base, HOME=str(ro)),
                dict(base, HOME=str(ro / "missing"))):
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=300, env=env,
                           cwd=str(tmp_path))
        assert r.returncode == 0, r.stderr[-2000:]
        assert "built 70 False" in r.stdout
    assert sorted(os.listdir(tmp_path)) == ["ro_home"]


def test_artifact_root_without_any_home_fails_soft(monkeypatch):
    """HOME unset and no passwd entry: ``~`` comes back unexpanded.  The
    root is then an OSError (= cache unavailable), not a ``~`` directory
    created under the working directory."""
    from distributed_matvec_tpu.utils import artifacts

    monkeypatch.setenv("DMT_ARTIFACT_CACHE", "on")
    monkeypatch.delenv("DMT_ARTIFACT_DIR", raising=False)
    monkeypatch.setattr(os.path, "expanduser", lambda p: p)
    with pytest.raises(OSError, match="DMT_ARTIFACT_DIR"):
        artifacts.artifact_root()
    assert artifacts.default_structure_cache("ab" * 32) is None
    b = build_heisenberg(8, 4, None, ()).basis
    assert artifacts.make_or_restore_basis(b) is False and b.is_built
