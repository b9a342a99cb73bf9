"""The ops tooling must not bit-rot: scale_bench end-to-end on a small
config (CPU), including the representative checkpoint and the engine
structure cache it wires up."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_X64="true")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "scale_bench.py"),
         "--config", "heisenberg_chain_16.yaml",
         "--out", str(tmp_path / "c16.h5"), "--solver-iters", "4", *args],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    return [json.loads(line) for line in r.stdout.splitlines()
            if line.startswith("{")]


def test_sharded_enum_scale_ranks_cli(tmp_path):
    """sharded_enum_scale --ranks: the multi-process enumeration CLI path
    end-to-end (2 spawned ranks, finalize, census) on a small config; a
    rerun restores every part."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_X64="true")
    out = str(tmp_path / "s16.h5")
    cmd = [sys.executable,
           os.path.join(REPO, "tools", "sharded_enum_scale.py"),
           "--config", "heisenberg_chain_16", "--out", out,
           "--shards", "4", "--ranks", "2", "--threads-per-rank", "1"]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=420,
                       env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "CENSUS_OK" in r.stdout
    assert os.path.exists(out) and os.path.exists(out + ".part1")
    r2 = subprocess.run(cmd, capture_output=True, text=True, timeout=420,
                        env=env, cwd=REPO)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "restored" in r2.stdout and "CENSUS_OK" in r2.stdout


def test_example_sharded_pipeline(tmp_path):
    """The shard-native pipeline example must keep running end to end
    (2-rank enumeration → census → compact from_shards → solve →
    per-shard eigenvector save); E0 is pinned to the chain_16 anchor."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_X64="true",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "examples", "example_sharded_pipeline.py"),
         "--num-spins", "16", "--ranks", "2",
         "--workdir", str(tmp_path)],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO)
    assert r.returncode == 0, (r.stdout[-800:], r.stderr[-1500:])
    assert "census OK" in r.stdout
    assert "E[0] = -28.5691854" in r.stdout       # 4 × (−7.1422963606)
    assert "saved per shard" in r.stdout


def test_scale_bench_end_to_end(tmp_path):
    phases = _run(["--mode", "compact"], tmp_path)
    by = {p["phase"]: p for p in phases}
    assert by["enumerate"]["n_states"] == 12870
    assert not by["enumerate"]["restored"]
    assert by["engine_build"]["ell_gb"] >= 0
    assert by["matvec"]["ms_per_apply"] > 0
    assert by["lanczos"]["iters"] == 4
    assert not by["engine_build"]["structure_restored"]
    # second run restores the representatives AND the engine structure
    phases2 = _run(["--mode", "compact"], tmp_path)
    by2 = {p["phase"]: p for p in phases2}
    assert by2["enumerate"]["restored"]
    assert by2["engine_build"]["structure_restored"]
    assert os.path.exists(str(tmp_path / "c16.h5") + ".structure.h5")


def test_f64_probe_sections_on_the_cpu(tmp_path):
    """The probe behind PERF.md's f64 findings runs every section, and on
    the CPU's IEEE f64 both dot forms reach the 16-site anchor."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import f64_probe

    by = f64_probe.run(n=20_000, out_dir=str(tmp_path))
    assert json.load(open(tmp_path / "f64_probe.json")) == by
    assert by["device"]["platform"] == "cpu"
    assert set(by["elementwise"]) >= {"mul", "sqrt", "div", "add", "mul_add"}
    assert by["elementwise"]["add"]["max_err_rel_to_operand"] < 1e-15
    assert by["combine"]["combine_rows_max_abs_err"] < 1e-12
    for form in ("elementwise_vdot", "jnp_vdot_in_program"):
        assert abs(by["in_solver"][form]["E0_minus_exact"]) < 2e-10
    assert by["restart"]["finite"] and by["restart"]["median_s"] > 0
