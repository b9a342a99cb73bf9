"""The ops tooling and the documents must not bit-rot: the CLI tools run
end to end on a small config (CPU), every check tool loads and names only
programs that exist, and the documents a planner reads first cite only
files and ``make`` targets the checkout has."""

import ast
import glob
import importlib.util
import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHECK_TOOLS = sorted(
    os.path.basename(p)[:-3]
    for p in glob.glob(os.path.join(REPO, "tools", "*_check.py")))


@pytest.fixture
def sealed_process():
    """The check tools pin ``XLA_FLAGS`` / ``JAX_PLATFORMS``, drop
    ``DMT_*`` variables and extend ``sys.path`` when they are imported:
    put the process back, or the children later tests spawn inherit it."""
    env, path = dict(os.environ), list(sys.path)
    yield
    os.environ.clear()
    os.environ.update(env)
    sys.path[:] = path


@pytest.mark.parametrize("name", CHECK_TOOLS + ["obs_report"])
def test_check_tools_import_nothing_deleted(name, sealed_process):
    """Every ``tools/*_check.py`` (and ``obs_report``) loads on the CPU
    without running its gate, every module it imports anywhere in its
    source (function bodies included: that is where the gates import
    their helpers) resolves, and every ``*.py`` it names as a string
    (the programs it spawns) exists."""
    path = os.path.join(REPO, "tools", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_tool_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert callable(mod.main)

    sys.path[:0] = [REPO, os.path.join(REPO, "tools")]
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        mods = []
        if isinstance(node, ast.Import):
            mods = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom) and not node.level:
            mods = [node.module]
        elif isinstance(node, ast.Constant) and isinstance(node.value, str) \
                and re.fullmatch(r"[\w/]+\.py", node.value):
            assert any(os.path.exists(os.path.join(REPO, d, node.value))
                       for d in ("", "tools", "apps", "tests",
                                 "distributed_matvec_tpu/obs")), \
                f"{name}.py:{node.lineno} names {node.value}"
        for m in mods:
            assert importlib.util.find_spec(m) is not None, \
                f"{name}.py:{node.lineno} imports {m}"


# -- the documents cite what exists -----------------------------------------

_PATH_PREFIXES = ("tools/", "tests/", "benchmark/", "apps/", "examples/",
                  "distributed_matvec_tpu/")


def _make_targets():
    with open(os.path.join(REPO, "Makefile")) as f:
        return set(re.findall(r"^([a-z][\w-]*):", f.read(), re.M))


def _citations(text):
    """Inline code spans and the lines of fenced blocks, after history is
    taken out: struck text (``~~...~~``) and lines saying ``removed in
    PR``."""
    text = re.sub(r"~~.*?~~", "", text, flags=re.S)
    text = "\n".join(ln for ln in text.splitlines()
                     if "removed in PR" not in ln)
    fenced = re.findall(r"^```.*?^```", text, flags=re.S | re.M)
    for block in fenced:
        text = text.replace(block, "")
    spans = [" ".join(s.split()) for s in re.findall(r"`([^`]+)`", text)]
    return spans + [ln for b in fenced for ln in b.splitlines()[1:-1]]


def _missing(citation, targets):
    """What ``citation`` names that the checkout does not have."""
    bad = []
    tokens = citation.split()
    for i, tok in enumerate(tokens):
        if tok == "make" and i + 1 < len(tokens):
            m = re.fullmatch(r"([a-z][\w-]*)[.,;:)]*", tokens[i + 1])
            if m and m.group(1) not in targets:
                bad.append(f"make {m.group(1)}")
        tok = re.split(r"::|:\d", tok.strip("()[],;"))[0].rstrip(".,:")
        if re.search(r"[<>${}]", tok):
            continue                        # a placeholder, not a name
        if (tok.startswith(_PATH_PREFIXES)
                or re.fullmatch(r"[\w.*-]+\.(py|json|jsonl)", tok)) \
                and not glob.glob(os.path.join(REPO, tok)):
            bad.append(tok)
    return bad


@pytest.mark.parametrize("doc", ["README.md", "ROADMAP.md",
                                 ".claude/skills/verify/SKILL.md"])
def test_documents_cite_files_that_exist(doc):
    """Every back-quoted path under the repo's own directories, every
    back-quoted root-level ``*.py`` / ``*.json`` / ``*.jsonl`` name and
    every ``make <target>`` in the documents a planning session reads
    first resolves in the checkout.  PERF.md is not held to this: it
    cites upstream's files and uncommitted scratch scripts by design."""
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    targets = _make_targets()
    bad = sorted({b for c in _citations(text) for b in _missing(c, targets)})
    assert not bad, f"{doc} cites what the checkout does not have: {bad}"


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
