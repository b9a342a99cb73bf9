"""The benchmark's own tests run on the CPU, at toy sizes, on four virtual
devices: they check the harness's logic and the yardstick's arithmetic, and
never produce a number under a device metric's name.

    python -m pytest benchmark/tests -q
"""

import gzip
import json
import os
import shutil
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
    + " --xla_cpu_collective_call_terminate_timeout_seconds=1200")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["DMT_ARTIFACT_CACHE"] = "off"
# as ``benchmark/system.py::start`` sets it, but before anything imports
# JAX: JAX reads the variable once, at import, and a test process imports
# JAX before a rehearsal's ``start``.  Left at JAX's default of 1 s, a toy
# program compiled during a warm-up is not in the persistent cache when the
# window asks for it again, and ``window_compiles.compiled`` reads 1
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

import pytest  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def ring_yaml(path, n_sites, symmetric=True):
    """A periodic Heisenberg chain in the upstream YAML schema, with the
    chain_32_symm symmetries at this size when ``symmetric``."""
    bonds = [[i, (i + 1) % n_sites] for i in range(n_sites)]
    lines = [f"basis:\n  number_spins: {n_sites}\n"
             f"  hamming_weight: {n_sites // 2}\n"]
    if symmetric:
        lines.append(
            "  spin_inversion: 1\n  symmetries:\n"
            f"    - {{permutation: {[*range(1, n_sites), 0]}, sector: 0}}\n"
            f"    - {{permutation: {[*reversed(range(n_sites))]}, "
            "sector: 0}\n")
    lines.append("hamiltonian:\n  name: Heisenberg\n  terms:\n")
    for axis in "ˣʸᶻ":
        lines.append(f"    - {{expression: \"σ{axis}₀ σ{axis}₁\", "
                     f"sites: {bonds}}}\n")
    with open(path, "w", encoding="utf-8") as f:
        f.writelines(lines)
    return str(path)


def momentum_ring_yaml(path, n_sites, k, inversion=None):
    """A periodic Heisenberg chain at half filling in the translation
    sector ``k`` (no reflection), with the spin flip's character
    ``inversion`` (+1, -1, or ``None`` for no spin flip)."""
    bonds = [[i, (i + 1) % n_sites] for i in range(n_sites)]
    lines = [f"basis:\n  number_spins: {n_sites}\n"
             f"  hamming_weight: {n_sites // 2}\n"]
    if inversion is not None:
        lines.append(f"  spin_inversion: {inversion}\n")
    lines.append("  symmetries:\n"
                 f"    - {{permutation: {[*range(1, n_sites), 0]}, "
                 f"sector: {k}}}\n")
    lines.append("hamiltonian:\n  name: Heisenberg\n  terms:\n")
    for axis in "ˣʸᶻ":
        lines.append(f"    - {{expression: \"σ{axis}₀ σ{axis}₁\", "
                     f"sites: {bonds}}}\n")
    with open(path, "w", encoding="utf-8") as f:
        f.writelines(lines)
    return str(path)


def load_ring_reference():
    """``benchmark/references/ring_heisenberg.py`` as a module of its own,
    as ``benchmark/check.py`` loads it."""
    import importlib.util

    path = os.path.join(ROOT, "benchmark", "references", "ring_heisenberg.py")
    spec = importlib.util.spec_from_file_location("ring_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def recorded_trace(tmp_path):
    """Path of a recorded TPU trace, unpacked: ``recorded_trace(name)``."""
    def unpack(name):
        out = tmp_path / (name + ".xplane.pb")
        with gzip.open(os.path.join(DATA, name + ".xplane.pb.gz")) as src, \
                open(out, "wb") as dst:
            shutil.copyfileobj(src, dst)
        return str(out)
    return unpack


@pytest.fixture
def toy_bench(tmp_path):
    """``BENCHMARK.json`` with every configuration cut to a 16-site ring
    (257 states): the cells, traffic files and metric readers are the real
    ones."""
    from benchmark import harness

    bench = harness.load_benchmark()
    model = ring_yaml(tmp_path / "ring_16.yaml", 16)
    for entry in bench["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            config = json.load(f)
        config.update(model=model, number_spins=16, hamming_weight=8,
                      number_states=257, offdiag_nonzeros=1774)
        path = tmp_path / (entry["name"] + ".json")
        path.write_text(json.dumps(config))
        entry["file"] = str(path)
    return bench


@pytest.fixture
def toy_system():
    """The system adapter for a backend that reports no memory statistics."""
    from benchmark.system import System

    class Toy(System):
        def memory_peak_bytes(self):
            return 1

    return Toy
