"""The two ``square_5x5`` cells rehearsed on the CPU at a 4x4 torus (12,870
states), through ``harness.run_cell`` with the real cells, traffic files,
readers and reference: a sound run reads ``correct`` true, the float32
controls and a Hamiltonian with half its bonds left out read false.  No
number read here is a device metric."""

import json
import os
import time

import pytest

from benchmark import check, harness, traffic
from conftest import ROOT
from test_lattice_heisenberg import lattice_yaml, torus_bonds

CELLS = ["square_5x5.apply", "square_5x5.ground_state"]
NO_CHECK = dict(chip_check=lambda devices, chips: None)


@pytest.fixture
def toy_square(tmp_path):
    """``BENCHMARK.json`` with ``square_5x5`` cut to the 4x4 torus at
    hamming weight 8, and the path of the same model with every second bond
    left out."""
    bench = harness.load_benchmark()
    bonds = torus_bonds(4, 4)
    model = lattice_yaml(tmp_path / "torus_4x4.yaml", 16, 8, bonds)
    entry = harness.find(bench["configs"], "square_5x5", "configuration")
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    config.update(model=model, number_spins=16, hamming_weight=8, bonds=32,
                  number_states=12_870, candidates=12_870,
                  offdiag_nonzeros=2 * 32 * 3_432)
    path = tmp_path / "square_4x4.json"
    path.write_text(json.dumps(config))
    entry["file"] = str(path)
    return bench, lattice_yaml(tmp_path / "half.yaml", 16, 8, bonds[::2])


def _run(bench, system, workload, seed=2_147_483_659):
    return harness.run_cell(bench, workload, seed, 0.2, False,
                            time.perf_counter(), system_factory=system,
                            **NO_CHECK)


def _over(res):
    return {k for k, row in res["checks"].items()
            if not row["value"] <= row["limit"]}


@pytest.mark.parametrize("workload", CELLS)
def test_a_sound_run_is_correct(toy_square, toy_system, workload):
    bench, _ = toy_square
    res = _run(bench, toy_system, workload)
    cell = harness.find(bench["workloads"], workload, "workload")
    assert res["correct"] is True and res["failed"] == 0, res["checks"]
    want = {m["name"] for m in
            harness.metrics_of(bench, "end_to_end", cell, None)}
    assert set(res["metrics"]) == want
    assert {"setup_s", "peak_hbm_gb"} < want and len(want) == 3
    assert res["window"]["window_compiles"]["compiled"] == 0
    if workload.endswith("ground_state"):
        assert res["window"]["restarts"] == 0
        assert res["checks"]["e0_rel_err"]["value"] < 1e-13
    json.dumps(res)


@pytest.mark.parametrize("workload", CELLS)
def test_the_new_cells_report_the_metrics_that_reach_them(workload):
    """A traced run's per-layer metrics, by ``harness.metrics_of``."""
    bench = harness.load_benchmark()
    cell = harness.find(bench["workloads"], workload, "workload")
    assert cell["chips"] == 1 and cell["config"] == "square_5x5"
    e2e = {m["name"] for m in
           harness.metrics_of(bench, "end_to_end", cell, None)}
    layer = {m["name"] for m in
             harness.metrics_of(bench, "per_layer", cell, e2e)}
    shared = {"enumeration_s", "structure_build_s", "compile_s",
              "compilations_setup"}
    if workload.endswith(".apply"):
        assert layer == shared | {
            "compilations_in_window.apply", "apply_device_ms",
            "apply_roofline", "device_idle_pct.apply", "gather_fill_pct",
            "gather_ns_per_slot", "build_fill_pass_s", "build_levels_pass_s"}
    else:
        assert layer == shared | {
            "compilations_in_window.solve", "iter_device_ms",
            "iter_roofline", "block_boundary_ms", "device_idle_pct.solve",
            "solver_dispatch_idle_ms", "solver_check_idle_ms",
            "applies_per_iteration", "block_programs_built.solve"}
    for name in layer:
        assert callable(harness.load_reader(name))


@pytest.mark.parametrize("workload, over", [
    ("square_5x5.apply", {"apply_err_over_tol"}),
    ("square_5x5.ground_state", {"residual_over_tol", "e0_rel_err",
                                 "norm_err"}),
])
def test_the_float32_control_is_not_correct(toy_square, toy_system, workload,
                                            over):
    bench, _ = toy_square
    cell = harness.find(bench["workloads"], workload, "workload")
    config = harness.load_config(bench, cell["config"])
    system = toy_system(config)
    system.start()
    n = system.enumerate()
    system.build_engine()
    mix = traffic.make(cell["traffic"], 4_000_000_007)
    mix.warm_up(system, n)
    mix.window(system, 0.0, harness.annotator(False))
    answers = mix.collect(system)
    ref = mix.reference(config)
    sound, ok = check.judge(mix.compare(ref, answers), mix.limits())
    assert ok, sound
    table, ok = check.judge(mix.compare(ref, mix.control(ref, answers)),
                            mix.limits())
    assert not ok
    assert over <= {k for k, row in table.items()
                    if not row["value"] <= row["limit"]}, table


@pytest.mark.parametrize("workload, over", [
    ("square_5x5.apply", {"apply_err_over_tol"}),
    ("square_5x5.ground_state", {"residual_over_tol", "e0_rel_err"}),
])
def test_half_the_bonds_left_out(toy_square, toy_system, workload, over):
    """The program is handed another Hamiltonian than the configuration
    states; the reference reads the configuration's."""
    bench, half = toy_square

    class HalfTheBonds(toy_system):
        def enumerate(self):
            self.config = dict(self.config, model=half)
            return super().enumerate()

    res = _run(bench, HalfTheBonds, workload)
    assert res["correct"] is False
    assert over <= _over(res), res["checks"]


def test_gather_ns_per_slot_reads_nothing_without_counts_or_a_trace():
    """The new reader on a run whose build span carries no counts (the
    parent commit's), and on one whose trace holds no apply."""
    from types import SimpleNamespace

    read = harness.load_reader("gather_ns_per_slot")
    fullest = SimpleNamespace(module_runs=lambda pattern: (4.0, 8))
    build = {"kind": "span", "name": "engine_init/build_structure",
             "dur_ms": 1000.0}

    def run(build):
        return SimpleNamespace(
            config={"engine": {"kind": "local"}},
            events={"build": [build], "window": [], "lost": False},
            trace=SimpleNamespace(fullest=fullest))

    assert read(run(build)) is None
    counted = dict(build, gather_slots=10**8)
    assert read(run(counted)) == pytest.approx(5.0)  # 0.5 s over 1e8 slots
    fullest.module_runs = lambda pattern: (0.0, 0)
    assert read(run(counted)) is None
