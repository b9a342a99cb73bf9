"""The two cells of PR 32 rehearsed on the CPU at a 16-site ring, through
``harness.run_cell`` with the real cells, traffic files, readers and
references: ``chain_28.apply`` on the ring without symmetries (12,870
states, the two-pass build forced as the full size's rule decides it), and
``chain_32_symm.ground_state_restart`` on the symmetric ring (257 states)
under a cap low enough that every solve restarts.  A sound run reads
``correct`` true, the float32 controls and a Hamiltonian with bonds left out
read false.  No number read here is a device metric."""

import json
import os
import time
from types import SimpleNamespace

import pytest

from benchmark import build_passes, check, harness, program_spans, traffic
from conftest import ROOT, ring_yaml
from test_lattice_heisenberg import lattice_yaml

CELLS = ["chain_28.apply", "chain_32_symm.ground_state_restart"]
NO_CHECK = dict(chip_check=lambda devices, chips: None)
RING = [[i, (i + 1) % 16] for i in range(16)]
TOY_CAP = 12


@pytest.fixture(autouse=True)
def leave_no_toy_builds():
    """The program's event store is the process's: the build readers take
    the one build whose duration the engine's timer read, and refuse where
    several toy builds of a few milliseconds qualify (``PERF.md`` §7).
    This file's dozen builds do not stay for the tests that follow."""
    yield
    from distributed_matvec_tpu.obs.events import reset

    reset()


@pytest.fixture
def toy_cells(tmp_path, monkeypatch):
    """``BENCHMARK.json`` with ``chain_28`` cut to the 16-site ring without
    symmetries and ``chain_32_symm`` to the symmetric one, the restart
    traffic's cap cut to :data:`TOY_CAP` (257 states converge under the
    file's 48 without a restart), and for each cell the path of its model
    with every fourth bond left out."""
    bench = harness.load_benchmark()
    models = {"chain_28": lattice_yaml(tmp_path / "ring_16.yaml", 16, 8,
                                       RING),
              "chain_32_symm": ring_yaml(tmp_path / "ring_16_symm.yaml", 16)}
    sizes = {"chain_28": dict(number_states=12_870, candidates=12_870,
                              bonds=16, offdiag_nonzeros=2 * 16 * 3_432),
             "chain_32_symm": dict(number_states=257,
                                   offdiag_nonzeros=1774)}
    for name, model in models.items():
        entry = harness.find(bench["configs"], name, "configuration")
        with open(os.path.join(ROOT, entry["file"])) as f:
            config = json.load(f)
        config.update(model=model, number_spins=16, hamming_weight=8,
                      **sizes[name])
        path = tmp_path / (name + ".json")
        path.write_text(json.dumps(config))
        entry["file"] = str(path)
    load = traffic.load

    def toy_load(name):
        params = load(name)
        if name == "ground_state_restart":
            assert params["max_basis_size"] == 48
            params["max_basis_size"] = TOY_CAP
        return params

    monkeypatch.setattr(traffic, "load", toy_load)
    fewer = [b for i, b in enumerate(RING) if i % 4]
    broken = {
        "chain_28.apply": lattice_yaml(tmp_path / "fewer.yaml", 16, 8,
                                       fewer),
        "chain_32_symm.ground_state_restart": str(tmp_path / "fewer_s.yaml")}
    with open(models["chain_32_symm"], encoding="utf-8") as f:
        text = f.read()
    with open(broken[CELLS[1]], "w", encoding="utf-8") as f:
        f.write(text.replace(str(RING), str(fewer)))
    assert text.count(str(RING)) == 3
    return bench, broken


@pytest.fixture
def two_pass():
    """The size rule sends the toy's build where it sends ``chain_28``'s."""
    from distributed_matvec_tpu.utils.config import get_config, update_config

    was = get_config().ell_build_budget_gb
    update_config(ell_build_budget_gb=1e-9)
    yield was
    update_config(ell_build_budget_gb=was)


def _run(bench, system, workload, seed=2_147_483_659):
    return harness.run_cell(bench, workload, seed, 0.2, False,
                            time.perf_counter(), system_factory=system,
                            **NO_CHECK)


def _over(res):
    return {k for k, row in res["checks"].items()
            if not row["value"] <= row["limit"]}


@pytest.mark.parametrize("workload", CELLS)
def test_a_sound_run_is_correct(toy_cells, toy_system, two_pass, workload):
    bench, _ = toy_cells
    res = _run(bench, toy_system, workload)
    cell = harness.find(bench["workloads"], workload, "workload")
    assert res["correct"] is True and res["failed"] == 0, res["checks"]
    want = {m["name"] for m in
            harness.metrics_of(bench, "end_to_end", cell, None)}
    assert set(res["metrics"]) == want
    assert {"setup_s", "peak_hbm_gb"} < want and len(want) == 3
    if workload == "chain_28.apply":
        assert "apply_ms" in want
        assert res["window"]["window_compiles"]["compiled"] == 0
    else:
        assert "lanczos_iter_ms" in want
        # every solve of the window compressed its basis at least once
        assert res["window"]["restarts"] >= res["window"]["solves"] >= 1
        assert res["checks"]["e0_rel_err"]["value"] < 1e-12
    json.dumps(res)


def test_the_restart_traffic_is_the_ground_state_traffic_under_a_cap_of_48():
    base, restart = (traffic.load(n) for n in ("ground_state",
                                               "ground_state_restart"))
    assert base["max_basis_size"] == 96
    assert restart == dict(base, max_basis_size=48)
    assert restart["min_restart_size"] is None


@pytest.mark.parametrize("workload", CELLS)
def test_the_new_cells_report_the_metrics_that_reach_them(workload):
    """A traced run's per-layer metrics, by ``harness.metrics_of``."""
    bench = harness.load_benchmark()
    cell = harness.find(bench["workloads"], workload, "workload")
    assert cell["chips"] == 1
    e2e = {m["name"] for m in
           harness.metrics_of(bench, "end_to_end", cell, None)}
    layer = {m["name"] for m in
             harness.metrics_of(bench, "per_layer", cell, e2e)}
    shared = {"enumeration_s", "structure_build_s", "compile_s",
              "compilations_setup"}
    if workload == "chain_28.apply":
        assert cell["config"] == "chain_28" and cell["traffic"] == "apply"
        assert layer == shared | {
            "compilations_in_window.apply", "apply_device_ms",
            "apply_roofline", "device_idle_pct.apply", "gather_fill_pct",
            "gather_ns_per_slot", "build_count_pass_s", "build_pack_pass_s"}
    else:
        assert cell["config"] == "chain_32_symm"
        assert layer == shared | {
            "compilations_in_window.solve", "iter_device_ms",
            "iter_roofline", "block_boundary_ms", "device_idle_pct.solve",
            "solver_dispatch_idle_ms", "solver_check_idle_ms",
            "applies_per_iteration", "block_programs_built.solve"}
    for name in layer:
        assert callable(harness.load_reader(name))
    # the two pass metrics are chain_28.apply's alone
    for name in ("build_count_pass_s", "build_pack_pass_s"):
        entry = harness.find(bench["per_layer"], name, "metric")
        assert entry["workloads"] == ["chain_28.apply"]
        assert (entry["layer"], entry["moves"], entry["source"]) == \
            ("structure build", "setup_s", "program_span")


def test_the_configuration_states_upstreams_size_and_the_roofline_bytes():
    from benchmark import work

    bench = harness.load_benchmark()
    config = harness.load_config(bench, "chain_28")
    entry = harness.find(bench["configs"], "chain_28", "configuration")
    assert entry["reduced"] == config["reduced"] == []
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert config["number_states"] == config["candidates"] == 40_116_600
    assert config["offdiag_nonzeros"] == 582_433_600
    assert config["engine"] == {"kind": "local", "devices": 1, "mode": None}
    assert config["guarantees"] == harness.load_config(
        bench, "square_5x5")["guarantees"]
    assert len(config["assumed"]) == 2
    assert work.apply_bytes(config) == 8_272_934_404


@pytest.mark.parametrize("workload, over", [
    ("chain_28.apply", {"apply_err_over_tol"}),
    ("chain_32_symm.ground_state_restart", {"residual_over_tol",
                                            "e0_rel_err", "norm_err"}),
])
def test_the_float32_control_is_not_correct(toy_cells, toy_system, workload,
                                            over):
    bench, _ = toy_cells
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
    ("chain_28.apply", {"apply_err_over_tol"}),
    ("chain_32_symm.ground_state_restart", {"residual_over_tol",
                                            "e0_rel_err"}),
])
def test_bonds_left_out(toy_cells, toy_system, workload, over):
    """The program is handed another Hamiltonian than the configuration
    states; the reference reads the configuration's."""
    bench, broken = toy_cells

    class FewerBonds(toy_system):
        def enumerate(self):
            self.config = dict(self.config, model=broken[workload])
            return super().enumerate()

    res = _run(bench, FewerBonds, workload)
    assert res["correct"] is False
    assert over <= _over(res), res["checks"]


def test_the_pass_readers_read_this_runs_two_pass_build(toy_cells,
                                                        toy_system, two_pass):
    """After a two-pass build the readers give the two passes' seconds,
    inside the build's; after a one-pass build, and on a program whose
    build opens no such spans (the parent commit's), nothing."""
    from distributed_matvec_tpu.obs.events import reset

    bench, _ = toy_cells
    config = harness.load_config(bench, "chain_28")
    count = harness.load_reader("build_count_pass_s")
    pack = harness.load_reader("build_pack_pass_s")

    def built():
        reset()         # one build in the store: toy builds last alike
        system = toy_system(config)
        system.start()
        system.enumerate()
        system.build_engine()
        return SimpleNamespace(config=config, timers=system.timers())

    run = built()
    a, b = count(run), pack(run)
    assert a > 0 and b > 0 and a + b <= run.timers["structure_build_s"]
    assert build_passes.pass_seconds(run, "ell/fill") is None

    from distributed_matvec_tpu.utils.config import update_config
    update_config(ell_build_budget_gb=two_pass)     # the rule as it stands
    run = built()
    assert count(run) is None and pack(run) is None
    assert build_passes.pass_seconds(run, "ell/fill") > 0


def test_the_pass_readers_read_nothing_without_their_spans(monkeypatch):
    run = SimpleNamespace(config={"engine": {"kind": "local"}},
                          timers={"structure_build_s": 1.0})
    build = {"name": "engine_init/build_structure", "dur_ms": 1000.0,
             "span_id": "b"}
    other = {"name": "ell/count_rows", "dur_ms": 400.0, "span_id": "c",
             "parent_span_id": "another build"}
    for name in ("build_count_pass_s", "build_pack_pass_s"):
        read = harness.load_reader(name)
        # no event store, no build span, a build span without passes, and
        # a pass of another build
        for spans in ([], [dict(other)], [dict(build)],
                      [dict(build), dict(other)]):
            monkeypatch.setattr(program_spans, "span_events", lambda: spans)
            assert read(run) is None
    mine = dict(other, parent_span_id="b")
    monkeypatch.setattr(program_spans, "span_events",
                        lambda: [dict(build), mine])
    assert harness.load_reader("build_count_pass_s")(run) == \
        pytest.approx(0.4)
    assert harness.load_reader("build_pack_pass_s")(run) is None
