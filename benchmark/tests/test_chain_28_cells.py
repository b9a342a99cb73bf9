"""The two cells of PR 32 rehearsed on the CPU at a 16-site ring, through
``harness.run_cell`` with the real cells, traffic files, readers and
references: ``chain_28.apply`` on the ring without symmetries (12,870
states, the two-pass build forced as the full size's rule decides it), and
``chain_32_symm.ground_state_restart`` on the symmetric ring (257 states)
under a cap low enough that every solve restarts.  A sound run reads
``correct`` true, the float32 controls and a Hamiltonian with bonds left out
read false.  The two metrics that took the place of PR 32's pass metrics
(``build_fill_pass_s``, ``build_levels_pass_s``, PR 34) are read off toy
builds of all three kinds: one pass below the table-cut line, one a table
range above it, none in the two-pass build.  No number read here is a device
metric."""

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
    assert restart == dict(base, max_basis_size=48, warm_restart=True)
    assert restart["min_restart_size"] is None
    assert "warm_restart" not in base


def test_the_warm_up_builds_the_restart_program_the_solver_builds(
        toy_cells, toy_system, monkeypatch):
    """Under the restart traffic every solve restarts and the warm-up's one
    block never does: ``warm_epilogue`` compiles the restart's program for
    the sizes the solver works out itself, so that a cold compile cache
    compiles it in set-up and not inside the first window (PR 34: the chip
    read ``jit(restart)`` compiled in the window of a machine's first run).
    Here: what the warm-up hands ``_make_restart`` is what ``lanczos`` hands
    it in the window, and the other ``ground_state`` traffic warms none."""
    import importlib

    module = importlib.import_module("distributed_matvec_tpu.solve.lanczos")
    real, asked = module._make_restart, []

    def recording(mcap, shape, dtype, keep):
        asked.append((mcap, tuple(shape), str(dtype), keep))
        return real(mcap, shape, dtype, keep)

    monkeypatch.setattr(module, "_make_restart", recording)
    bench, _ = toy_cells
    res = _run(bench, toy_system, "chain_32_symm.ground_state_restart")
    assert res["correct"] is True and res["window"]["restarts"] >= 1
    # the warm-up's block (lanczos builds its restart closure every call),
    # then warm_epilogue, then one a solve of the window
    assert len(asked) == 2 + res["window"]["solves"]
    assert len(set(asked)) == 1 and asked[0][0] == TOY_CAP
    assert asked[0][1:3] == ((257,), "float64")
    del asked[:]
    res = _run(bench, toy_system, "chain_32_symm.ground_state")
    assert len(asked) == 1 + res["window"]["solves"]    # no warm_epilogue's


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
            "gather_ns_per_slot", "build_fill_pass_s", "build_levels_pass_s"}
    else:
        assert cell["config"] == "chain_32_symm"
        assert layer == shared | {
            "compilations_in_window.solve", "iter_device_ms",
            "iter_roofline", "block_boundary_ms", "device_idle_pct.solve",
            "solver_dispatch_idle_ms", "solver_check_idle_ms",
            "applies_per_iteration", "block_programs_built.solve"}
    for name in layer:
        assert callable(harness.load_reader(name))
    # the two pass metrics are the three apply cells'; the two they took
    # the place of are gone with the build that made their passes
    for name in ("build_fill_pass_s", "build_levels_pass_s"):
        entry = harness.find(bench["per_layer"], name, "metric")
        assert entry["workloads"] == [
            "chain_32_symm.apply", "square_5x5.apply", "chain_28.apply"]
        assert (entry["layer"], entry["moves"], entry["source"]) == \
            ("structure build", "setup_s", "program_span")
    assert not {"build_count_pass_s", "build_pack_pass_s"} & {
        m["name"] for m in bench["per_layer"]}


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


@pytest.fixture
def table_outside_vmem(monkeypatch):
    """The rules' VMEM number a range of 1,024 rows wide: ``x`` as a gather
    table does not fit, the row-block rule has no room, and the table is
    cut (steered through the rules' input, as ``tests/
    test_chain_28_config.py`` does, not through an option)."""
    from distributed_matvec_tpu.parallel import engine

    monkeypatch.setattr(engine, "GATHER_VMEM_BYTES", 36 * 1024)


def _built(toy_system, config):
    """A run as far as the readers need it: the engine built, this run's
    events handed over."""
    system = toy_system(config)
    system.start()
    system.enumerate()
    system.build_engine()
    system.open_window()
    return SimpleNamespace(config=config, timers=system.timers(),
                           events=system.close_window(),
                           counts=system.engine_counts())


def test_the_pass_readers_sum_a_pass_a_table_range(toy_cells, toy_system,
                                                   table_outside_vmem):
    """Above the table-cut line the build makes an ``ell/fill`` and an
    ``ell/stair_levels`` pass a range (twelve at ``chain_28``): each reader
    gives the sum of its passes, inside the build's seconds."""
    bench, _ = toy_cells
    config = harness.load_config(bench, "chain_28")
    run = _built(toy_system, config)
    ranges = run.counts["table_ranges"]
    assert ranges == run.counts["row_blocks"] > 1
    assert 0 < run.counts["far_slots"] < run.counts["near_slots"]
    build = program_spans.build_span(run)
    passes = {name: [e["dur_ms"] for e in run.events["build"]
                     if e.get("name") == name
                     and e.get("parent_span_id") == build["span_id"]]
              for name in ("ell/fill", "ell/stair_levels")}
    assert [len(p) for p in passes.values()] == [ranges, ranges]
    fill = harness.load_reader("build_fill_pass_s")(run)
    levels = harness.load_reader("build_levels_pass_s")(run)
    assert fill == pytest.approx(sum(passes["ell/fill"]) / 1e3)
    assert levels == pytest.approx(sum(passes["ell/stair_levels"]) / 1e3)
    assert fill > max(passes["ell/fill"]) / 1e3
    assert 0 < fill + levels <= run.timers["structure_build_s"]


def test_the_pass_readers_read_the_one_pass_and_not_the_two_pass_build(
        toy_cells, toy_system, two_pass):
    """Below the line the one-pass build makes one pass of each name; the
    two-pass build (no cell takes it since PR 33) makes neither, and the
    readers say nothing there.  Every build of this process is in the
    program's ring; each run reads its own."""
    from distributed_matvec_tpu.utils.config import update_config

    bench, _ = toy_cells
    config = harness.load_config(bench, "chain_28")
    fill = harness.load_reader("build_fill_pass_s")
    levels = harness.load_reader("build_levels_pass_s")

    run = _built(toy_system, config)            # the two-pass build
    assert run.counts["build_passes"] == 2
    assert fill(run) is None and levels(run) is None
    assert build_passes.pass_seconds(run, "ell/count_rows") > 0
    assert build_passes.pass_seconds(run, "ell/pack") > 0

    update_config(ell_build_budget_gb=two_pass)     # the rule as it stands
    run = _built(toy_system, config)
    assert run.counts["build_passes"] == 1
    assert run.counts["table_ranges"] == 1
    a, b = fill(run), levels(run)
    assert a > 0 and b > 0 and a + b <= run.timers["structure_build_s"]
    build = program_spans.build_span(run)
    assert [e["name"] for e in run.events["build"]
            if e.get("parent_span_id") == build["span_id"]
            and e["name"] in ("ell/fill", "ell/stair_levels")] == \
        ["ell/fill", "ell/stair_levels"]


def test_the_pass_readers_read_nothing_without_their_spans():
    build = {"kind": "span", "name": "engine_init/build_structure",
             "dur_ms": 1000.0, "span_id": "b"}
    other = {"kind": "span", "name": "ell/fill", "dur_ms": 400.0,
             "span_id": "c", "parent_span_id": "another build"}

    def run(*events):
        return SimpleNamespace(
            config={"engine": {"kind": "local"}},
            events={"build": [dict(e) for e in events], "window": [],
                    "lost": False})

    for name in ("build_fill_pass_s", "build_levels_pass_s"):
        read = harness.load_reader(name)
        # no events, no build span, a build span without passes, and a
        # pass of another build
        for events in ((), (other,), (build,), (build, other)):
            assert read(run(*events)) is None
    mine = dict(other, parent_span_id="b")
    second = dict(mine, span_id="d", dur_ms=100.0)
    assert harness.load_reader("build_fill_pass_s")(
        run(build, mine, second)) == pytest.approx(0.5)
    assert harness.load_reader("build_levels_pass_s")(
        run(build, mine, second)) is None
