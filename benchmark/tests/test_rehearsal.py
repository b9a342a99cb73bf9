"""The harness end to end at a toy size on the CPU, through its internal
entry (``harness.run_cell`` with the chip check switched off), and the
command's refusal to run without a TPU.  No number read here is a device
metric: the traced rehearsal is given a recorded TPU trace to reduce, and
only the result line's shape is checked."""

import json
import os
import subprocess
import sys
import time

import pytest

from benchmark import harness, trace_reduce
from conftest import ROOT

NO_CHECK = dict(chip_check=lambda devices, chips: None)
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _metric_names(bench, section, cell):
    reported = {m["name"] for m in
                harness.metrics_of(bench, "end_to_end", cell, None)}
    if section == "end_to_end":
        return reported
    return {m["name"] for m in
            harness.metrics_of(bench, "per_layer", cell, reported)}


@pytest.mark.parametrize("workload", [
    "chain_32_symm.apply", "chain_32_symm.ground_state",
    "chain_32_symm_x4.ground_state"])
def test_untraced_run_reports_the_end_to_end_metrics(toy_bench, toy_system,
                                                     workload):
    res = harness.run_cell(toy_bench, workload, 3_000_000_019, 0.3, False,
                           time.perf_counter(), system_factory=toy_system,
                           **NO_CHECK)
    cell = harness.find(toy_bench["workloads"], workload, "workload")
    assert list(res)[:5] == RESULT_KEYS and list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == _metric_names(toy_bench, "end_to_end", cell)
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["count"] >= cell["chips"]
    assert res["window"]["elapsed_s"] >= 0.3
    assert res["window"]["window_compiles"]["compiled"] == 0, \
        res["window"]["window_compiles"]
    for row in res["checks"].values():
        assert row["value"] <= row["limit"]
    json.dumps(res)


def test_ground_state_window_is_whole_solves(toy_bench, toy_system):
    res = harness.run_cell(toy_bench, "chain_32_symm.ground_state", 11, 0.2,
                           False, time.perf_counter(),
                           system_factory=toy_system, **NO_CHECK)
    w = res["window"]
    assert w["solves"] == res["attempted"] >= 1
    assert w["iterations"] % 16 == 0 and w["iterations"] >= 16 * w["solves"]
    assert res["metrics"]["lanczos_iter_ms"]["value"] == pytest.approx(
        1e3 * w["elapsed_s"] / w["iterations"])


def test_traced_run_reports_the_per_layer_metrics(toy_bench, toy_system,
                                                  recorded_trace):
    """``--trace 1``: the window runs under the profiler; the CPU's trace
    has no device plane, so the reduction is handed a recorded TPU trace."""
    recorded = recorded_trace("chain32_apply")

    class Described(toy_system):
        """Names the chip the recorded trace came from: the CPU is in no
        peaks table, and a traced run stops there."""

        def start(self):
            class Device:
                platform, device_kind = "cpu", "TPU v5 lite"
            return [Device() for _ in super().start()]

    with pytest.raises(KeyError, match="no published peaks"):
        harness.run_cell(
            toy_bench, "chain_32_symm.apply", 5, 0.1, True,
            time.perf_counter(), system_factory=toy_system,
            reduce_trace=lambda directory: trace_reduce.reduce_file(recorded),
            chip_check=lambda devices, chips: None)
    res = harness.run_cell(
        toy_bench, "chain_32_symm.apply", 5, 0.2, True, time.perf_counter(),
        system_factory=Described,
        reduce_trace=lambda directory: trace_reduce.reduce_file(recorded),
        chip_check=lambda devices, chips: None)
    cell = harness.find(toy_bench["workloads"], "chain_32_symm.apply", "w")
    want = _metric_names(toy_bench, "per_layer", cell)
    # 257 rows keep one plain table: the build cuts no staircase, so it
    # makes no ``ell/stair_levels`` pass, and a reader that finds nothing
    # to read leaves its metric out of the line
    assert want - set(res["metrics"]) == {"build_levels_pass_s"}
    assert set(res["metrics"]) < want
    assert res["metrics"]["build_fill_pass_s"]["value"] > 0
    assert res["window"]["engine"]["table_ranges"] == 1
    assert res["device"]["busy_s"] > 0
    assert res["device"]["window_s"] >= res["device"]["busy_s"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(res["breakdown"]["device_ops"]) <= 10
    assert 0 < res["metrics"]["apply_roofline"]["value"] < 100


def test_every_metric_has_a_reader_and_every_cell_its_files():
    bench = harness.load_benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.load_reader(m["name"])), m["name"]
    names = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in names
    for cell in bench["workloads"]:
        config = harness.load_config(bench, cell["config"])
        assert os.path.exists(os.path.join(ROOT, config["model"]))
        from benchmark import traffic
        assert traffic.load(cell["traffic"])["kind"] in traffic.KINDS
        e2e = _metric_names(bench, "end_to_end", cell)
        assert "setup_s" in e2e and len(e2e) >= 2
        assert _metric_names(bench, "per_layer", cell)


def test_chip_check_refuses_the_cpu_and_too_few_chips():
    import jax

    with pytest.raises(harness.NoChip, match="needs a TPU"):
        harness.require_chips(jax.devices(), 1)

    class Fake:
        platform, device_kind = "tpu", "TPU v5 lite"

    harness.require_chips([Fake()], 1)
    with pytest.raises(harness.NoChip, match="asks for 4"):
        harness.require_chips([Fake()], 4)


def test_command_refuses_to_run_without_a_tpu():
    """The command itself, on this CPU: non-zero exit, no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "chain_32_symm.apply", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr
