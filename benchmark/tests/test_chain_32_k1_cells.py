"""The cell of PR 35, ``chain_32_k1.apply`` (upstream's 32-site chain at
translation character k = 1, spin inversion +1: a complex sector in (re, im)
pair form whose gather table is cut at 32 B a row), and the three per-layer
metrics that came with it (``near_gather_ns_per_slot``,
``far_gather_ns_per_slot``, ``near_slot_pct``).  The entries by name; the
roofline's bytes by hand; the sector's size by the closed form; the readers
on a synthetic table of operations and on a rehearsal through
``harness.run_cell`` with the real cell, traffic file, readers and
reference: the 20-site ring of the same sector (4,587 states) in pair form,
the rules' VMEM number patched below its table so that the table is cut, the
traced run handed a device trace made up from the toy engine's own gathers
at a rate a class.  No number read here is a device metric."""

import json
import os
import time
from math import comb
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import check, gather_rates, harness, trace_reduce, traffic
from benchmark import work
from conftest import ROOT, load_ring_reference, momentum_ring_yaml

CELL = "chain_32_k1.apply"
NEW = ("near_gather_ns_per_slot", "far_gather_ns_per_slot", "near_slot_pct")
NO_CHECK = dict(chip_check=lambda devices, chips: None)
TOY_SITES, TOY_STATES, TOY_RANGE = 20, 4_587, 2_048


# ---------------------------------------------------------------------------
# the entries and the configuration's files


def test_the_new_entries_name_their_cells():
    """PR 35's entries, by name: wherever later PRs put theirs."""
    bench = harness.load_benchmark()
    cell = harness.find(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("chain_32_k1", "apply", 1)
    entry = harness.find(bench["configs"], "chain_32_k1", "configuration")
    assert entry["file"] == "benchmark/configs/chain_32_k1.json"
    assert entry["reduced"] == [] and len(entry["source"]) <= 200
    assert "sector: 1" in entry["source"] and "9,390,656" in entry["source"]
    for text in (cell["why"], entry["why"]):
        assert 0 < len(text) <= 200 and "\n" not in text
    above = ["chain_28.apply", CELL]     # the cells above the VMEM line
    for name in NEW:
        m = harness.find(bench["per_layer"], name, "metric")
        assert (m["layer"], m["moves"], m["workloads"]) == \
            ("apply kernels", "apply_ms", above)
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert callable(harness.load_reader(name))
    assert [(m["unit"], m["better"], m["source"]) for m in
            (harness.find(bench["per_layer"], n, "metric") for n in NEW)] == [
        ("ns", "lower", "device_trace"), ("ns", "lower", "device_trace"),
        ("%", "higher", "program_counter")]
    # the metrics of the apply cells that list their cells list this one
    for name in ("gather_fill_pct", "gather_ns_per_slot",
                 "build_fill_pass_s", "build_levels_pass_s"):
        listed = harness.find(bench["per_layer"], name, "metric")["workloads"]
        assert CELL in listed and "chain_28.apply" in listed
    assert CELL in harness.find(bench["end_to_end"], "apply_ms",
                                "metric")["workloads"]


def test_the_cell_reports_the_metrics_that_reach_it():
    bench = harness.load_benchmark()
    cell = harness.find(bench["workloads"], CELL, "workload")
    e2e = {m["name"] for m in
           harness.metrics_of(bench, "end_to_end", cell, None)}
    assert e2e == {"apply_ms", "peak_hbm_gb", "setup_s"}
    layer = {m["name"] for m in
             harness.metrics_of(bench, "per_layer", cell, e2e)}
    assert layer == {
        "enumeration_s", "structure_build_s", "compile_s",
        "compilations_setup", "compilations_in_window.apply",
        "apply_device_ms", "apply_roofline", "device_idle_pct.apply",
        "gather_fill_pct", "gather_ns_per_slot", "build_fill_pass_s",
        "build_levels_pass_s", *NEW}
    # the cells under the line do not report the three: one kind of gather
    for under in ("chain_32_symm.apply", "square_5x5.apply"):
        c = harness.find(bench["workloads"], under, "workload")
        assert not set(NEW) & {m["name"] for m in harness.metrics_of(
            bench, "per_layer", c, e2e)}


def test_the_configuration_states_the_sector_and_the_roofline_bytes():
    bench = harness.load_benchmark()
    config = harness.load_config(bench, "chain_32_k1")
    entry = harness.find(bench["configs"], "chain_32_k1", "configuration")
    assert entry["reduced"] == config["reduced"] == []
    assert entry["source"] == config["source"]
    assert work.sector(config) == "complex" and work.value_bytes(config) == 16
    assert config["reference"] == "ring_heisenberg"
    assert config["engine"] == {"kind": "local", "devices": 1, "mode": None}
    assert (config["number_spins"], config["hamming_weight"],
            config["spin_inversion"], config["bonds"]) == (32, 16, 1, 32)
    assert config["group_order"] == 64          # 32 turns x the flip
    assert config["candidates"] == comb(32, 16) == 601_080_390
    # full-period orbits; those the flip maps to themselves (the states
    # (a, ~a), through the half turn: character -1) cancel; the rest pair
    orbits = (comb(32, 16) - comb(16, 8)) // 32
    assert orbits == 18_783_360
    assert config["number_states"] == (orbits - 2 ** 16 // 32) // 2 \
        == 9_390_656
    g, real = config["guarantees"], harness.load_config(
        bench, "chain_28")["guarantees"]
    assert (g["apply_atol"], g["apply_rtol"]) == \
        (real["apply_atol"], real["apply_rtol"]) == (1e-14, 1e-12)
    assert g["artifact_cache"] == real["artifact_cache"]
    assert len(config["assumed"]) == 2
    # counted by the reference over every row; the engine stores a slot a
    # bond, 448 more (two bonds of a row that reach one representative)
    n, nnz = 9_390_656, 155_077_440
    assert config["offdiag_nonzeros"] == nnz
    assert "count_offdiagonal" in config["offdiag_nonzeros_from"]
    # by hand: 16 B a value and 4 B an index for every non-zero (the
    # diagonal's too), a row pointer a row and one more, x read and y
    # written once at 16 B a row
    by_hand = (nnz + n) * (16 + 4) + (n + 1) * 4 + 2 * n * 16
    assert work.apply_bytes(config) == by_hand == 3_627_425_540
    assert work.iteration_bytes(config) == by_hand + 4 * n * 16


def test_the_models_text_is_the_symmetric_chains_but_for_the_sector():
    bench = harness.load_benchmark()
    config = harness.load_config(bench, "chain_32_k1")
    assert config["model"] == "benchmark/configs/chain_32_k1.yaml"
    with open(os.path.join(ROOT, config["model"]), encoding="utf-8") as f:
        text = f.read()
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "chain_32_symm.yaml"), encoding="utf-8") as f:
        symm = f.read()
    at = "hamiltonian:"
    assert text[text.index(at):] == symm[symm.index(at):]
    spec = load_ring_reference().Spec(os.path.join(ROOT, config["model"]))
    assert (spec.n, spec.hw, spec.k, spec.complex) == (32, 16, 1, True)
    assert spec.inversion and spec.inversion_character == 1
    assert spec.translation and not spec.reflection
    assert spec.group_order == config["group_order"]


# ---------------------------------------------------------------------------
# the three readers on a synthetic table of operations


def _run_with(own, counts, applies=2):
    """A run as the readers see it: the build span's counts and the fullest
    device's own seconds by signature, over ``applies`` applies."""
    build = dict({"kind": "span", "name": "engine_init/build_structure",
                  "dur_ms": 1000.0, "span_id": "b"}, **counts)
    fullest = SimpleNamespace(
        own={sig: [seconds, count] for sig, (seconds, count) in own.items()},
        module_runs=lambda pattern: (1.0, applies))
    return SimpleNamespace(
        config={"engine": {"kind": "local"}},
        events={"build": [build], "window": [], "lost": False},
        trace=SimpleNamespace(fullest=fullest))


# both row widths: a real vector's rows of three f32 parts (16 B), table
# ranges of 3,000 rows over 10,000 padded rows (9,990 states); a pair-form
# vector's rows of six (32 B), ranges of 1,500 over 4,500 (4,490 states)
WIDTHS = {
    3: dict(range_rows=3_000, table_rows=10_000, row_bytes=16,
            near_slots=40_000, far_slots=5_000, unpermute_slots=20_000,
            states=9_990),
    6: dict(range_rows=1_500, table_rows=4_500, row_bytes=32,
            near_slots=20_000, far_slots=2_500, unpermute_slots=9_000,
            states=4_490),
}


def _synthetic(parts):
    c = WIDTHS[parts]
    W, n = c["range_rows"], c["states"]
    last = n - n // W * W               # the last range of x is shorter
    p = parts
    own = {
        # near: a full range, a shorter level, the last range's rest
        f"fusion f32[{W},{p}](f32[{W},{p}],s32[{W}])": (8e-3, 12),
        f"fusion f32[{W - 512},{p}](f32[{W},{p}],s32[{W - 512}])": (2e-3, 4),
        f"fusion f32[{last},{p}](f32[{last},{p}],s32[{last}])": (1e-3, 2),
        # far: whole x is the table (the states, not the padded rows)
        f"fusion f32[{W},{p}](f32[{n},{p}],s32[{W}])": (3e-3, 2),
        f"fusion f32[1024,{p}](f32[{n},{p}],s32[1024])": (1e-3, 6),
        # what is no row gather: a multiply-add, a slice, a gather of
        # another element type, a fusion with a third operand
        f"fusion f32[{W},{p}](f32[{W},{p}],f32[{W},{p}])": (5e-3, 40),
        f"fusion s32[{W}](s32[4,{W}],s32[])": (1e-3, 40),
        f"fusion s32[{W}](s32[{W}],s32[{W}])": (1e-3, 2),
        f"fusion f32[{W},{p}](f32[{W},{p}],s32[{W}],f32[{W},{p}])": (9.0, 1),
        "custom-call f64[100](f32[100],f32[100]) X64Combine": (2e-3, 8),
    }
    return own, {k: v for k, v in c.items() if k != "states"}


@pytest.mark.parametrize("parts", [3, 6])
def test_the_readers_tell_the_gathers_apart_by_their_tables_rows(parts):
    own, counts = _synthetic(parts)
    run = _run_with(own, counts, applies=2)
    near, far, share = (harness.load_reader(n)(run) for n in NEW)
    # own seconds of the class, an apply, over the class's slots
    assert near == pytest.approx(
        1e9 * (8e-3 + 2e-3 + 1e-3) / 2
        / (counts["near_slots"] + counts["unpermute_slots"]))
    assert far == pytest.approx(1e9 * (3e-3 + 1e-3) / 2
                                / counts["far_slots"])
    assert share == pytest.approx(100.0 * counts["near_slots"] / (
        counts["near_slots"] + counts["far_slots"]))
    assert gather_rates.seconds_an_apply(run, counts) == {
        "near": pytest.approx(5.5e-3), "far": pytest.approx(2e-3)}


@pytest.mark.parametrize("parts", [3, 6])
def test_the_readers_read_nothing_without_their_counts(parts):
    own, counts = _synthetic(parts)
    readers = [harness.load_reader(n) for n in NEW]
    # the parent of PR 35: the twelve counts, none of the four
    parent = {k: counts[k] for k in ("near_slots", "far_slots")}
    for lacking in (parent, {}, dict(counts, range_rows=None),
                    {k: v for k, v in counts.items() if k != "table_rows"},
                    {k: v for k, v in counts.items()
                     if k != "unpermute_slots"}):
        run = _run_with(own, lacking)
        assert [read(run) for read in readers] == [None, None, None]
    # a cell under the line: the table is not cut, every slot a far one
    under = dict(counts, range_rows=0, near_slots=0,
                 far_slots=counts["near_slots"] + counts["far_slots"])
    run = _run_with(own, under)
    assert [read(run) for read in readers] == [None, None, None]
    # no build span at all, and a trace that holds no apply
    run = _run_with(own, counts)
    run.events["build"] = []
    assert [read(run) for read in readers] == [None, None, None]
    run = _run_with(own, counts, applies=0)
    assert [read(run) for read in readers[:2]] == [None, None]
    assert readers[2](run) is not None      # a counter needs no trace


@pytest.mark.parametrize("parts", [3, 6])
def test_a_gather_that_fits_neither_class_raises(parts):
    own, counts = _synthetic(parts)
    longer = counts["table_rows"] + 1
    own[f"fusion f32[1024,{parts}](f32[{longer},{parts}],s32[1024])"] = \
        (1e-3, 1)
    run = _run_with(own, counts)
    for name in NEW[:2]:
        with pytest.raises(RuntimeError, match="neither near nor far"):
            harness.load_reader(name)(run)
    assert harness.load_reader(NEW[2])(run) is not None


def test_the_signature_of_a_row_gather():
    """What ``trace_reduce.signature`` makes of a gather fusion's HLO text,
    as the chip's traces hold it (PERF.md §5), is what the readers match."""
    text = ("%fusion.212 = f32[1572864,6]{0,1:T(8,128)S(1)} fusion("
            "f32[1572864,6]{0,1:T(8,128)S(1)} %slice.4, "
            "s32[1572864]{0:T(1024)} %get-tuple-element.7), kind=kCustom, "
            "calls=%fused_computation.212")
    sig = trace_reduce.signature(text)
    assert sig == "fusion f32[1572864,6](f32[1572864,6],s32[1572864])"
    m = gather_rates.GATHER.match(sig)
    assert m and (int(m.group(1)), int(m.group(3))) == (1_572_864,) * 2
    for other in ("fusion f32[8,6](f32[8,3],s32[8])",
                  "fusion f32[8,6](f32[9,6],s32[7])",
                  "fusion f64[8,6](f64[9,6],s32[8])",
                  "copy f32[8,6](f32[8,6])"):
        assert not gather_rates.GATHER.match(other)


# ---------------------------------------------------------------------------
# a rehearsal at the 20-site ring of the same sector, the table cut


@pytest.fixture
def toy_k1(tmp_path, monkeypatch):
    """``BENCHMARK.json`` with ``chain_32_k1`` cut to the 20-site ring at
    k = 1 with spin inversion +1 (4,587 states: one chunk), the program in
    pair form as on a TPU, and the rules' VMEM number at what three ranges
    of 2,048 pair rows take (68 B a row), below the 32 B a row of the whole
    table: the table is cut, steered through the rules' input as
    ``tests/test_chain_32_k1_config.py`` does."""
    from distributed_matvec_tpu.parallel import engine
    from distributed_matvec_tpu.utils.config import get_config, update_config

    ref = load_ring_reference()
    model = momentum_ring_yaml(tmp_path / "ring_20_k1.yaml", TOY_SITES, 1, 1)
    spec = ref.Spec(model)
    reps = ref.enumerate_representatives(spec)
    assert reps.size == TOY_STATES
    bench = harness.load_benchmark()
    entry = harness.find(bench["configs"], "chain_32_k1", "configuration")
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    config.update(
        model=model, number_spins=TOY_SITES, hamming_weight=TOY_SITES // 2,
        group_order=2 * TOY_SITES, bonds=TOY_SITES,
        candidates=comb(TOY_SITES, TOY_SITES // 2),
        number_states=TOY_STATES,
        offdiag_nonzeros=ref.count_offdiagonal(spec, reps,
                                               np.arange(reps.size)))
    path = tmp_path / "ring_20_k1.json"
    path.write_text(json.dumps(config))
    entry["file"] = str(path)
    monkeypatch.setattr(engine, "GATHER_VMEM_BYTES", 68 * TOY_RANGE)
    assert engine.gather_table_ranges(TOY_STATES, 6) == (3, TOY_RANGE)
    was = get_config().complex_pair
    update_config(complex_pair="on")
    yield bench
    update_config(complex_pair=was)


def _device_trace_of(eng, applies, near_ns, far_ns):
    """A reduced device trace of ``applies`` applies of ``eng`` that holds
    its row gathers and nothing else, each as long as its rows at the rate
    of its class: one operation a column of every level (the gather of the
    ``lax.scan``'s body) and one a staircase whose rows are put back, with
    the tables ``_make_ell_matvec`` hands them: a range of ``x`` (the last
    one ends with the states), the accumulator of a range's rows, or whole
    ``x``."""
    W, n, n_pad = eng._ell_range_rows, eng.n_states, eng.n_padded
    ops, modules, t = [], [], 0

    def gather(rows, table, ns):
        nonlocal t
        text = (f"%fusion.{len(ops)} = f32[{rows},6]{{0,1:T(8,128)S(1)}} "
                f"fusion(f32[{table},6]{{0,1:T(8,128)}} %p.0, "
                f"s32[{rows}]{{0:T(1024)}} %p.1), kind=kCustom, calls=%f")
        ops.append((text, t, rows * ns))
        t += rows * ns

    for i in range(applies):
        start = t
        for j, (blk, pos) in enumerate(zip(eng._ell_blocks,
                                           eng._ell_pos_of)):
            r, is_far = divmod(j, 2)
            rows = min(W, n_pad - r * W)
            table = n if is_far else min(n, r * W + rows) - r * W
            for idx, _ in blk:
                for _ in range(idx.shape[0]):
                    gather(idx.shape[1], table, far_ns if is_far else near_ns)
            if pos is not None:
                gather(pos.shape[0], rows, near_ns)
        modules.append((f"jit_apply_fn({i})", start, t - start))
    device = trace_reduce.DeviceTrace(0, ops, modules, 0, t)
    return trace_reduce.TraceSummary(0, t, [device], [], [(0, t)])


def test_a_rehearsal_above_the_patched_line(toy_k1, toy_system):
    """``chain_32_k1.apply`` through ``harness.run_cell`` at toy size: a
    sound run is correct in pair form with the table cut; the traced run
    reports the three new metrics, which read the rates the made-up trace
    was given, so the span's counts are the rows of the engine's own
    gathers class by class; the result line carries the four counts."""
    engines = []

    class Described(toy_system):
        """Names the chip whose peaks a traced run looks up, and keeps the
        engine for the made-up trace."""

        def start(self):
            class Device:
                platform, device_kind = "cpu", "TPU v5 lite"
            return [Device() for _ in super().start()]

        def build_engine(self):
            super().build_engine()
            engines.append(self.engine)

    res = harness.run_cell(toy_k1, CELL, 2_147_483_659, 0.2, False,
                           time.perf_counter(), system_factory=Described,
                           **NO_CHECK)
    assert res["correct"] is True and res["failed"] == 0, res["checks"]
    assert set(res["metrics"]) == {"apply_ms", "peak_hbm_gb", "setup_s"}
    assert res["checks"]["apply_err_over_tol"]["value"] < 0.5
    assert res["window"]["window_compiles"]["compiled"] == 0
    counts = res["window"]["engine"]
    assert counts["pair"] is True and counts["n_states"] == TOY_STATES
    assert (counts["table_ranges"], counts["range_rows"],
            counts["table_rows"], counts["row_bytes"]) == \
        (3, TOY_RANGE, TOY_STATES, 32)
    assert counts["unpermute_slots"] == counts["gather_slots"] \
        - counts["near_slots"] - counts["far_slots"] > 0
    assert 0 < counts["far_slots"] < counts["near_slots"]

    res = harness.run_cell(
        toy_k1, CELL, 2_147_483_693, 0.2, True, time.perf_counter(),
        system_factory=Described, chip_check=lambda devices, chips: None,
        reduce_trace=lambda directory: _device_trace_of(
            engines[-1], 3, near_ns=4, far_ns=16))
    assert res["correct"] is True, res["checks"]
    assert set(NEW) < set(res["metrics"])
    got = {n: res["metrics"][n]["value"] for n in NEW}
    counts = res["window"]["engine"]
    assert got == {
        "near_gather_ns_per_slot": pytest.approx(4.0),
        "far_gather_ns_per_slot": pytest.approx(16.0),
        "near_slot_pct": pytest.approx(100.0 * counts["near_slots"] / (
            counts["near_slots"] + counts["far_slots"]))}
    # the whole apply's mean lies between the two, as at chain_28
    assert 4.0 < res["metrics"]["gather_ns_per_slot"]["value"] < 16.0
    assert res["metrics"]["gather_fill_pct"]["value"] > 50.0
    assert res["metrics"]["build_fill_pass_s"]["value"] > 0
    json.dumps(res)


def test_the_complex64_control_is_not_correct(toy_k1, toy_system):
    cell = harness.find(toy_k1["workloads"], CELL, "workload")
    config = harness.load_config(toy_k1, cell["config"])
    system = toy_system(config)
    system.start()
    n = system.enumerate()
    system.build_engine()
    assert system.engine._ell_range_rows == TOY_RANGE and system.engine.pair
    mix = traffic.make(cell["traffic"], 4_000_000_007)
    mix.warm_up(system, n)
    mix.window(system, 0.0, harness.annotator(False))
    answers = mix.collect(system)
    ref = mix.reference(config)
    sound, ok = check.judge(mix.compare(ref, answers), mix.limits())
    assert ok and sound["apply_err_over_tol"]["value"] < 0.1, sound
    table, ok = check.judge(mix.compare(ref, mix.control(ref, answers)),
                            mix.limits())
    assert not ok and table["apply_err_over_tol"]["value"] > 1e3, table
    assert table["basis_size_diff"]["value"] == 0
