"""The one span surface (obs/trace.py::span, PR 26): a span is an event in
the in-memory ring, a monotonic duration, counts added while it is open,
and — for the leaf kinds — a ``jax.profiler.TraceAnnotation`` of the same
name, so that a device trace shows the program's spans on its host line.
And the spans the program opens with it: the Lanczos host loop, the plan
build's passes, the places a build waits for the device."""

import os
import re
import time
from contextlib import nullcontext

import jax
import pytest

from distributed_matvec_tpu import obs
from distributed_matvec_tpu.obs import trace as obs_trace

from test_operator import build_heisenberg

PACKAGE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "distributed_matvec_tpu")


@pytest.fixture
def clean_trace():
    obs.reset_all()
    yield
    obs.reset_all()


@pytest.fixture
def annotations(monkeypatch):
    """Every ``jax.profiler.TraceAnnotation`` the program opens, as
    ``("open" | "close", name)`` in order."""
    log = []

    class Recorder:
        def __init__(self, name, **kwargs):
            self.name = name

        def __enter__(self):
            log.append(("open", self.name))
            return self

        def __exit__(self, *exc):
            log.append(("close", self.name))
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    return log


# ---------------------------------------------------------------------------
# the span call


def test_span_holds_an_annotation_of_its_name(clean_trace, annotations):
    with obs.span("plan/pack", kind="phase"):
        assert annotations == [("open", "plan/pack")]
        with obs.span("device_wait", kind="phase", at="here"):
            pass
    assert annotations == [("open", "plan/pack"), ("open", "device_wait"),
                           ("close", "device_wait"), ("close", "plan/pack")]
    assert [e["name"] for e in obs.events("span")] == \
        ["device_wait", "plan/pack"]


def test_annotation_closes_on_an_exception(clean_trace, annotations):
    with pytest.raises(RuntimeError):
        with obs.span("lanczos/check", kind="phase"):
            raise RuntimeError("boom")
    assert annotations == [("open", "lanczos/check"),
                           ("close", "lanczos/check")]
    assert obs.open_spans() == []
    assert [e["name"] for e in obs.events("span")] == ["lanczos/check"]


@pytest.mark.parametrize("kind", sorted(obs_trace.ENCLOSING_KINDS))
def test_enclosing_kinds_stay_off_the_profilers_line(clean_trace,
                                                     annotations, kind):
    """A span that encloses a whole run, solve or block would take the
    label of every idle gap under it (benchmark/trace_reduce.py keeps the
    first of equal covers): it is an event, and no annotation."""
    with obs.span("outer", kind=kind):
        with obs.span("inner", kind="phase"):
            pass
    assert annotations == [("open", "inner"), ("close", "inner")]
    assert [e["name"] for e in obs.events("span")] == ["inner", "outer"]


@pytest.mark.parametrize("kind", ["span", "phase", "apply", "chunk"])
def test_leaf_kinds_are_mirrored(clean_trace, annotations, kind):
    with obs.span("leaf", kind=kind):
        pass
    assert annotations == [("open", "leaf"), ("close", "leaf")]


def test_duration_is_monotonic_and_t0_is_wall(clean_trace, monkeypatch):
    """``dur_ms`` ignores the wall clock (an NTP step back mid-span must not
    give a negative or a wild duration); ``t0`` stays on it for the
    cross-rank merge."""
    wall = iter([1000.0, 900.0, 900.0, 900.0])
    real = time.time
    before = real()
    with obs.span("a", kind="phase"):
        monkeypatch.setattr(time, "time", lambda: next(wall, 900.0))
        t_in = time.perf_counter()
        while time.perf_counter() - t_in < 0.005:
            pass
    monkeypatch.setattr(time, "time", real)
    ev = obs.events("span")[-1]
    assert 5.0 <= ev["dur_ms"] < 5000.0
    assert before <= ev["t0"] <= real()


def test_counts_added_to_an_open_span_are_on_its_event(clean_trace):
    with obs.span("lanczos", kind="solve", k=1) as root:
        root.add(steps_run=0, blocks=0)
        with obs.span("iteration", kind="iteration") as it:
            root.add(steps_run=16, blocks=1)
            it.add(steps=16)
        root.add(steps_run=16, blocks=1)
        # a live reader (the stall watchdog) sees the counts so far
        assert obs.open_spans()[0]["steps_run"] == 32
    it_ev, root_ev = obs.events("span")
    assert (root_ev["steps_run"], root_ev["blocks"], root_ev["k"]) == \
        (32, 2, 1)
    assert it_ev["steps"] == 16 and "steps_run" not in it_ev


@pytest.mark.parametrize("switch", ["DMT_OBS", "DMT_TRACE"])
def test_off_is_a_shared_null_context_that_takes_counts(
        clean_trace, annotations, monkeypatch, switch):
    monkeypatch.setenv(switch, "off")
    cm = obs.span("x", kind="phase")
    assert isinstance(cm, nullcontext) and cm is obs.span("y", kind="solve")
    with cm as sp:
        sp.add(steps_run=16)            # kept nowhere
        assert sp is obs_trace.NULL_SPAN and sp.sid is None
    assert annotations == []            # no profiler annotation either
    assert obs.open_spans() == []
    monkeypatch.delenv(switch)
    assert obs.events("span") == []


def test_the_program_opens_an_annotation_in_one_place():
    """``annotate`` is folded into ``span``: nothing else of the package
    reaches for the profiler's annotation."""
    assert not hasattr(obs, "annotate")
    opened, called = [], []
    for folder, _, files in os.walk(PACKAGE):
        for name in files:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(folder, name), encoding="utf-8") as f:
                text = f.read()
            rel = os.path.relpath(os.path.join(folder, name), PACKAGE)
            opened += [rel] * len(re.findall(r"\bTraceAnnotation\(", text))
            called += [rel] * len(re.findall(r"\bannotate\(", text))
    assert opened == [os.path.join("obs", "trace.py")]
    assert called == []


# ---------------------------------------------------------------------------
# the solver's host loop


def _tree(spans):
    """{span_id: [child events]} and the events by id."""
    by_id = {e["span_id"]: e for e in spans}
    children = {}
    for e in spans:
        children.setdefault(e.get("parent_span_id"), []).append(e)
    return children, by_id


def test_lanczos_span_tree_and_counts(clean_trace, monkeypatch):
    """A 16-site solve with one window block stopped by the ω gate at its
    sixth step: ``lanczos > iteration > {lanczos/dispatch, lanczos/wait,
    lanczos/check}``, ``lanczos/start`` and ``lanczos/epilogue`` under the
    root, and the root's counts equal to what the solve did."""
    import importlib

    from distributed_matvec_tpu.parallel.engine import LocalEngine
    from distributed_matvec_tpu.solve import lanczos

    # the package re-exports the function under the module's name
    module = importlib.import_module("distributed_matvec_tpu.solve.lanczos")
    op = build_heisenberg(16, hw=8)
    eng = LocalEngine(op, mode="ell")

    # the omega gate trips at step 5 of the second window block, and only
    # there: host and window program read the same ``_omega_row``
    omega_row = module._omega_row

    def tripping(xp, w, wp, alph, bet, j, eps):
        new, worst = omega_row(xp, w, wp, alph, bet, j, eps)
        return new, xp.where(j == 21, 1.0, xp.minimum(worst, 1e-12))

    monkeypatch.setattr(module, "_omega_row", tripping)
    built = len(obs.events("span"))
    res = lanczos(eng.matvec, op.basis.number_states, k=1, tol=1e-9,
                  max_iters=96, compute_eigenvectors=True)
    assert res.converged
    spans = obs.events("span")[built:]
    children, by_id = _tree(spans)
    root = spans[-1]
    assert (root["name"], root["cat"], root["parent_span_id"]) == \
        ("lanczos", "solve", None)

    under_root = [e["name"] for e in children[root["span_id"]]]
    iterations = [e for e in children[root["span_id"]]
                  if e["name"] == "iteration"]
    # start vector + probe apply, then the Krylov buffer
    assert under_root[:2] == ["lanczos/start", "lanczos/start"]
    assert under_root[2] == "lanczos/dispatch"     # the zero-step warm call
    assert under_root[-1] == "lanczos/epilogue"
    assert set(under_root) == {"lanczos/start", "lanczos/dispatch",
                               "iteration", "lanczos/epilogue"}
    # the probe apply is the one apply span, under lanczos/start
    start, buffer = children[root["span_id"]][:2]
    assert [e["name"] for e in children[start["span_id"]]] == ["apply"]
    assert buffer["span_id"] not in children
    for it in iterations:
        assert [e["name"] for e in children[it["span_id"]]] == \
            ["lanczos/dispatch", "lanczos/wait", "lanczos/check"]
        assert all(e["cat"] == "phase" for e in children[it["span_id"]])

    # the stopped block's head (steps 16..20) is kept; the rest of the same
    # block runs under the full sweep, in the span marked ``redo``
    redone = [it for it in iterations if it.get("redo")]
    assert len(redone) == 1
    i = iterations.index(redone[0])
    assert i == 2 and (iterations[i - 1]["iter"],
                       iterations[i - 1]["steps"]) == (16, 16)
    assert (redone[0]["iter"], redone[0]["steps"]) == (21, 11)
    assert iterations[i + 1]["iter"] == 32
    dispatches = [e for e in spans if e["name"] == "lanczos/dispatch"]
    assert [d["full"] for d in dispatches[:4]] == [True, False, False, True]
    steps_counted = res.num_iters
    assert root["steps_counted"] == steps_counted
    # asked of the programs: the stopped block's 16 and its remainder
    assert sum(d["steps"] for d in dispatches) \
        == steps_counted + redone[0]["steps"]
    # what they report they ran: the crossing step is the one step run
    # and not kept
    assert root["steps_run"] == steps_counted + 1
    assert (root["omega_stops"], root["steps_discarded"]) == (1, 1)
    (trip,) = [e for e in obs.events("solver_health")
               if e.get("check") == "selective_reorth_fallback"]
    assert (trip["step"], trip["iter"]) == (5, 32)
    # a block program's run is an iteration span, the remainder's carries
    # ``redo``, restarts are on the result: the root repeats none of them
    assert not {"blocks", "blocks_redone", "restarts"} & set(root)
    assert len(iterations) == steps_counted // 16 + 1
    assert root["programs_built"] == sum(d["built"] for d in dispatches) == 2
    assert dispatches[0]["built"] and dispatches[0]["steps"] == 0
    assert root["probe_applies"] == 1 and res.restarts == 0
    # children lie inside their parent, on the monotonic clock
    for pid, kids in children.items():
        if pid is not None:
            assert sum(k["dur_ms"] for k in kids) <= by_id[pid]["dur_ms"]


def test_lanczos_restart_spans(clean_trace):
    from distributed_matvec_tpu.parallel.engine import LocalEngine
    from distributed_matvec_tpu.solve import lanczos

    op = build_heisenberg(12, hw=6)
    eng = LocalEngine(op, mode="ell")
    built = len(obs.events("span"))
    res = lanczos(eng.matvec, op.basis.number_states, k=1, tol=1e-10,
                  max_iters=200, max_basis_size=12, min_restart_size=4)
    spans = obs.events("span")[built:]
    root = spans[-1]
    restarts = [e for e in spans if e["name"] == "lanczos/restart"]
    assert res.restarts >= 1
    assert res.restarts == len(restarts)
    assert all(e["parent_span_id"] == root["span_id"] for e in restarts)
    assert root["steps_counted"] == res.num_iters


def test_a_solve_called_without_its_span_counts_nothing(clean_trace):
    """``_lanczos_impl`` outside ``lanczos``'s root span (``root`` left at
    its default) runs the same solve."""
    import importlib

    from distributed_matvec_tpu.parallel.engine import LocalEngine

    module = importlib.import_module("distributed_matvec_tpu.solve.lanczos")
    op = build_heisenberg(10, hw=5)
    eng = LocalEngine(op, mode="ell")
    res = module._lanczos_impl(eng.matvec, op.basis.number_states, k=1,
                               tol=1e-9, max_iters=64)
    assert res.converged
    assert not [e for e in obs.events("span") if e["cat"] == "solve"]


# ---------------------------------------------------------------------------
# the builds


def _self_ms(span, children):
    return span["dur_ms"] - sum(
        k["dur_ms"] for k in children.get(span["span_id"], []))


def test_plan_build_pass_spans_on_four_devices(clean_trace):
    from distributed_matvec_tpu.parallel.distributed import DistributedEngine

    op = build_heisenberg(12, hw=6)
    eng = DistributedEngine(op, n_devices=4, mode="ell", batch_size=64)
    spans = obs.events("span")
    children, by_id = _tree(spans)
    build = [e for e in spans if e["name"] == "engine_init/build_plan"]
    assert len(build) == 1 and build[0]["cat"] == "build"
    passes = [e for e in children[build[0]["span_id"]]
              if e["name"].startswith("plan/")]
    assert [e["name"] for e in passes] == \
        ["plan/count", "plan/resolve", "plan/queries", "plan/pack"]
    waits = {p["name"]: [k for k in children.get(p["span_id"], [])
                         if k["name"] == "device_wait"] for p in passes}
    # both chunk-streamed passes fetch every chunk of every shard; the
    # query lists and the packed tables are uploaded shard by shard
    chunks = 4 * -(-eng.shard_size // 64)
    assert [w["at"] for w in waits["plan/count"]] == \
        ["plan_chunk_fetch"] * chunks
    assert [w["at"] for w in waits["plan/pack"]].count(
        "plan_chunk_fetch") == chunks
    assert {w["at"] for w in waits["plan/queries"]} == {"shard_put"}
    assert "shard_put" in {w["at"] for w in waits["plan/pack"]}
    assert waits["plan/resolve"] == []
    for p in passes:
        assert sum(w["dur_ms"] for w in waits[p["name"]]) <= p["dur_ms"]
        assert _self_ms(p, children) >= 0
    assert sum(p["dur_ms"] for p in passes) <= build[0]["dur_ms"]
    # the engine timer the benchmark reads still brackets the same build
    total = eng.timer.scope_total("build_plan")
    assert total * 1e3 == pytest.approx(build[0]["dur_ms"], rel=0.05,
                                        abs=2.0)
    assert eng.timer.scope_total("build_plan", "compile") >= 0.0


def test_structure_build_pass_spans_on_one_device(clean_trace, annotations):
    from distributed_matvec_tpu.parallel.engine import (LocalEngine,
                                                        clear_program_cache)

    clear_program_cache()               # so that this build compiles
    op = build_heisenberg(12, hw=6, inv=1)
    eng = LocalEngine(op, mode="ell")
    spans = obs.events("span")
    children, by_id = _tree(spans)
    build = [e for e in spans if e["name"] == "engine_init/build_structure"]
    assert len(build) == 1 and build[0]["cat"] == "build"
    # the build encloses its passes: they are on the profiler's line, it
    # is not (it would take the label of every gap under them)
    opened = [name for what, name in annotations if what == "open"]
    assert "engine_init/build_structure" not in opened
    assert "ell/fill" in opened and "device_wait" in opened
    passes = [e["name"] for e in children[build[0]["span_id"]]]
    assert passes[:2] == ["ell/fill", "ell/count"]
    assert set(passes) <= {"ell/fill", "ell/count",
                           "ell/stair_levels"}
    for name, at in [("ell/fill", "ell_fill"),
                     ("ell/count", "ell_count")]:
        span = next(e for e in spans if e["name"] == name)
        waits = [k for k in children[span["span_id"]]
                 if k["name"] == "device_wait"]
        assert [w["at"] for w in waits] == [at]
        assert waits[0]["dur_ms"] <= span["dur_ms"]
    # compile/<program> spans replace annotate("compile/...") and lie under
    # the pass that needed the program
    compiles = [e for e in spans if e["name"].startswith("compile/")]
    assert "compile/ell_fill_chunk" in {e["name"] for e in compiles}
    assert all(by_id[e["parent_span_id"]]["name"].startswith("ell/")
               for e in compiles)
    assert eng.timer.scope_total("build_structure") * 1e3 == pytest.approx(
        build[0]["dur_ms"], rel=0.05, abs=2.0)
    assert eng.timer.scope_total("build_structure", "compile") > 0.0
    assert [e["name"] for e in spans if e["cat"] == "phase"
            and e["parent_span_id"] is None][:1] == ["engine_init/transfer"]


def test_two_pass_build_pass_spans_on_one_device(clean_trace, annotations):
    """The low-memory build's passes, kinds as the one-pass build's: the
    four passes and their ``device_wait``s lie on the profiler's host line,
    the build span does not; its programs compile under the pass that
    needs them; the build span says that the kernels ran twice."""
    from distributed_matvec_tpu.parallel.engine import (LocalEngine,
                                                        clear_program_cache)
    from distributed_matvec_tpu.utils.config import get_config, update_config

    clear_program_cache()
    was = get_config().ell_build_budget_gb
    update_config(ell_build_budget_gb=1e-9)
    try:
        eng = LocalEngine(build_heisenberg(16, hw=8), mode="ell",
                          batch_size=4096)
    finally:
        update_config(ell_build_budget_gb=was)
    spans = obs.events("span")
    children, by_id = _tree(spans)
    (build,) = [e for e in spans
                if e["name"] == "engine_init/build_structure"]
    opened = [name for what, name in annotations if what == "open"]
    assert "engine_init/build_structure" not in opened
    passes = ["ell/count_rows", "ell/row_order", "ell/pack", "ell/cut"]
    assert [e["name"] for e in children[build["span_id"]]] == passes
    assert [n for n in opened if n.startswith("ell/")] == passes
    ats = {p: {k["at"] for k in children[next(
        e for e in spans if e["name"] == p)["span_id"]]
        if k["name"] == "device_wait"} for p in passes}
    assert ats == {"ell/count_rows": {"ell_count_rows"},
                   "ell/row_order": {"ell_row_order"},
                   "ell/pack": {"ell_pack"}, "ell/cut": {"ell_cut"}}
    under = {e["name"]: by_id[e["parent_span_id"]]["name"] for e in spans
             if e["name"].startswith("compile/")}
    assert under["compile/count_row_nnz"] == "ell/count_rows"
    assert under["compile/ell_lowmem_pack"] == "ell/pack"
    assert under["compile/ell_stair_order"] == "ell/row_order"
    assert (build["build_passes"], build["table_bytes"]) == \
        (2, 16 * eng.n_padded)
    assert eng.timer.scope_total("build_structure") * 1e3 == pytest.approx(
        build["dur_ms"], rel=0.05, abs=2.0)


@pytest.mark.parametrize("nb, ranges", [(1, 1), (3, 1), (3, 3)], ids=[
    "whole", "three_row_blocks", "three_table_ranges"])
def test_build_span_counts_how_far_the_staircase_engages(clean_trace, nb,
                                                         ranges, monkeypatch):
    """``gather_slots``, ``live_entries`` and ``levels`` on the build span
    and in the ``engine_init`` event, and the benchmark's reader of their
    ratio (``benchmark/metrics/gather_fill_pct.py``): 16-site ring, 12,870
    rows, one entry a domain wall.  ``row_blocks`` and ``gather_pieces``
    beside them say how the rows are cut (``row_blocks`` 1: not at all);
    that cut moves none of the other counts.  ``table_ranges``,
    ``near_slots`` and ``far_slots`` say whether the gather table is cut:
    1, 0 and every table slot where it is not; where it is, a near and a
    far staircase a range, each with its own un-permute rows."""
    import importlib.util
    from types import SimpleNamespace

    from distributed_matvec_tpu.parallel import engine
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    if ranges > 1:  # ... that leaves the rows whole and cuts the table
        monkeypatch.setattr(engine, "GATHER_VMEM_BYTES", 5 * 1024 * 36)
    elif nb > 1:    # the rule's VMEM number that cuts 12,870 rows in three
        monkeypatch.setattr(engine, "GATHER_VMEM_BYTES",
                            12_870 * 16 + 5 * 1024 * 20)
    eng = LocalEngine(build_heisenberg(16, hw=8), mode="ell")
    assert eng._ell_pos_of is not None
    build = [e for e in obs.events("span")
             if e["name"] == "engine_init/build_structure"]
    assert len(build) == 1 and "ell/stair_levels" in {
        e["name"] for e in obs.events("span")
        if e["parent_span_id"] == build[0]["span_id"]}
    counts = {k: build[0][k] for k in (
        "gather_slots", "live_entries", "levels", "terms", "widest_row",
        "row_blocks", "gather_pieces", "build_passes", "table_bytes",
        "table_ranges", "near_slots", "far_slots")}
    assert counts == eng._ell_counts
    # one run of the kernels; ``x`` as a gather table: 16 B a padded row,
    # the number the block rule holds against the chip's VMEM
    assert (counts["build_passes"], counts["table_bytes"]) == \
        (1, 16 * eng.n_padded)
    assert counts["terms"] == 16
    assert counts["live_entries"] == 109_824    # 16 bonds x 2 x C(14, 7)
    assert (counts["row_blocks"], counts["table_ranges"]) == (nb, ranges)
    slots = counts["near_slots"] + counts["far_slots"]
    if ranges == 1:
        assert (counts["gather_slots"], counts["levels"]) == (133_702, 5)
        assert counts["gather_pieces"] == {1: 6, 3: 12}[nb]
        assert (counts["widest_row"], counts["near_slots"], slots) == \
            (16, 0, 133_702 - 12_870)
    else:           # two un-permute rows a row, most entries near
        assert counts["gather_slots"] == slots + 2 * 12_870
        assert counts["gather_pieces"] == counts["levels"] + 2 * ranges
        assert counts["far_slots"] < counts["near_slots"] < 109_824 * 1.1
        assert 16 <= counts["widest_row"] <= 2 * 16
    init = obs.events("engine_init")[-1]
    assert {k: init[k] for k in counts} == counts

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "gather_fill_pct",
        os.path.join(root, "benchmark", "metrics", "gather_fill_pct.py"))
    reader = importlib.util.module_from_spec(spec)
    import sys
    sys.path.insert(0, root)
    try:
        spec.loader.exec_module(reader)
    finally:
        sys.path.remove(root)
    run = SimpleNamespace(
        config={"engine": {"kind": "local"}},
        timers={"structure_build_s":
                eng.timer.scope_total("build_structure")})
    assert reader.read(run) == pytest.approx(
        100.0 * counts["live_entries"] / counts["gather_slots"])
    # a build span without the counts (the parent commit's) reads nothing
    for key in counts:
        del build[0][key]
    assert reader.read(run) is None


def test_an_eager_apply_is_one_span_and_one_annotation(clean_trace,
                                                       annotations, rng):
    from distributed_matvec_tpu.parallel.engine import LocalEngine

    op = build_heisenberg(10, hw=5)
    eng = LocalEngine(op, mode="ell")
    x = rng.standard_normal(op.basis.number_states)
    eng.matvec(x)                       # validates, may probe
    del annotations[:]
    before = len(obs.events("span"))
    eng.matvec(x)
    assert annotations == [("open", "apply"), ("close", "apply")]
    assert [e["name"] for e in obs.events("span")[before:]] == ["apply"]
