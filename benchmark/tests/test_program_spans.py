"""The readers of the program's own spans (PR 26), on a synthetic trace and
a synthetic copy of a run's events (``run.events``, which
``benchmark/system.py`` hands over since PR 34), and once against the
program itself at a toy size:
the names the readers go by are the names the program opens, and what the
program puts on the profiler's host line is what labels an idle gap best.
Times are made up; nothing here is a device number."""

from types import SimpleNamespace

import pytest

from benchmark import harness, program_spans as P, trace_reduce as T

MS = 1_000_000          # the trace's clock counts nanoseconds


def ms(*pairs):
    return [(name, a * MS, b * MS) for name, a, b in pairs]


# One solve of 32 counted iterations: probe apply, the zero-step warm call,
# a window block whose program is built (107 ms of dispatch), a cached one,
# the block redone with the full sweep (35 ms to build it), the epilogue.
OPS = [("%probe = f32[8] fusion(f32[8] %x)", 10, 20),
       ("%warm = f32[8] fusion(f32[8] %x)", 30, 30.1),
       ("%w1 = f32[8] fusion(f32[8] %x)", 150, 400),
       ("%w2 = f32[8] fusion(f32[8] %x)", 410, 650),
       ("%full = f32[8] fusion(f32[8] %x)", 700, 900),
       ("%ritz = f32[8] fusion(f32[8] %x)", 905, 910)]
MODULES = [("jit_apply_fn(1)", 10, 20), ("jit_run_block(2)", 30, 30.1),
           ("jit_run_window(3)", 150, 400), ("jit_run_window(3)", 410, 650),
           ("jit_run_block(2)", 700, 900), ("jit__combine_rows(4)", 905, 910)]
HOST = ms(("bench/solve", 0, 1000),
          ("lanczos/start", 1, 28), ("apply", 5, 21),
          ("lanczos/dispatch", 28, 32),
          ("lanczos/dispatch", 33, 140), ("PjitFunction(run_window)", 34, 139),
          ("lanczos/wait", 140, 400), ("lanczos/check", 400, 404),
          ("lanczos/dispatch", 404, 405),
          ("lanczos/wait", 405, 650), ("lanczos/check", 650, 655),
          ("lanczos/dispatch", 655, 690), ("PjitFunction(run_block)", 656, 689),
          ("lanczos/wait", 690, 900), ("lanczos/check", 900, 903),
          ("lanczos/epilogue", 903, 911), ("PjitFunction(_combine_rows)",
                                           903.5, 905))


def summary(host=HOST, ops=OPS, modules=MODULES):
    lo, hi = 0, 1000 * MS
    dev = T.DeviceTrace(
        0, [(n, a * MS, (b - a) * MS) for n, a, b in ops],
        [(n, a * MS, (b - a) * MS) for n, a, b in modules], lo, hi)
    return T.TraceSummary(lo, hi, [dev], host, marks=[(lo, hi)])


def run_with(trace=None, engine="distributed", build=(), events=(),
             lost=False, **window):
    """A run whose set-up emitted ``build`` and whose window ``events``."""
    return SimpleNamespace(trace=trace, window=window,
                           config={"engine": {"kind": engine}},
                           events={"build": list(build),
                                   "window": list(events), "lost": lost})


def reader(name):
    return harness.load_reader(name)


# ---------------------------------------------------------------------------
# times: the host line's spans against the device's busy intervals


def test_idle_under_a_span_is_the_span_less_the_busy_time_inside():
    s = summary()
    assert P.idle_ns(s.fullest, 1 * MS, 28 * MS) == 17 * MS
    assert P.idle_ns(s.fullest, 150 * MS, 400 * MS) == 0
    assert P.idle_ns(s.fullest, 28 * MS, 28 * MS) == 0
    assert P.idle_under(s, ("lanczos/epilogue",)) == pytest.approx(3e-3)
    assert P.idle_under(s, ("no/such/span",)) is None


def test_dispatch_idle_takes_the_wait_up_to_the_programs_first_operation():
    # under dispatch: 3.9 + 107 + 1 + 35; under wait until the block
    # program starts: 10 + 5 + 10
    assert P.dispatch_idle(summary()) == pytest.approx(171.9e-3)
    value = reader("solver_dispatch_idle_ms")(
        run_with(summary(), iterations=32))
    assert value == pytest.approx(171.9 / 32)


def test_a_wait_whose_program_never_ran_is_idle_to_its_end():
    s = summary(ops=OPS[:2], modules=MODULES[:2],
                host=ms(("lanczos/dispatch", 33, 140),
                        ("lanczos/wait", 140, 400)))
    assert P.dispatch_idle(s) == pytest.approx((107 + 260) * 1e-3)


def test_check_idle_is_start_check_restart_and_epilogue():
    # start 17 (the probe apply runs for 10 of its 27), checks 4 + 5 + 3,
    # epilogue 3 (the combination runs for 5 of its 8)
    value = reader("solver_check_idle_ms")(run_with(summary(), iterations=32))
    assert value == pytest.approx(32 / 32)
    with_restart = summary(host=HOST + ms(("lanczos/restart", 911, 913)))
    assert reader("solver_check_idle_ms")(
        run_with(with_restart, iterations=32)) == pytest.approx(34 / 32)


def test_the_two_idle_metrics_account_for_the_windows_idle_time():
    s = summary()
    idle = s.window_s - s.fullest.busy_s
    named = P.dispatch_idle(s) + P.idle_under(s, P.CHECKS)
    # what is left lies outside the solve: before lanczos/start, after the
    # epilogue (89 ms of the harness's own made-up time), and 1 ms between
    # spans
    assert idle - named == pytest.approx((1 + 89 + 1) * 1e-3)


def test_spans_are_clipped_to_the_window():
    s = summary(host=ms(("lanczos/dispatch", -50, 5),
                        ("lanczos/check", 990, 1200)))
    assert P.host_spans(s, ("lanczos/dispatch",)) == [(0, 5 * MS)]
    assert P.idle_under(s, P.CHECKS) == pytest.approx(10e-3)


@pytest.mark.parametrize("name", ["solver_dispatch_idle_ms",
                                  "solver_check_idle_ms"])
def test_time_readers_find_nothing_on_a_host_line_without_the_spans(name):
    """The parent commit's trace, or any program without these spans."""
    bare = summary(host=ms(("bench/solve", 0, 1000),
                           ("PjitFunction(run_window)", 34, 139)))
    assert reader(name)(run_with(bare, iterations=32)) is None


# ---------------------------------------------------------------------------
# gap labels: what the program mirrors into the profiler


def test_idle_gaps_are_labelled_by_the_innermost_program_span():
    labels = {}
    for secs, label in summary().gaps():
        name = label.split(" [")[0]
        labels[name] = labels.get(name, 0.0) + secs
    # the 5 ms before the second window block starts are the wait's: the
    # host was already blocked when the device began
    assert set(labels) == {"lanczos/start", "lanczos/dispatch",
                           "lanczos/wait", "lanczos/check",
                           "lanczos/epilogue"}
    # the gap of 119.9 ms around the built window program's dispatch, and
    # the 50 ms around the full program's, not PjitFunction(run_...)
    assert labels["lanczos/dispatch"] == pytest.approx(169.9e-3)


def test_an_enclosing_span_on_the_host_line_would_take_every_label():
    """Why ``obs/trace.py`` keeps the run / solve / iteration spans off the
    profiler's line: ``TraceSummary.gaps`` keeps the first of equal covers,
    and an enclosing span starts first and covers every gap under it."""
    host = sorted(HOST + ms(("lanczos", 0.5, 912), ("iteration", 32.5, 404),
                            ("iteration", 404, 655),
                            ("iteration", 655, 903)), key=lambda h: h[1])
    labels = {label.split(" [")[0] for _, label in summary(host=host).gaps()}
    assert labels == {"lanczos"}


def test_a_build_span_on_the_host_line_would_take_its_passes_labels():
    """Why ``engine_init/build_*`` has an enclosing kind too (a session
    that covers set-up): its passes label the gaps of a build, each with
    the ``device_wait`` under it where the device works; the build span
    itself, on the line, would take all of them."""
    ops = [("%count = f32[8] fusion(f32[8] %x)", 10, 20),
           ("%count = f32[8] fusion(f32[8] %x)", 60, 70),
           ("%pack = f32[8] fusion(f32[8] %x)", 120, 130)]
    passes = ms(("plan/count", 0, 100), ("device_wait", 8, 21),
                ("device_wait", 58, 71), ("plan/resolve", 100, 104),
                ("plan/pack", 104, 1000), ("device_wait", 118, 131))

    def labels(host):
        return {label.split(" [")[0]
                for _, label in summary(host=host, ops=ops,
                                        modules=[]).gaps()}

    assert labels(passes) == {"plan/count", "plan/pack"}
    build = ms(("engine_init/build_plan", 0, 1000))
    assert labels(build + passes) == {"engine_init/build_plan"}


def test_the_program_mirrors_the_names_the_readers_go_by(monkeypatch,
                                                         tmp_path):
    """A toy solve and a toy plan build under a recorder in the place of
    ``jax.profiler.TraceAnnotation``: the leaf spans are opened under the
    readers' names, the enclosing ones not at all."""
    import jax

    from conftest import ring_yaml

    opened = []

    class Recorder:
        def __init__(self, name, **kwargs):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    from benchmark.system import System

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    system = System({"model": ring_yaml(tmp_path / "ring.yaml", 12),
                     "engine": {"kind": "local", "mode": "ell"}})
    system.start()
    system.enumerate()
    system.build_engine()
    system.open_window()
    system.solve({"k": 1, "tol": 1e-8, "max_iters": 64,
                  "eigenvectors": True})
    events = system.close_window()
    assert {P.DISPATCH, P.WAIT, "lanczos/start", "lanczos/check",
            "lanczos/epilogue", "apply", "ell/fill", P.DEVICE_WAIT} \
        <= set(opened)
    assert set(P.CHECKS) - set(opened) == {"lanczos/restart"}
    assert not {"lanczos", "iteration", *P.BUILDS.values()} & set(opened)
    run = run_with(engine="local", build=events["build"],
                   events=events["window"], solves=1)
    solves = P.window_solves(run)
    assert solves and solves[0]["steps_counted"] >= 16
    # this run's build and nothing older: what the process's ring held
    # before build_engine is not in the snapshot
    assert not events["lost"]
    assert [e["kind"] for e in events["build"]].count("engine_init") == 1
    assert not [e for e in events["window"]
                if e.get("name") == P.BUILDS["local"]]
    assert 0 < P.build_host_seconds(run) \
        <= system.timers()["structure_build_s"] * 1.05 + 0.002
    counts = system.engine_counts()
    assert counts["pair"] is False and counts["n_states"] == 35
    assert {"table_ranges", "near_slots", "far_slots", "row_blocks",
            "scanned_columns"} <= set(counts)
    assert not {"seq", "ts", "kind", "span_id"} & set(counts)


# ---------------------------------------------------------------------------
# counts: the program's own span store


def solve_span(sid, **counts):
    return {"kind": "span", "name": "lanczos", "cat": "solve",
            "span_id": sid, "parent_span_id": None, "dur_ms": 1.0, **counts}


WARM_UP = solve_span("1", steps_counted=16, steps_run=16, probe_applies=1,
                     programs_built=2)
FIRST = solve_span("2", steps_counted=64, steps_run=80, probe_applies=1,
                   programs_built=2)
SECOND = solve_span("3", steps_counted=80, steps_run=96, probe_applies=1,
                    programs_built=3)
OTHER = {"kind": "span", "name": "lanczos_block", "cat": "solve",
         "span_id": "9", "parent_span_id": None, "dur_ms": 1.0}


def test_a_window_of_two_solves_after_a_warm_up_solve():
    store = [WARM_UP, OTHER, FIRST, {"kind": "span", "name": "apply",
                                     "cat": "apply", "span_id": "7"}, SECOND]
    run = run_with(events=store, solves=2, iterations=144)
    assert P.window_solves(run) == [FIRST, SECOND]
    assert reader("applies_per_iteration")(run) == pytest.approx(
        (80 + 1 + 96 + 1) / 144)
    assert reader("block_programs_built.solve")(run) == 2.5
    one = run_with(events=store, solves=1, iterations=80)
    assert reader("applies_per_iteration")(one) == pytest.approx(97 / 80)
    # a solve of the set-up (the warm-up's) is not the window's
    setup = run_with(build=store, events=[SECOND], solves=2, iterations=144)
    assert P.window_solves(setup) is None


@pytest.mark.parametrize("store", [
    [], [WARM_UP],                                  # fewer than the window's
    [solve_span("1"), solve_span("2")],             # the parent: no counts
], ids=["empty", "too_few", "no_counts"])
def test_count_readers_find_nothing(store):
    run = run_with(events=store, solves=2, iterations=128)
    assert reader("applies_per_iteration")(run) is None
    assert reader("block_programs_built.solve")(run) is None
    assert reader("applies_per_iteration")(
        run_with(events=store, iterations=16)) is None


def phase(sid, parent, name, dur_ms, seq=0):
    return {"kind": "span", "name": name, "cat": "phase", "span_id": sid,
            "parent_span_id": parent, "dur_ms": dur_ms, "seq": seq}


BUILD_STORE = [phase("a", None, "device_wait", 5.0),     # not under a build
               phase("c", "b", "device_wait", 100.0),
               phase("b", "2", "plan/count", 400.0),
               phase("e", "d", "device_wait", 50.0),
               phase("f", "d", "compile/dist_gather_chunk", 70.0),
               phase("d", "2", "plan/pack", 500.0),
               phase("2", None, "engine_init/build_plan", 1000.0)]
ANOTHER = phase("1", None, "engine_init/build_plan", 900.0)


def test_the_builds_host_time_is_its_span_less_the_device_waits_under_it():
    """This run's build is the one emitted while its engine was built."""
    read = reader("structure_build_host_s")
    assert read(run_with(build=BUILD_STORE)) == pytest.approx(0.850)
    assert read(run_with(build=[ANOTHER])) == pytest.approx(0.900)
    # a build span among the window's events is not the set-up's
    assert read(run_with(events=BUILD_STORE)) is None
    # a program without the span (the parent commit): nothing, no error
    assert read(run_with(build=BUILD_STORE, engine="local")) is None
    assert read(run_with(build=BUILD_STORE[:1])) is None


@pytest.mark.parametrize("build, lost, message", [
    (BUILD_STORE + [ANOTHER], False, "which is the build"),   # two builds
    (BUILD_STORE[:1], True, "has dropped some of them"),  # the ring overflowed
], ids=["two_builds", "overflowed"])
def test_a_build_that_cannot_be_told_is_an_error(build, lost, message):
    with pytest.raises(RuntimeError, match=message):
        reader("structure_build_host_s")(run_with(build=build, lost=lost))


def test_solves_lost_from_an_overflowed_ring_are_an_error():
    store = [dict(FIRST, seq=66000)]
    assert P.window_solves(run_with(events=store, lost=True, solves=1)) \
        == store
    with pytest.raises(RuntimeError, match="1 of the window's 2 solves"):
        reader("applies_per_iteration")(
            run_with(events=store, lost=True, solves=2, iterations=144))
    assert reader("applies_per_iteration")(
        run_with(events=store, solves=2, iterations=144)) is None


def test_the_snapshot_holds_what_came_after_its_mark(monkeypatch):
    """``benchmark/system.py`` copies out of the program's ring what was
    emitted since its mark: by the identity of the marked event, so a ring
    that was cleared in between (tests do) or one that has let the mark go
    is told apart from one that holds it."""
    from benchmark import system as system_module

    ring = [{"seq": i, "kind": "span"} for i in range(5)]
    monkeypatch.setattr(system_module, "program_ring", lambda: list(ring))
    system = system_module.System({"engine": {"kind": "local"}})
    system.open_window()
    assert system.close_window()["window"] == []
    ring += [{"seq": 5, "kind": "span"}, {"seq": 6, "kind": "engine_init"}]
    assert [e["seq"] for e in system.close_window()["window"]] == [5, 6]
    assert system.close_window()["lost"] is False
    del ring[:6]                    # the mark has left the ring
    events = system.close_window()
    assert [e["seq"] for e in events["window"]] == [6] and events["lost"]
    # an empty ring at the mark: everything after it is the run's
    kept, ring[:] = list(ring), []
    fresh = system_module.System({"engine": {"kind": "local"}})
    fresh.open_window()
    ring[:] = kept
    assert fresh.close_window() == {"build": [], "window": kept,
                                    "lost": False}


def test_a_run_that_was_handed_no_events_reads_the_whole_ring(monkeypatch):
    """A reader called outside the harness (``tests/test_span_surface.py``
    calls ``gather_fill_pct`` with the engine it has just built): the whole
    ring, through ``benchmark/system.py``."""
    from benchmark import system as system_module

    build = dict(phase("2", None, "engine_init/build_plan", 1000.0, seq=3),
                 gather_slots=200, live_entries=150)
    monkeypatch.setattr(system_module, "program_ring", lambda: [build])
    run = SimpleNamespace(config={"engine": {"kind": "distributed"}})
    assert P.run_events(run) == {"build": [build], "window": [build],
                                 "lost": True}
    assert reader("gather_fill_pct")(run) == 75.0
    assert P.run_events(run_with(build=[build]))["lost"] is False


def test_the_new_entries_name_their_cells():
    """PR 26's five entries, by name: wherever later PRs put theirs."""
    bench = harness.load_benchmark()
    solves = [c["name"] for c in bench["workloads"]
              if c["traffic"].startswith("ground_state")]
    assert solves[:2] == ["chain_32_symm.ground_state",
                          "chain_32_symm_x4.ground_state"]
    for name in ("solver_dispatch_idle_ms", "solver_check_idle_ms",
                 "applies_per_iteration", "block_programs_built.solve",
                 "structure_build_host_s"):
        m = harness.find(bench["per_layer"], name, "metric")
        assert callable(reader(name))
        # the one-chip builds are device-bound: the span less its waits is
        # no host time there (PERF.md section 5), so the metric is not listed
        want = solves[1:2] if name == "structure_build_host_s" else solves
        assert m["workloads"] == want
        assert m["source"] in ("program_span", "program_counter")


# ---------------------------------------------------------------------------
# two recorded traces that hold the spans and the scopes (TPU v5 lite, PR 26,
# cold compile cache: a 20-site ring, one eager apply, a window block of 8
# steps, a full-sweep block of 4, on one chip and on four).  Cut to size
# after recording: the plane ``/host:metadata`` (the programs' HLO protos,
# 4.6 MB) is dropped, and of the four chips' planes only ``/device:TPU:0``
# is kept; every other plane is as the profiler wrote it.

LANCZOS_SCOPES = ["lanczos/apply", "lanczos/reorth", "lanczos/recurrence",
                  "lanczos/store"]
APPLY_SCOPES = ["apply/split", "apply/diag", "apply/terms", "apply/tail"]


@pytest.mark.parametrize("name, scopes", [
    ("ring20_local_scopes", APPLY_SCOPES + LANCZOS_SCOPES),
    ("ring20_distributed_scopes",
     APPLY_SCOPES + LANCZOS_SCOPES + ["apply/pack", "apply/exchange"]),
])
def test_recorded_traces_hold_the_spans_and_the_scopes(recorded_trace, name,
                                                       scopes):
    path = recorded_trace(name)
    s = T.reduce_file(path)
    on_the_line = {n for n, _, _ in s.host}
    assert {P.DISPATCH, P.WAIT, "lanczos/start", "lanczos/check",
            "apply"} <= on_the_line
    assert not {"lanczos", "iteration"} & on_the_line
    # at this size the device has next to nothing to do: the window is the
    # three block programs' builds, and the readers say so
    idle = s.window_s - s.fullest.busy_s
    dispatch, checks = P.dispatch_idle(s), P.idle_under(s, P.CHECKS)
    assert 0.9 * idle < dispatch + checks <= idle
    assert dispatch > 10 * checks
    top = max(s.gaps())[1]
    assert top.startswith("lanczos/dispatch [") and "jit_run_" in top
    # the scope names ride in the event metadata of the device's operations
    # (stat ``tf_op``: "jit(run_window)/.../lanczos/apply/apply/terms/gather"),
    # which ``jax.profiler.ProfileData`` does not hand over: read as bytes
    with open(path, "rb") as f:
        raw = f.read()
    assert b"tf_op" in raw
    for scope in scopes:
        assert f"/{scope}/".encode() in raw, scope
    assert (b"/apply/pack/" in raw) == ("apply/pack" in scopes)
