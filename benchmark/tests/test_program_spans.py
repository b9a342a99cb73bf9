"""The readers of the program's own spans (PR 26), on a synthetic trace and
a synthetic span store, and once against the program itself at a toy size:
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


def run_with(trace=None, engine="distributed", build_s=1.0, **window):
    return SimpleNamespace(trace=trace, window=window,
                           config={"engine": {"kind": engine}},
                           timers={"structure_build_s": build_s})


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
    system.solve({"k": 1, "tol": 1e-8, "max_iters": 64,
                  "eigenvectors": True})
    assert {P.DISPATCH, P.WAIT, "lanczos/start", "lanczos/check",
            "lanczos/epilogue", "apply", "ell/fill", P.DEVICE_WAIT} \
        <= set(opened)
    assert set(P.CHECKS) - set(opened) == {"lanczos/restart"}
    assert not {"lanczos", "iteration", *P.BUILDS.values()} & set(opened)
    solves = P.window_solves(run_with(solves=1))
    assert solves and solves[0]["steps_counted"] >= 16
    # the build is found by what system.py read off the engine's timer
    run = run_with(engine="local",
                   build_s=system.timers()["structure_build_s"])
    assert 0 < P.build_host_seconds(run) <= run.timers["structure_build_s"]


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


def test_a_window_of_two_solves_after_a_warm_up_solve(monkeypatch):
    store = [WARM_UP, OTHER, FIRST, {"kind": "span", "name": "apply",
                                     "cat": "apply", "span_id": "7"}, SECOND]
    monkeypatch.setattr(P, "program_events", lambda: store)
    run = run_with(solves=2, iterations=144)
    assert P.window_solves(run) == [FIRST, SECOND]
    assert reader("applies_per_iteration")(run) == pytest.approx(
        (80 + 1 + 96 + 1) / 144)
    assert reader("block_programs_built.solve")(run) == 2.5
    one = run_with(solves=1, iterations=80)
    assert reader("applies_per_iteration")(one) == pytest.approx(97 / 80)


@pytest.mark.parametrize("store", [
    [], [WARM_UP],                                  # fewer than the window's
    [solve_span("1"), solve_span("2")],             # the parent: no counts
], ids=["empty", "too_few", "no_counts"])
def test_count_readers_find_nothing(monkeypatch, store):
    monkeypatch.setattr(P, "program_events", lambda: store)
    run = run_with(solves=2, iterations=128)
    assert reader("applies_per_iteration")(run) is None
    assert reader("block_programs_built.solve")(run) is None
    assert reader("applies_per_iteration")(run_with(iterations=16)) is None


def phase(sid, parent, name, dur_ms, seq=0):
    return {"kind": "span", "name": name, "cat": "phase", "span_id": sid,
            "parent_span_id": parent, "dur_ms": dur_ms, "seq": seq}


BUILD_STORE = [phase("1", None, "engine_init/build_plan", 900.0),  # another
               phase("a", None, "device_wait", 5.0),     # not under a build
               phase("c", "b", "device_wait", 100.0),
               phase("b", "2", "plan/count", 400.0),
               phase("e", "d", "device_wait", 50.0),
               phase("f", "d", "compile/dist_gather_chunk", 70.0),
               phase("d", "2", "plan/pack", 500.0),
               phase("2", None, "engine_init/build_plan", 1000.0)]


def test_the_builds_host_time_is_its_span_less_the_device_waits_under_it(
        monkeypatch):
    """This run's build is the one the engine's timer read at set-up
    (1.0 s), not the last of the store and not another engine's."""
    monkeypatch.setattr(P, "program_events", lambda: BUILD_STORE)
    read = reader("structure_build_host_s")
    assert read(run_with(build_s=1.0)) == pytest.approx(0.850)
    assert read(run_with(build_s=0.9)) == pytest.approx(0.900)
    # a program without the span (the parent commit): nothing, no error
    assert read(run_with(engine="local")) is None
    monkeypatch.setattr(P, "program_events", lambda: BUILD_STORE[1:2])
    assert read(run_with()) is None


@pytest.mark.parametrize("store, build_s, message", [
    (BUILD_STORE, 2.0, "none, or more than one"),     # no build so long
    (BUILD_STORE + [phase("3", None, "engine_init/build_plan", 1001.0)],
     1.0, "none, or more than one"),                  # two candidates
    ([phase("a", None, "device_wait", 5.0, seq=70000)], 1.0,
     "dropped 70000 older events"),                   # the ring overflowed
], ids=["no_match", "two_match", "overflowed"])
def test_a_build_that_is_not_this_runs_is_an_error(monkeypatch, store,
                                                   build_s, message):
    monkeypatch.setattr(P, "program_events", lambda: store)
    with pytest.raises(RuntimeError, match=message):
        reader("structure_build_host_s")(run_with(build_s=build_s))


def test_solves_lost_from_an_overflowed_store_are_an_error(monkeypatch):
    store = [dict(FIRST, seq=66000)]
    monkeypatch.setattr(P, "program_events", lambda: store)
    assert P.window_solves(run_with(solves=1)) == store
    with pytest.raises(RuntimeError, match="1 of the window's 2 solves"):
        reader("applies_per_iteration")(run_with(solves=2, iterations=144))


def test_the_new_entries_name_their_cells():
    bench = harness.load_benchmark()
    added = {m["name"]: m for m in bench["per_layer"][-5:]}
    assert list(added) == [
        "solver_dispatch_idle_ms", "solver_check_idle_ms",
        "applies_per_iteration", "block_programs_built.solve",
        "structure_build_host_s"]
    solves = ["chain_32_symm.ground_state", "chain_32_symm_x4.ground_state"]
    for name, m in added.items():
        assert callable(reader(name))
        # the one-chip builds are device-bound: the span less its waits is
        # no host time there (PERF.md section 5), so the metric is not listed
        want = solves[1:] if name == "structure_build_host_s" else solves
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
