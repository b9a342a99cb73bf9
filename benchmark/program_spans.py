"""The program's own spans, as the per-layer metrics of PR 26 read them.

Two sources, both written by ``distributed_matvec_tpu/obs/trace.py::span``:

* the profiler's host line (``run.trace.host``): a span of a leaf kind holds a
  ``TraceAnnotation`` of its name, so it lies on the clock of the device's
  operations.  Times come from here: the idle time of the fullest device
  (``run.trace.fullest.busy``) under the spans of a name.
* the program's own events of this run (``run.events``): what
  ``benchmark/system.py`` copied out of the program's in-memory ring right
  after the engine was built (``build``) and right after the window
  (``window``).  One ``span`` event a closed span, with its parent, its
  monotonic duration and the counts the program added while it was open.
  Counts come from here, and the times of set-up, which runs before the
  profiler starts.  Nothing here imports the program: what another run of
  the same process left in its ring is not in the snapshot (a run that was
  handed no events at all gets the whole ring, through ``system.py``).

Every function returns ``None`` (or an empty list) where it finds nothing: a
program without these spans, or a CPU rehearsal whose trace has no device.
Where the snapshot says that the ring let some of this run's events go (it
keeps the newest 65,536) and the reader does not find what it needs, or the
snapshot holds more than one build, the reader raises: a wrong number is
worse than none.
"""

import re

PROGRAM = re.compile(r"jit_run_(window|block)")
DISPATCH, WAIT = "lanczos/dispatch", "lanczos/wait"
CHECKS = ("lanczos/start", "lanczos/check", "lanczos/restart",
          "lanczos/epilogue")
BUILDS = {"local": "engine_init/build_structure",
          "distributed": "engine_init/build_plan"}
DEVICE_WAIT = "device_wait"
COUNTS = ("steps_counted", "steps_run", "probe_applies", "programs_built")


def host_spans(trace, names):
    """[(start, end)] ns of the host line's spans named in ``names``,
    clipped to the window."""
    return [(max(s, trace.lo), min(e, trace.hi)) for n, s, e in trace.host
            if n in names and e > trace.lo and s < trace.hi]


def idle_ns(device, a, b):
    """Nanoseconds of [a, b) in which no operation ran on ``device``."""
    if b <= a:
        return 0.0
    busy = sum(min(y, b) - max(x, a) for x, y in device.busy
               if y > a and x < b)
    return (b - a) - busy


def idle_under(trace, names):
    """Idle seconds of the fullest device under the host spans named in
    ``names``; ``None`` where the host line has none."""
    spans = host_spans(trace, names)
    if not spans:
        return None
    return sum(idle_ns(trace.fullest, a, b) for a, b in spans) / 1e9


def dispatch_idle(trace):
    """Idle seconds of the fullest device under the ``lanczos/dispatch``
    spans, and under each ``lanczos/wait`` until the first operation of the
    block program its dispatch sent (the ``XLA Modules`` run that starts
    after the dispatch began)."""
    dispatches = host_spans(trace, (DISPATCH,))
    if not dispatches:
        return None
    dev = trace.fullest
    total = sum(idle_ns(dev, a, b) for a, b in dispatches)
    runs = sorted(s for n, s, _ in dev.modules if PROGRAM.search(n))
    for a, b in host_spans(trace, (WAIT,)):
        sent = max((d for d, _ in dispatches if d <= a), default=a)
        first = next((s for s in runs if s >= sent), b)
        total += idle_ns(dev, a, min(b, max(a, first)))
    return total / 1e9


def run_events(run):
    """``run.events``; for a run that was handed none, the whole of the
    program's ring through ``benchmark/system.py`` (which alone imports the
    program)."""
    events = getattr(run, "events", None)
    if events is None:
        from .system import whole_ring

        events = whole_ring()
    return events


def span_events(run, part):
    """The program's closed spans of this run's ``build`` or ``window``,
    oldest first."""
    return [e for e in run_events(run)[part] if e.get("kind") == "span"]


def window_solves(run):
    """The root ``lanczos`` spans of the window's solves: the last
    ``run.window["solves"]`` of the window's events.  ``None`` where there
    are fewer, or where they carry no counts."""
    solves = [e for e in span_events(run, "window")
              if e.get("name") == "lanczos" and e.get("cat") == "solve"]
    n = int(run.window.get("solves") or 0)
    if n and len(solves) < n and run_events(run)["lost"]:
        raise RuntimeError(
            f"the snapshot of the program's events holds {len(solves)} of "
            f"the window's {n} solves, and the program's ring has dropped "
            "events of this run")
    if not n or len(solves) < n:
        return None
    solves = solves[-n:]
    if not all(key in e for e in solves for key in COUNTS):
        return None
    return solves


def build_span(run):
    """The span of the build that this run's set-up made: the one of the
    engine's name among the events emitted while the engine was built.
    ``None`` for a program that opens no such span."""
    name = BUILDS.get(run.config["engine"]["kind"])
    builds = [e for e in span_events(run, "build") if e.get("name") == name]
    if not builds:
        if run_events(run)["lost"]:
            raise RuntimeError(
                f"no {name} span among this run's events, and the "
                "program's ring has dropped some of them: the build may "
                "have been among those")
        return None
    if len(builds) != 1:
        raise RuntimeError(
            f"{len(builds)} {name} spans of "
            f"{[e['dur_ms'] for e in builds]} ms were emitted while this "
            "run's engine was built: which is the build?")
    return builds[0]


def build_host_seconds(run):
    """This run's structure or plan build less the ``device_wait`` spans
    under it: the span's own time and the passes' own."""
    spans = span_events(run, "build")
    build = build_span(run)
    if build is None:
        return None
    parent = {e["span_id"]: e.get("parent_span_id") for e in spans
              if "span_id" in e}
    waited = 0.0
    for e in spans:
        if e.get("name") != DEVICE_WAIT:
            continue
        up = e.get("parent_span_id")
        while up is not None and up != build["span_id"]:
            up = parent.get(up)
        if up is not None:
            waited += e["dur_ms"]
    return (build["dur_ms"] - waited) / 1e3
