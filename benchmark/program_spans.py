"""The program's own spans, as the per-layer metrics of PR 26 read them.

Two sources, both written by ``distributed_matvec_tpu/obs/trace.py::span``:

* the profiler's host line (``run.trace.host``): a span of a leaf kind holds a
  ``TraceAnnotation`` of its name, so it lies on the clock of the device's
  operations.  Times come from here: the idle time of the fullest device
  (``run.trace.fullest.busy``) under the spans of a name.
* the program's in-memory event store (``obs.events.events("span")``): one
  event a closed span, with its parent, its monotonic duration and the counts
  the program added while it was open.  Counts come from here, and the times
  of set-up, which runs before the profiler starts.

Every function returns ``None`` (or an empty list) where it finds nothing: a
program without these spans, or a CPU rehearsal whose trace has no device.
Where the store shows that what the reader needs may have been there and is
gone (it keeps the newest 65,536 events of every kind), or holds a build that
is not this run's, the reader raises: a wrong number is worse than none.
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


def program_events():
    """The program's in-memory event store, oldest first; [] for a program
    that keeps none."""
    try:
        from distributed_matvec_tpu.obs.events import events
    except ImportError:
        return []
    return events()


def span_events():
    """The program's closed spans, oldest first."""
    return [e for e in program_events() if e.get("kind") == "span"]


def dropped_events():
    """How many of its oldest events the store has let go (the first kept
    event's ``seq``; the program counts them from 0)."""
    kept = program_events()
    return int(kept[0].get("seq", 0)) if kept else 0


def window_solves(run):
    """The root ``lanczos`` spans of the window's solves: the last
    ``run.window["solves"]`` of the store.  ``None`` where there are fewer,
    or where they carry no counts."""
    solves = [e for e in span_events()
              if e.get("name") == "lanczos" and e.get("cat") == "solve"]
    n = int(run.window.get("solves") or 0)
    if n and len(solves) < n and dropped_events():
        raise RuntimeError(
            f"the program's span store holds {len(solves)} of the window's "
            f"{n} solves and has dropped {dropped_events()} older events")
    if not n or len(solves) < n:
        return None
    solves = solves[-n:]
    if not all(key in e for e in solves for key in COUNTS):
        return None
    return solves


def build_span(run, spans):
    """The span of the build that this run's set-up made: the one of the
    engine's name whose duration is what the engine's own timer read at
    set-up (``run.timers``; both bracket the same ``with``).  ``None``
    for a program that opens no such span."""
    name = BUILDS.get(run.config["engine"]["kind"])
    builds = [e for e in spans if e.get("name") == name]
    if not builds:
        if dropped_events():
            raise RuntimeError(
                f"no {name} span in the program's span store, which has "
                f"dropped {dropped_events()} older events: this run's "
                "build may have been among them")
        return None
    timed_ms = 1e3 * run.timers["structure_build_s"]
    mine = [e for e in builds
            if abs(e["dur_ms"] - timed_ms) <= max(0.05 * timed_ms, 2.0)]
    if len(mine) != 1:
        raise RuntimeError(
            f"{len(builds)} {name} spans of "
            f"{[e['dur_ms'] for e in builds]} ms, and the engine's timer "
            f"read {timed_ms:.1f} ms at set-up: none, or more than one, is "
            "this run's build")
    return mine[0]


def build_host_seconds(run):
    """This run's structure or plan build less the ``device_wait`` spans
    under it: the span's own time and the passes' own."""
    spans = span_events()
    build = build_span(run, spans)
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
