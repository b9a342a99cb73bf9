"""The passes of a structure build, as the per-layer metrics of PR 32 read
them: the ``ell/*`` spans that ``LocalEngine`` opens under its
``engine_init/build_structure`` span (``ell/fill``, ``ell/count`` and
``ell/stair_levels`` in the one-pass build; ``ell/count_rows``,
``ell/row_order``, ``ell/pack`` and ``ell/cut`` in the two-pass one).
Durations are the spans' own, on a monotonic clock, from the program's
in-memory event store: the build runs before the profiler starts.
"""

from . import program_spans


def pass_seconds(run, name):
    """Seconds of the pass ``name`` of this run's build; ``None`` where the
    program opens no build span, or its build made no such pass."""
    spans = program_spans.span_events()
    build = program_spans.build_span(run, spans)
    if build is None:
        return None
    passes = [e["dur_ms"] for e in spans if e.get("name") == name
              and e.get("parent_span_id") == build.get("span_id")]
    return sum(passes) / 1e3 if passes else None
