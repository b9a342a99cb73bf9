"""The passes of a structure build, as the per-layer metrics read them: the
``ell/*`` spans that ``LocalEngine`` opens under its
``engine_init/build_structure`` span (``ell/fill``, ``ell/count`` and
``ell/stair_levels`` in the one-pass build; an ``ell/fill`` and an
``ell/stair_levels`` a table range in the range build; ``ell/count_rows``,
``ell/row_order``, ``ell/pack`` and ``ell/cut`` in the two-pass one).
Durations are the spans' own, on a monotonic clock, from this run's copy of
the program's events (``run.events``): the build runs before the profiler
starts.
"""

from . import program_spans


def pass_seconds(run, name):
    """Seconds of the passes named ``name`` of this run's build, summed (one
    in the one-pass build, one a table range in the range build); ``None``
    where the program opens no build span, or its build made no such
    pass."""
    build = program_spans.build_span(run)
    if build is None:
        return None
    passes = [e["dur_ms"] for e in program_spans.span_events(run, "build")
              if e.get("name") == name
              and e.get("parent_span_id") == build.get("span_id")]
    return sum(passes) / 1e3 if passes else None
