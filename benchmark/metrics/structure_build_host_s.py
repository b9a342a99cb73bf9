"""Layer: structure build.  The host's own part of the plan build: the
program's ``engine_init/build_plan`` span (``DistributedEngine``) less the
``device_wait`` spans under it (fetches of a chunk's results, shard
uploads).  Durations are the spans' own, on a monotonic clock: the build
runs before the profiler starts.

Listed for the four-chip cell alone.  There the device is busy 18% of the
build and the span less its waits is host NumPy.  A one-chip
``engine_init/build_structure`` reads the same way, but is not host work:
the device is busy 82% of that build and the host sits in the chunk
programs' dispatch calls, which block once a few are queued and which no
``device_wait`` can be put around (PERF.md sections 5 and 7).  The one-chip
cells come back when set-up is traced and the metric can be the device's
idle time under the build span."""

from benchmark import program_spans


def read(run):
    return program_spans.build_host_seconds(run)
