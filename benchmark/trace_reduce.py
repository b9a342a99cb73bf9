"""From a profiler trace (``.xplane.pb``) to the numbers the metrics read.

Read with ``jax.profiler.ProfileData`` and nothing else.  What the first TPU
trace of this repository held (PR 25, TPU v5 lite, read by hand before this
code was trusted; a copy is ``tests/data/ring20_apply.xplane.pb.gz``):

* one plane per chip, ``/device:TPU:<n>``, with the lines ``XLA Modules``
  (one event per program run, named ``jit_<function>(<fingerprint>)``),
  ``XLA Ops`` (one event per HLO operation, named by its whole HLO text;
  the body of a ``while`` nests inside the ``while`` event) and
  ``Async XLA Ops`` (copies in flight beside the operations);
* the plane ``/host:CPU`` with a line per thread; the main thread's line
  (``python``, ``python3``) holds the harness's ``TraceAnnotation`` spans (``bench/...``), JAX's
  ``PjitFunction(<name>)`` calls and its compile and lowering spans;
* both on one clock, in nanoseconds from the start of the trace.

Busy time is the union of the ``XLA Ops`` intervals, so nested and
overlapping events count once; an operation's own time is its duration less
that of the events nested directly in it.
"""

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE, HOST_LINE = "/host:CPU", "python"
ANNOTATION = "bench/"
COLLECTIVE = re.compile(
    r"\b(all-to-all|all-reduce|all-gather|reduce-scatter|"
    r"collective-permute|collective-broadcast)(-start|-done)?\b")
_SHAPE = re.compile(r"\{[^{}]*\}|/\*[^*]*\*/")
_OPCODE = re.compile(r"^\s*(\([^=]*\)|\S+)\s+([a-z][a-z0-9\-_]*)\(")


def _fold(result):
    """``(f32[8], f32[8], f32[8])`` as ``(3 x f32[8])``."""
    parts = [p.strip() for p in result.strip("()").split(", ")]
    if len(parts) > 1 and len(set(parts)) == 1:
        return f"({len(parts)} x {parts[0]})"
    return result


def signature(name):
    """A short, stable name for an HLO operation's text: its opcode with
    the shapes of its result and operands, layouts and numbering dropped,
    so that the twenty gathers of one apply are one entry."""
    if " = " not in name:
        return name[:120]
    lhs, rhs = name.split(" = ", 1)
    rhs = _SHAPE.sub("", rhs)
    m = _OPCODE.match(rhs)
    if not m:
        return lhs[:120]
    result, opcode = _fold(m.group(1)), m.group(2)
    args = rhs[m.end():]
    depth, end = 1, len(args)
    for i, ch in enumerate(args):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            end = i
            break
    operands = re.findall(r"([a-z]+[0-9]*\[[0-9,]*\])", args[:end])
    if len(operands) > 6:
        operands = operands[:6] + [f"+{len(operands) - 6}"]
    custom = re.search(r'custom_call_target="([^"]+)"', rhs)
    tail = f" {custom.group(1)}" if custom else ""
    text = f"{opcode} {result}({','.join(operands)}){tail}"
    return text if len(text) <= 160 else text[:157] + "..."


def _union(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _length(intervals):
    return sum(b - a for a, b in intervals)


def _self_times(events):
    """[(event, own ns)]: duration less the events nested directly in it."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    own = [e[2] for e in order]
    stack = []
    for i, (_, start, dur) in enumerate(order):
        while stack and start >= order[stack[-1]][1] + order[stack[-1]][2]:
            stack.pop()
        if stack:
            own[stack[-1]] -= dur
        stack.append(i)
    return [(order[i], max(own[i], 0.0)) for i in range(len(order))]


class DeviceTrace:
    """One chip's part of the trace, clipped to the window."""

    def __init__(self, index, ops, modules, lo, hi):
        self.index = index
        inside = [(n, s, d) for n, s, d in ops if s + d > lo and s < hi]
        self.busy = _union(_clip([(s, s + d) for _, s, d in inside], lo, hi))
        self.busy_s = _length(self.busy) / 1e9
        self.modules = sorted(
            [(n, s, d) for n, s, d in modules if s + d > lo and s < hi],
            key=lambda m: m[1])
        self._starts = [m[1] for m in self.modules]
        self.own = {}           # signature -> [seconds, count]
        self.by_module = {}     # module name -> busy seconds inside it
        self.collective_s = 0.0
        for (name, start, dur), own in _self_times(inside):
            sig = signature(name)
            row = self.own.setdefault(sig, [0.0, 0])
            row[0] += own / 1e9
            row[1] += 1
            if COLLECTIVE.search(name):
                self.collective_s += own / 1e9
        for name, start, dur in self.modules:
            span = _length(_clip(self.busy, start, start + dur)) / 1e9
            self.by_module[name] = self.by_module.get(name, 0.0) + span

    def module_at(self, t):
        """Names of the programs that ran last before ``t`` and first
        after it."""
        i = bisect.bisect_right(self._starts, t)
        prev = self.modules[i - 1][0] if i else None
        nxt = self.modules[i][0] if i < len(self.modules) else None
        return prev, nxt

    def module_runs(self, pattern):
        """(busy seconds, runs) of the programs whose name matches."""
        rx = re.compile(pattern)
        runs = sum(1 for n, _, _ in self.modules if rx.search(n))
        secs = sum(s for n, s in self.by_module.items() if rx.search(n))
        return secs, runs


def _short_module(name):
    return re.sub(r"\(\d+\)$", "", name or "none")


class TraceSummary:
    def __init__(self, lo, hi, devices, host, marks=()):
        self.lo, self.hi = lo, hi
        self.marks = sorted(marks)  # the harness's annotated requests
        self.window_s = (hi - lo) / 1e9
        self.devices = devices
        self.host = host            # [(name, start, end)] of the python line
        if not devices or not any(d.busy_s > 0 for d in devices):
            raise ValueError("the trace holds no operation on any device")
        self.busy_s = sum(d.busy_s for d in devices) / len(devices)
        self.fullest = max(devices, key=lambda d: d.busy_s)

    def gaps(self):
        """[(seconds, label)] of the fullest device's idle stretches in the
        window: what the host was doing (the span of its ``python`` line
        that covers most of the gap) and between which programs."""
        dev = self.fullest
        edges = [self.lo] + [t for ab in dev.busy for t in ab] + [self.hi]
        out = []
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a <= 0:
                continue
            best, cover = "no host span", 0.0
            for name, s, e in self.host:
                if name.startswith(ANNOTATION):
                    continue
                c = min(e, b) - max(s, a)
                if c > cover:
                    best, cover = name, c
            prev, nxt = dev.module_at((a + b) / 2)
            out.append(((b - a) / 1e9,
                        f"{best} [{_short_module(prev)} -> "
                        f"{_short_module(nxt)}]"))
        return out

    def boundary_seconds(self, pattern):
        """Idle seconds of the fullest device between one run of a program
        whose name matches ``pattern`` and the next such run inside the
        same annotated request: a solver's block boundaries (Ritz solve,
        convergence check, the next block's dispatch or load)."""
        rx = re.compile(pattern)
        dev, total = self.fullest, 0.0
        for lo, hi in self.marks or [(self.lo, self.hi)]:
            runs = [(s, s + d) for n, s, d in dev.modules
                    if rx.search(n) and s >= lo and s + d <= hi]
            for (_, end), (start, _) in zip(runs, runs[1:]):
                if start > end:
                    total += (start - end
                              - _length(_clip(dev.busy, end, start))) / 1e9
        return total

    def breakdown(self, top=10):
        ops = sorted(self.fullest.own.items(), key=lambda kv: -kv[1][0])
        by_label = {}
        for secs, label in self.gaps():
            by_label[label] = by_label.get(label, 0.0) + secs
        gaps = sorted(by_label.items(), key=lambda kv: -kv[1])
        return {"device_ops": [[f"{n} x{c}", s] for n, (s, c) in ops[:top]],
                "idle_gaps": [[n, s] for n, s in gaps[:top]]}


def reduce_file(path):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    raw, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: [(e.name, e.start_ns, e.duration_ns)
                               for e in ln.events]
                     for ln in plane.lines
                     if ln.name in (OPS_LINE, MODULES_LINE)}
            raw[int(m.group(1))] = lines
        elif plane.name == HOST_PLANE:
            # the thread that made the harness's annotations; it is named
            # after the interpreter ("python", "python3")
            for ln in plane.lines:
                events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in ln.events]
                if any(n.startswith(ANNOTATION) for n, _, _ in events) or (
                        not host and ln.name.startswith(HOST_LINE)):
                    host = events
    marks = [(s, e) for n, s, e in host if n.startswith(ANNOTATION)]
    if marks:
        lo, hi = min(s for s, _ in marks), max(e for _, e in marks)
    else:
        spans = [(s, s + d) for lines in raw.values()
                 for _, s, d in lines.get(OPS_LINE, [])]
        if not spans:
            raise ValueError("the trace holds no operation on any device")
        lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    devices = [DeviceTrace(i, lines.get(OPS_LINE, []),
                           lines.get(MODULES_LINE, []), lo, hi)
               for i, lines in sorted(raw.items())]
    devices = [d for d in devices if d.busy_s > 0]
    # keep the spans long enough to explain a gap; the python line of a
    # long solve holds thousands of microsecond-long calls
    host = [h for h in host if h[2] - h[1] >= 1e5]
    return TraceSummary(lo, hi, devices, host, marks)


def reduce_directory(directory):
    """Reduce the newest ``.xplane.pb`` under a ``jax.profiler`` trace
    directory."""
    found = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return reduce_file(found[-1])
