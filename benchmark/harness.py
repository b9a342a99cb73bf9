"""One run of one cell: set-up, warm-up, the measured window, the trace's
reduction, the comparison with the plain reference, the metrics.

Driven by data: the cell, its configuration, its traffic mix and its
metrics are looked up by name in ``BENCHMARK.json`` and in the files beside
this one (``configs/<config>.json``, ``traffic/<traffic>.json``,
``metrics/<metric>.py``).  Adding any of them edits no file that is here.
"""

import contextlib
import gc
import importlib.util
import json
import os
import shutil
import time
from types import SimpleNamespace

from . import check, peaks, trace_reduce, traffic, work
from .compiles import CompileCounter

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_benchmark(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json names no {what} {name!r}")


def load_config(bench, name):
    entry = find(bench["configs"], name, "configuration")
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


def metrics_of(bench, section, cell, reported):
    """The metrics of ``section`` that ``cell`` reports: those that list it
    under ``workloads``, and those without the key whose end-to-end metric
    (``moves``, or the metric itself) the cell reports."""
    out = []
    for m in bench[section]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in reported:
            out.append(m)
    return out


def load_reader(name):
    """``read(run)`` of ``metrics/<name>.py``."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def require_chips(devices, chips):
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"the benchmark needs a TPU; JAX found {dev.platform} "
                     f"({dev.device_kind}). Nothing was run.")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")


class Spans:
    """The harness's own host spans: name -> seconds, kept in memory."""

    def __init__(self):
        self.seconds = {}

    @contextlib.contextmanager
    def __call__(self, name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.seconds[name] = self.seconds.get(name, 0.0) \
                + time.perf_counter() - t0


def annotator(on):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax

    return lambda name: jax.profiler.TraceAnnotation(name)


def _trace_dir(workload):
    # a fixed path inside the checkout; emptied before and after
    return os.path.join(ROOT, ".cache", "benchmark", "trace-" + workload)


def _start_trace(directory):
    import jax

    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # no Python call stacks: small files
    opts.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=opts)


def run_cell(bench, workload, seed, seconds, trace, t_start,
             system_factory=None, chip_check=require_chips,
             reduce_trace=trace_reduce.reduce_directory):
    """Run ``workload`` once and return the result line's object.

    ``system_factory``, ``chip_check`` and ``reduce_trace`` are the test
    suite's way in: a rehearsal at a toy size on the CPU (whose traces hold
    no device plane), or a run with the timed path broken underneath.  The
    command passes none of them.
    """
    if system_factory is None:
        from .system import System as system_factory
    cell = find(bench["workloads"], workload, "workload")
    config = load_config(bench, cell["config"])
    mix = traffic.make(cell["traffic"], seed)
    spans = Spans()
    system = system_factory(config)

    with spans("device_start"):
        devices = system.start()
    chip_check(devices, int(cell["chips"]))
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    compiles = CompileCounter()
    start = compiles.mark()
    with spans("enumeration"):
        n_states = system.enumerate()
    with spans("engine"):
        system.build_engine()
    with spans("warm_up"):
        mix.warm_up(system, n_states)
    timers = system.timers()
    setup_compiles = compiles.since(start)
    directory = _trace_dir(workload)
    if trace:
        _start_trace(directory)
    mark = compiles.mark()
    setup_s = time.perf_counter() - t_start

    system.open_window()
    # ---- the measured window -------------------------------------------
    try:
        window = mix.window(system, seconds, annotator(trace))
    finally:
        if trace:
            import jax

            jax.profiler.stop_trace()
    # ---------------------------------------------------------------------

    window_compiles = compiles.since(mark)
    events = system.close_window()
    engine_counts = system.engine_counts()
    device["memory_peak_bytes"] = system.memory_peak_bytes()
    answers = mix.collect(system)
    system.close()
    del system
    gc.collect()

    summary = None
    if trace:
        summary = reduce_trace(directory)
        shutil.rmtree(directory, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s

    with spans("reference"):
        numbers = mix.compare(mix.reference(config), answers)
    table, correct = check.judge(numbers, mix.limits())

    end_to_end = metrics_of(bench, "end_to_end", cell, None)
    reported = {m["name"] for m in end_to_end}
    wanted = metrics_of(bench, "per_layer", cell, reported) if trace \
        else end_to_end
    run = SimpleNamespace(
        cell=cell, config=config, traffic=mix.params, window=window,
        setup_s=setup_s, spans=spans.seconds, timers=timers,
        setup_compiles=setup_compiles, window_compiles=window_compiles,
        events=events, device=device, trace=summary,
        chips=int(cell["chips"]),
        peaks=peaks.peaks_for(dev.device_kind) if trace else None,
        work=work)
    metrics = {}
    for m in wanted:
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    result = {"correct": bool(correct), "attempted": window["requests"],
              "failed": 0, "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    result["window"] = dict(window, setup_compiles=setup_compiles,
                            window_compiles=window_compiles,
                            spans=spans.seconds, timers=timers,
                            engine=engine_counts)
    result["checks"] = table
    return result
