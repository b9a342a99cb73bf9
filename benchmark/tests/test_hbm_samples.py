"""The seven readers under ``peak_hbm_gb`` (PR 37) on a copy of a run's
events built by hand, and once against the program itself at a toy size with
an allocator in the place of the CPU's missing ``memory_stats()``.  Bytes are
made up; nothing here is a device number."""

from types import SimpleNamespace

import pytest

from benchmark import harness, hbm_samples as H

GB = 10 ** 9
NAMES = ("hbm_engine_gb", "hbm_build_peak_gb", "hbm_window_rise_gb",
         "hbm_krylov_gb", "hbm_solve_resident_gb", "hbm_solve_transient_gb",
         "hbm_unattributed_gb")


def sample(tag, in_use, peak, ledger, span_id=None, synced=True, seq=0):
    return {"kind": "memory_watermark", "seq": seq, "tag": tag,
            "span_id": span_id, "synced": synced,
            "fullest": {"device": "tpu:2", "bytes_in_use": in_use,
                        "peak_bytes_in_use": peak, "bytes_limit": 16 * GB},
            "ledger": dict(ledger), "ledger_bytes": sum(ledger.values()),
            # the sum and the maximum over four devices: not what is read
            "bytes_in_use": 4 * in_use, "peak_bytes": peak}


def span(name, span_id):
    return {"kind": "span", "name": name, "span_id": span_id,
            "cat": "phase", "dur_ms": 1.0}


ENGINE = {"engine": 1_070_000_000}
SOLVE = dict(ENGINE, solver=3_920_000_000)
# a build whose fill pass set its peak, and the engine it left
BUILD = [sample("engine_init_start/local", 50_000_000, 60_000_000, {}),
         sample("ell/fill", 3_000_000_000, 3_400_000_000, {}, "b1"),
         span("ell/fill", "b1"),
         sample("engine_init/build_structure", 1_090_000_000, 3_400_000_000,
                {}, "b0"),
         span("engine_init/build_structure", "b0"),
         {"kind": "engine_init", "n_states": 4707969},
         sample("engine_init/local", 1_100_000_000, 3_400_000_000, ENGINE)]
# one solve: the buffer allocated, three block programs, the epilogue; an
# eager apply's sample and an unsynced one are not the readers'
WINDOW = [sample("lanczos/start", 5_060_000_000, 9_000_000_000, SOLVE, "w0"),
          span("lanczos/start", "w0"),
          sample("lanczos/wait", 5_100_000_000, 9_000_000_000, SOLVE, "w1"),
          span("lanczos/wait", "w1"),
          sample("lanczos/wait", 5_140_000_000, 9_400_000_000, SOLVE, "w2"),
          span("lanczos/wait", "w2"),
          sample("apply/local", 9_900_000_000, 9_400_000_000, SOLVE, "w3",
                 synced=False),
          span("apply", "w3"),
          sample("lanczos/wait", 5_120_000_000, 9_420_700_000,
                 dict(SOLVE, solver=3_900_000_000), "w4"),
          span("lanczos/wait", "w4"),
          sample("lanczos/wait", 9_999_000_000, 9_420_700_000, SOLVE, "w5",
                 synced=False),
          span("lanczos/wait", "w5"),
          sample("lanczos/epilogue", 5_200_000_000, 9_420_700_000, SOLVE,
                 "w6", synced=False),
          span("lanczos/epilogue", "w6")]
PEAK = 9_420_700_000


def run_with(build=BUILD, window=WINDOW, lost=False, peak=PEAK,
             engine="local"):
    return SimpleNamespace(
        config={"engine": {"kind": engine}},
        device={"memory_peak_bytes": peak},
        events={"build": list(build), "window": list(window), "lost": lost})


def read(name, run):
    return harness.load_reader(name)(run)


def test_each_reader_returns_what_its_docstring_says():
    run = run_with()
    assert read("hbm_engine_gb", run) == 1.07
    assert read("hbm_build_peak_gb", run) == 3.4
    assert read("hbm_window_rise_gb", run) == pytest.approx(6.0207)
    assert read("hbm_krylov_gb", run) == 3.92
    # the largest synced lanczos/wait sample, not the apply's, not the
    # unsynced wait's, not the epilogue's
    assert read("hbm_solve_resident_gb", run) == 5.14
    assert read("hbm_solve_transient_gb", run) == pytest.approx(4.2807)
    assert read("hbm_unattributed_gb", run) == pytest.approx(0.15)
    # the two sums that make up peak_hbm_gb, to the byte
    built, res = H.built(run), H.resident(run)
    assert built["fullest"]["peak_bytes_in_use"] + round(
        read("hbm_window_rise_gb", run) * GB) == PEAK
    assert res["fullest"]["bytes_in_use"] + round(
        read("hbm_solve_transient_gb", run) * GB) == PEAK


def test_a_peak_that_is_the_builds_reads_no_rise():
    run = run_with(window=[], peak=3_400_000_000)
    assert read("hbm_window_rise_gb", run) == 0.0
    assert read("hbm_build_peak_gb", run) == 3.4


def test_a_mesh_reads_its_own_engine_sample_and_adds_the_plan():
    build = [sample("plan/pack", 900_000_000, 1_500_000_000,
                    {"plan": 44_000_000}, "p1"),
             span("plan/pack", "p1"),
             sample("engine_init/distributed", 400_000_000, 1_500_000_000,
                    {"engine": 340_000_000, "plan": 2_000_000})]
    run = run_with(build=build, engine="distributed")
    assert read("hbm_engine_gb", run) == pytest.approx(0.342)
    assert read("hbm_build_peak_gb", run) == 1.5
    # the other engine's tag is not this run's
    assert read("hbm_engine_gb", run_with(build=build)) is None


@pytest.mark.parametrize("name", NAMES)
def test_events_without_samples_read_nothing(name):
    """A CPU rehearsal (no ``memory_stats()``: no sample), and a program
    from before PR 37 (samples without ``fullest``)."""
    spans_only = [e for e in BUILD + WINDOW if e["kind"] != "memory_watermark"]
    assert read(name, run_with(build=spans_only, window=spans_only)) is None
    old = [{k: v for k, v in e.items()
            if k not in ("fullest", "ledger", "ledger_bytes", "synced")}
           for e in BUILD + WINDOW]
    assert read(name, run_with(build=old, window=old)) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_ring_that_lost_the_sample_raises(name):
    with pytest.raises(RuntimeError, match="has dropped some"):
        read(name, run_with(build=[], window=[], lost=True))
    # what is found is read, lost events or not
    assert read(name, run_with(lost=True)) is not None


def test_two_engines_built_in_one_set_up_raise():
    with pytest.raises(RuntimeError, match="which engine"):
        H.built(run_with(build=BUILD + BUILD[-1:]))


def test_the_program_takes_the_samples_the_readers_go_by(monkeypatch,
                                                         tmp_path):
    """A toy build and solve with an allocator that counts live arrays:
    the tags, the spans and the ledger's owners are the program's own."""
    import jax

    from benchmark.system import System
    from conftest import ring_yaml
    from distributed_matvec_tpu.obs import memory as obs_memory

    peak = [0]

    def stats():
        dev = jax.local_devices()[0]
        held = {}
        for arr in jax.live_arrays():
            for sh in arr.addressable_shards:
                if sh.device == dev:
                    held[sh.data.unsafe_buffer_pointer()] = sh.data.nbytes
        peak[0] = max(peak[0], sum(held.values()))
        return [{"device": f"{dev.platform}:{dev.id}",
                 "bytes_in_use": sum(held.values()),
                 "peak_bytes_in_use": peak[0], "bytes_limit": 16 * GB}]

    monkeypatch.setattr(obs_memory, "_device_stats", stats)
    system = System({"model": ring_yaml(tmp_path / "ring.yaml", 16),
                     "engine": {"kind": "local", "mode": "ell"}})
    system.start()
    n = system.enumerate()
    system.build_engine()
    system.open_window()
    system.solve({"k": 1, "tol": 1e-8, "max_iters": 64,
                  "max_basis_size": 32, "eigenvectors": True})
    events = system.close_window()
    stats()
    run = SimpleNamespace(config=system.config, events=events,
                          device={"memory_peak_bytes": peak[0]})
    values = {name: read(name, run) for name in NAMES}
    assert all(v is not None for v in values.values()), values
    assert values["hbm_engine_gb"] > 0
    assert values["hbm_krylov_gb"] * GB == pytest.approx(40 * n * 8, abs=512)
    assert values["hbm_unattributed_gb"] >= 0
    assert values["hbm_solve_resident_gb"] >= values["hbm_krylov_gb"] \
        + values["hbm_engine_gb"]
    assert values["hbm_build_peak_gb"] + values["hbm_window_rise_gb"] \
        == pytest.approx(peak[0] / GB, abs=1e-12)
    assert values["hbm_solve_resident_gb"] \
        + values["hbm_solve_transient_gb"] == pytest.approx(peak[0] / GB,
                                                            abs=1e-12)
