"""The trace reduction, on synthetic events and on two recorded TPU traces
(TPU v5 lite, PR 25: two applies of a 20-site ring, two applies of
chain_32_symm)."""

import pytest

from benchmark import trace_reduce as T


def test_union_merges_nested_and_overlapping():
    assert T._union([(0, 10), (2, 3), (5, 12), (20, 21)]) == \
        [[0, 12], [20, 21]]
    assert T._length(T._clip([(0, 12), (20, 21)], 10, 20.5)) == 2.5


def test_self_time_subtracts_direct_children_only():
    events = [("while", 0, 100), ("a", 10, 20), ("b", 40, 50),
              ("b_inner", 45, 10), ("after", 100, 5)]
    own = {e[0]: t for e, t in T._self_times(events)}
    assert own == {"while": 30, "a": 20, "b": 40, "b_inner": 10, "after": 5}


@pytest.mark.parametrize("text, want", [
    ("%fusion.18 = f32[4718592,3]{0,1:T(4,128)} fusion(f32[4707969,3]"
     "{0,1:T(4,128)S(1)} %copy-done.1, s32[4718592]{0:T(1024)S(1)} %b.1), "
     "kind=kCustom, calls=%fused_computation.18",
     "fusion f32[4718592,3](f32[4707969,3],s32[4718592])"),
    ("%f.4 = (f32[8]{0:T(1024)}, f32[8]{0:T(1024)}, /*index=2*/f32[8]{0}) "
     "fusion(f32[3,8]{1,0} %c.7), kind=kLoop",
     "fusion (3 x f32[8])(f32[3,8])"),
    ("%all-to-all.3 = f64[4,128]{1,0} all-to-all(f64[4,128]{1,0} %x)",
     "all-to-all f64[4,128](f64[4,128])"),
    ("not hlo text", "not hlo text"),
])
def test_signature(text, want):
    assert T.signature(text) == want


def test_collectives_are_recognised():
    assert T.COLLECTIVE.search("%all-to-all.3 = f64[4] all-to-all(f64[4])")
    assert T.COLLECTIVE.search("%ar = f64[] all-reduce-start(f64[] %x)")
    assert not T.COLLECTIVE.search("%fusion.1 = f32[8] fusion(f32[8] %x)")


def test_recorded_small_trace(recorded_trace):
    s = T.reduce_file(recorded_trace("ring20_apply"))
    assert len(s.devices) == 1 and s.fullest.index == 0
    # two annotated applies 50 ms apart; only the second run's program lies
    # wholly inside the annotations' span... both do: two runs
    seconds, runs = s.fullest.module_runs(r"jit_apply_fn")
    assert runs >= 1 and 0 < seconds <= s.busy_s + 1e-12
    assert 0 < s.busy_s < s.window_s
    assert s.window_s == pytest.approx(0.0531, abs=1e-3)
    b = s.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(sec >= 0 for _, sec in b["device_ops"] + b["idle_gaps"])


def test_recorded_full_size_trace(recorded_trace):
    s = T.reduce_file(recorded_trace("chain32_apply"))
    seconds, runs = s.fullest.module_runs(r"jit_apply_fn")
    assert runs == 2
    # read by hand from the trace: each run's XLA Modules event lasts
    # 0.65534 s and its operations fill it
    assert seconds / runs == pytest.approx(0.65533, abs=2e-4)
    assert s.busy_s == pytest.approx(1.31065, abs=1e-4)
    # own times add up to the busy union: nothing is counted twice
    assert sum(sec for sec, _ in s.fullest.own.values()) == \
        pytest.approx(s.busy_s, rel=1e-6)
    name, sec = s.breakdown()["device_ops"][0]
    assert name == "fusion f32[4718592,3](f32[4707969,3],s32[4718592]) x40"
    assert sec == pytest.approx(1.1425, abs=1e-3)
    # the probe slept 50 ms between its two applies: that is the idle gap
    assert s.window_s - s.busy_s == pytest.approx(0.0538, abs=2e-3)
    label, gap = s.breakdown()["idle_gaps"][0]
    assert "jit_apply_fn -> jit_apply_fn" in label
    assert s.boundary_seconds(r"jit_apply_fn") == 0.0   # one run a request


def test_a_trace_with_no_device_plane_is_refused(tmp_path):
    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="no operation on any device"):
        T.reduce_directory(str(tmp_path))
