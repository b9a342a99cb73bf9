"""``correct`` comes out false when it should: the control (the answers in
float32), and a run with the timed path broken underneath, at a toy size on
the CPU.  The same controls were read on the chip at the cells' own size
(PERF.md, PR 25); these are the copies a test run can hold."""

import time

import numpy as np
import pytest

from benchmark import check, harness, traffic

NO_CHECK = dict(chip_check=lambda devices, chips: None)


def _run(toy_bench, system, workload, seed=23):
    return harness.run_cell(toy_bench, workload, seed, 0.2, False,
                            time.perf_counter(), system_factory=system,
                            **NO_CHECK)


def _over(res):
    return {k for k, row in res["checks"].items()
            if not row["value"] <= row["limit"]}


def _answers(toy_bench, toy_system, workload, seed):
    """A sound window's answers, its mix and its reference."""
    cell = harness.find(toy_bench["workloads"], workload, "workload")
    config = harness.load_config(toy_bench, cell["config"])
    system = toy_system(config)
    system.start()
    n = system.enumerate()
    system.build_engine()
    mix = traffic.make(cell["traffic"], seed)
    mix.warm_up(system, n)
    mix.window(system, 0.0, harness.annotator(False))
    answers = mix.collect(system)
    return mix, mix.reference(config), answers


@pytest.mark.parametrize("seed", [1, 2_147_483_659, 4_000_000_007])
def test_apply_control_is_not_correct(toy_bench, toy_system, seed):
    mix, ref, answers = _answers(
        toy_bench, toy_system, "chain_32_symm.apply", seed)
    sound, ok = check.judge(mix.compare(ref, answers), mix.limits())
    assert ok and sound["apply_err_over_tol"]["value"] < 0.5
    table, ok = check.judge(mix.compare(ref, mix.control(ref, answers)),
                            mix.limits())
    assert not ok
    assert table["apply_err_over_tol"]["value"] > 1e3


@pytest.mark.parametrize("seed", [1, 2_147_483_659, 4_000_000_007])
def test_ground_state_control_is_not_correct(toy_bench, toy_system, seed):
    mix, ref, answers = _answers(
        toy_bench, toy_system, "chain_32_symm.ground_state", seed)
    sound, ok = check.judge(mix.compare(ref, answers), mix.limits())
    assert ok, sound
    table, ok = check.judge(mix.compare(ref, mix.control(ref, answers)),
                            mix.limits())
    assert not ok
    assert table["residual_over_tol"]["value"] > \
        30 * sound["residual_over_tol"]["value"]
    assert table["e0_rel_err"]["value"] > table["e0_rel_err"]["limit"]


def test_half_of_the_rows_left_out(toy_bench, toy_system):
    class Half(toy_system):
        def apply(self, xd):
            y = super().apply(xd)
            return y.at[y.shape[0] // 2:].set(0.0)

    res = _run(toy_bench, Half, "chain_32_symm.apply")
    assert res["correct"] is False
    assert _over(res) == {"apply_err_over_tol"}


def test_an_apply_that_returns_its_input_unchanged(toy_bench, toy_system):
    class Unchanged(toy_system):
        def apply(self, xd):
            return xd

    res = _run(toy_bench, Unchanged, "chain_32_symm.apply")
    assert res["correct"] is False
    assert _over(res) == {"apply_err_over_tol"}


def test_an_apply_altered_where_it_is_produced(toy_bench, toy_system):
    class Altered(toy_system):
        def apply(self, xd):
            return super().apply(xd) * (1.0 + 1e-9)

    res = _run(toy_bench, Altered, "chain_32_symm.apply")
    assert res["correct"] is False


def test_a_basis_with_a_state_missing(toy_bench, toy_system):
    class Short(toy_system):
        def to_block(self, y):
            return super().to_block(y)[:-1]

    res = _run(toy_bench, Short, "chain_32_symm.apply")
    assert res["correct"] is False
    assert "basis_size_diff" in _over(res)


@pytest.mark.parametrize("what, over", [
    ("eigenvalue", {"e0_rel_err", "residual_over_tol"}),
    ("vector", {"residual_over_tol"}),
    ("unconverged", {"unconverged_solves"}),
])
def test_a_solve_altered_where_it_is_produced(toy_bench, toy_system, what,
                                              over):
    class Altered(toy_system):
        def solve(self, params, start_seed=None):
            s = super().solve(params, start_seed)
            if what == "eigenvalue":
                s.eigenvalue *= 1.0 + 1e-8
            elif what == "unconverged":
                s.converged = False
            else:
                keep = s.vector

                def vector():
                    v = keep()
                    bump = 1e-7 * np.sin(np.arange(v.size))
                    return (v + bump) / np.linalg.norm(v + bump)
                s.vector = vector
            return s

    res = _run(toy_bench, Altered, "chain_32_symm.ground_state")
    assert res["correct"] is False
    assert over <= _over(res), res["checks"]


def test_the_exchange_left_out(toy_bench, toy_system, monkeypatch):
    """Four (virtual) chips whose all_to_all hands every chip its own rows
    back: each shard then multiplies with what it already had."""
    import jax

    monkeypatch.setattr(
        jax.lax, "all_to_all",
        lambda x, axis_name, split_axis, concat_axis, **kw: x)
    try:
        res = _run(toy_bench, toy_system, "chain_32_symm_x4.ground_state")
    except Exception as e:      # a solve that fails outright is no answer
        pytest.skip(f"the broken exchange stopped the solver itself: {e!r}")
    assert res["correct"] is False
    assert "residual_over_tol" in _over(res) or "e0_rel_err" in _over(res)


def test_a_nan_is_over_its_limit():
    table, ok = check.judge({"x": float("nan")}, {"x": 1.0})
    assert not ok
    with pytest.raises(KeyError):
        check.judge({"no_limit": 0.0}, {})
